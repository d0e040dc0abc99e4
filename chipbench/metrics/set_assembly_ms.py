"""Mean host time per request in the program's `pipeline.set_assembly`
spans (Stage-2 interval sets built on the host and padded to the batch),
in ms."""
from chipbench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "pipeline.set_assembly")
