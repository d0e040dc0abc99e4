"""Mean host time per request in the program's `pipeline.stage2` spans
(the jitted Stage-2 call through the download of its outputs), in ms."""
from chipbench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "pipeline.stage2")
