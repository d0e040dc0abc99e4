"""Mean host time per request in the program's `kb.assign_all` spans
(the whole store downloaded, assigned to the archetypes and the
assignments brought back; the store's upload inside it), in ms."""
from chipbench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "kb.assign_all")
