"""Mean host time per request in the program's `store.compact` spans
(tombstoned rows dropped, the device gather included), in ms."""
from chipbench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "store.compact")
