"""Mean bytes per request that the program moved between host and device
(`h2d_bytes` plus `d2h_bytes` of its spans), in MB of 10^6 bytes."""
from chipbench import program_spans


def read(run):
    return program_spans.transfer_mb(run)
