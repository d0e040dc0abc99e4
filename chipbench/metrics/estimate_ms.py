"""Mean host time per request in the benchmark's estimate and vacuum
spans (store + knowledge base: whole-store assignment, fingerprint,
eviction, compaction), in ms."""


def read(run):
    return run.span_ms("estimate", "vacuum")
