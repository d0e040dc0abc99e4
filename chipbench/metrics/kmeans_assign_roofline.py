"""Roofline share of the nearest-archetype kernel: the least time the
rows each attach request adds need, over the kernel's device time, %."""


def read(run):
    return run.roofline("assign", "kmeans_assign_pallas")
