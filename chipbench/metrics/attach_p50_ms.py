"""Median attach request latency of the traced window, in ms."""


def read(run):
    return run.percentile(50)
