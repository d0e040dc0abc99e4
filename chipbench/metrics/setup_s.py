"""Set-up seconds: process start to the start of the window (loading,
weights, traffic, base store, warm-up and any compilation)."""


def read(run):
    return run.setup_s
