"""Roofline share of the set-attention forward kernel: the least time the
ingested intervals' attention needs, over the kernel's device time, %."""


def read(run):
    return run.roofline("set_attention", "set_attention_pallas")
