"""FLOPs an attach request needs (Stage-2 of its chunk plus assigning its
own rows) times requests per second, over the chip's peak, %."""


def read(run):
    return run.mfu("request")
