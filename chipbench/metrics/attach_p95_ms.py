"""95th percentile of every attach request of the window, each from its
send to its estimate's return (and the vacuum it triggers), in ms."""


def read(run):
    return run.percentile(95)
