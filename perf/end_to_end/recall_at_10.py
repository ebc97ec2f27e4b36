"""Mean over the window's answered requests of |served ∩ reference| / k."""


def read(run):
    return run.numbers["recall_at_10"]
