"""Seconds from the first warm-up chunk to its result on the host: XLA
compilation or persistent-cache load of every program the cell runs."""


def read(run):
    return run.compile_s
