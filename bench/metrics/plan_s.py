"""Seconds inside the system's ``Session.register``: parse, decomposition,
used-KB pruning, closures, KB statistics and plan compilation (host)."""


def read(run):
    return run.plan_s
