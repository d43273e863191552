"""Set-up: process start to the first timed chunk (world, KB build,
registration, compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
