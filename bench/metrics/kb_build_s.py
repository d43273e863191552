"""Seconds to make the KB on the device from the seed (one jitted call:
filler draw, composite keys, two stable sorts)."""


def read(run):
    return run.kb_build_s
