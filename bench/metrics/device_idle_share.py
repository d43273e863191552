"""Share of the traced window, in %, in which the busiest chip ran no
operation (the union of its op intervals)."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"][t["busiest"]] / t["window_s"])
