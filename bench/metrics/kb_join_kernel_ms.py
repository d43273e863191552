"""Device milliseconds per chunk in the KB-join kernels (``hash_join_*``
ops of the trace, every chip)."""
from bench.roofline import KB_JOIN_KERNELS


def read(run):
    if run.trace is None:
        return None
    total = sum(s for per in run.trace["kernel_s"].values()
                for k, s in per.items() if k in KB_JOIN_KERNELS)
    if total <= 0:
        return None
    return 1e3 * total / len(run.window.recs)
