"""Share of the memory roofline the KB-join kernels reach, in %: the bytes
their calls must move (operand and result shapes, ``bench/roofline.py``)
over peak HBM bandwidth, over their device time.  Memory-bound: the join
compares run on the vector unit, which has no published peak."""
from bench.roofline import KB_JOIN_KERNELS


def read(run):
    if run.trace is None or not run.kernel_bytes or run.peaks is None:
        return None
    secs = sum(s for per in run.trace["kernel_s"].values()
               for k, s in per.items() if k in KB_JOIN_KERNELS)
    nbytes = sum(run.kernel_bytes.values())
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / secs
