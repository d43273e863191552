"""Binding-table high-water over ``bind_cap``, in %, of the fullest
operator: the useful share of the ``[bind_cap, scan_cap]`` work every join
pays (the system's ``hw_bind`` counter, read after the window)."""


def read(run):
    if not run.counters:
        return None
    fills = [100.0 * e["saturation"]["hw_bind"]
             for e in run.counters.values() if "hw_bind" in e["saturation"]]
    return max(fills) if fills else None
