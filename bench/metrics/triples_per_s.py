"""Stream triples whose window results reached the host, over the window:
from the first chunk handed to the system to the last result on the host."""


def read(run):
    w = run.window
    return sum(r.triples for r in w.recs) / (w.t_end - w.t0)
