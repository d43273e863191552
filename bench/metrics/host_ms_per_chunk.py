"""Host milliseconds per chunk inside the system's stream (``process_chunk``
or ``feed``/``drain``), the benchmark's own waiting and fetching left out."""


def read(run):
    w = run.window
    return 1e3 * w.system_s / len(w.recs)
