"""95th percentile of the event-to-result latency over every window due in
the measured window (open loop)."""
from bench.metrics._window import quantile, window_latencies_ms


def read(run):
    lat = window_latencies_ms(run)
    return None if not lat else quantile(lat, 0.95)
