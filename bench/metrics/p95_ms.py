"""95th percentile of the latency from the instant a request was due to
its answer on the host, over every request of the window (ms)."""

from bench.readers import latencies_s, percentile


def read(run):
    v = percentile(latencies_s(run), 95)
    return None if v is None else 1e3 * v
