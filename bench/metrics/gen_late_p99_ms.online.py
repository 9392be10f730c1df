"""Load generator: 99th percentile of how late the benchmark submitted
each request after the instant it was due (ms)."""

from bench.readers import due_in_window, percentile


def read(run):
    late = [r["submitted"] - r["due"] for r in due_in_window(run)]
    v = percentile(late, 99)
    return None if v is None else 1e3 * v
