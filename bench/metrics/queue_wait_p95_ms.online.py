"""Scheduler: 95th percentile of the wait from admission to dispatch
(t_dispatch - t_admit) over the untraced window's answered requests (ms)."""

from bench.readers import due_in_window, percentile


def read(run):
    waits = [r["dispatch"] - r["admit"] for r in due_in_window(run)
             if "dispatch" in r]
    v = percentile(waits, 95)
    return None if v is None else 1e3 * v
