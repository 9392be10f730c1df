"""Scheduler: real images over padded rows, over the waves dispatched in
the window (%)."""

from bench.readers import waves_in_window


def read(run):
    waves = waves_in_window(run)
    rows = sum(w["batch"] for w in waves)
    return 100.0 * sum(w["n"] for w in waves) / rows if rows else None
