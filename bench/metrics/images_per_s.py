"""Images answered inside the window over the window's seconds."""

from bench.readers import done_in_window


def read(run):
    win = run.window
    return len(done_in_window(run)) / (win.t1 - win.t0)
