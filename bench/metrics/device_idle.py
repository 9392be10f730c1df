"""Device: share of the untraced window in which no op ran on the chip
(%): one less the window's waves times the traced device time per wave
of their bucket, over the window's length.  Read this way because
tracing slows the host and would inflate an idle share read from the
trace itself."""

from bench.readers import device_idle


def read(run):
    return device_idle(run)
