"""Wave program: device busy time (the union of its op intervals) per
execution of the wave program in the traced segment (ms)."""

from bench.readers import device_ms_per_wave


def read(run):
    return device_ms_per_wave(run)
