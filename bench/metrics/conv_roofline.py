"""Kernels: the conv layers' least time on the chip (bench/work.py, at
each traced wave's padded bucket and batch) over the device time of
the conv ops (Mosaic tile kernels and XLA convolutions) in those
waves (%)."""

from bench.readers import conv_roofline


def read(run):
    return conv_roofline(run)
