"""Model step: direct-convolution operations of the images answered in
the untraced window, at their true sizes, over the window's length
times the chip's bfloat16 peak (%).  The program computes in float32
at `highest`, which takes several bfloat16 passes per product, so this
share cannot come near 100."""

from bench.readers import mfu


def read(run):
    return mfu(run)
