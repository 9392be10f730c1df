"""Wave program glue: device time, per execution of the wave program in
the traced segment, of the ops that are neither a named
`convserve_tile_*` kernel nor an XLA convolution -- pads, masks, pools,
slices, copies (ms).  A wave's execution is the one that overlaps its
`convserve.replica.compute` span most."""

from bench.spans import glue_ms


def read(run):
    return glue_ms(run.trace)
