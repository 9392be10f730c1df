"""Runtime dispatch loop: mean gap in the traced segment from the end of
one wave's `convserve.replica.run` span to the start of the next --
completion bookkeeping, the loop's wake-up and the next dispatch (ms).
Read in the batch cells, where the next wave's requests always wait."""

from bench.spans import loop_gap_ms


def read(run):
    return loop_gap_ms(run.trace)
