"""Set-up: from the start of the process to the first timed request --
JAX start-up, weights, planning, binding, warm-up of the cell's programs."""

def read(run):
    return run.setup_s
