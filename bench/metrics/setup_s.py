"""Set-up: process start to the first timed request (data, partitioning,
staging, warm-up of the cell's own shapes)."""


def read(run):
    return run.setup_s
