"""Host seconds of ``api.partition`` (ending in ``block_until_ready``)
inside set-up."""


def read(run):
    return run.partition_s
