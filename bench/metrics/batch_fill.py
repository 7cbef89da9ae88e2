"""Admitted requests per compiled batch slot (``FrontendMetrics``)."""


def read(run):
    slots = run.frontend["batch_slots"]
    return run.frontend["batch_fill"] / slots if slots else None
