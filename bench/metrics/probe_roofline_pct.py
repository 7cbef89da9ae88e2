"""Share of the HBM roofline the range probe kernel reaches.

The least time of a range batch is its least bytes over the chip's
published HBM bandwidth (``bench/peaks.json``): every object of the
tiles whose partition box meets a query of the batch, 16 bytes each
and counted once, plus the batch's query boxes and answers
(``yardstick.range_batch_bytes``).  The time spent is the device time
of the probe kernel's operations inside the batch's ``bench.range_*``
span.  The operations have no term: the chip publishes no peak for
the vector unit's compares, so the bound is the bytes'."""
from bench.peaks import peaks
from bench.xplane import per_span
from bench.yardstick import range_batch_bytes

KINDS = ("range_counts", "range_ids")


def is_probe(name: str) -> bool:
    """The probe kernel's device operations: on the range path the
    ``range_probe`` Pallas kernel is the only one (``tpu_custom_call``
    in the operation's HLO text)."""
    return "tpu_custom_call" in name


def read(run):
    if run.trace is None or not run.batches:
        return None
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    _, first = run.tile_objects()
    least = spent = 0.0
    for kind in KINDS:
        batches = [b for b in run.batches if b[0] == kind]
        for (_, qboxes, n_answers), ns in zip(
                batches, per_span(run.trace, "bench." + kind, is_probe)):
            if ns > 0:
                least += range_batch_bytes(qboxes, n_answers, run.part_boxes,
                                           run.part_valid, first) / bw
                spent += ns * 1e-9
    return 100.0 * least / spent if spent else None
