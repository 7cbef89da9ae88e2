"""The paper's fan-out: valid partitions whose box meets each range
query of the window, averaged over those queries."""
import numpy as np

from bench.yardstick import fanout


def read(run):
    boxes = [r.payload for r in run.reqs if r.kind.startswith("range")]
    if not boxes:
        return None
    return float(fanout(np.stack(boxes), run.part_boxes,
                        run.part_valid).mean())
