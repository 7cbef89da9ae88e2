"""The paper's skew: max over mean of the objects that meet each valid
partition box, from the run's objects and the program's partitions."""
from bench.yardstick import skew_ratio


def read(run):
    overlap, _ = run.tile_objects()
    return skew_ratio(overlap, run.part_valid)
