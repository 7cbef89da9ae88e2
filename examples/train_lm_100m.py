"""End-to-end driver: train a ~110M-param dense LM for a few hundred
steps with checkpointing + fault-tolerant restart (CPU-scaled batch; on
a pod, raise --batch/--seq and point the mesh at real devices).

    PYTHONPATH=src python examples/train_lm_100m.py [--steps 200]
"""
import os
import sys

from repro.core.compat import use_compile_cache
from repro.launch import train

if __name__ == "__main__":
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    args = sys.argv[1:] or ["--steps", "200"]
    raise SystemExit(train.main([
        "--preset", "100m", "--batch", "2", "--seq", "32",
        "--ckpt-dir", "runs/ckpt_100m", "--log-every", "20", *args]))
