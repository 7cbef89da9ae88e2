"""Scenario: node failure mid-training → checkpoint restart.

Injects a failure at step 30 of 60; the FT runtime restores the last
checkpoint and finishes the run (watch the restart warning).

    PYTHONPATH=src python examples/fault_tolerant_train.py
"""
import logging
import os

from repro.core.compat import use_compile_cache
from repro.launch import train

logging.basicConfig(level=logging.WARNING)

if __name__ == "__main__":
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    raise SystemExit(train.main([
        "--steps", "60", "--batch", "4", "--seq", "64",
        "--ckpt-dir", "runs/ckpt_ft_demo", "--ckpt-every", "10",
        "--inject-failure-at", "30", "--log-every", "20"]))
