"""Scenario: batched greedy serving with KV/SSM caches.

Serves a reduced Gemma-2-style model (local+global attention, softcaps)
and a Mamba2 model (O(1) SSM state) side by side.

    PYTHONPATH=src python examples/serve_batched.py
"""
import os

from repro.core.compat import use_compile_cache
from repro.launch import serve

if __name__ == "__main__":
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    for arch in ["gemma2_27b", "mamba2_1p3b"]:
        serve.main(["--arch", arch, "--batch", "8",
                    "--prompt-len", "16", "--gen", "32"])
