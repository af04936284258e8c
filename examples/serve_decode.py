"""Batched serving demo: prefill + greedy decode on a reduced config, for a
GQA transformer AND an attention-free SSM (different cache structures).

  PYTHONPATH=src python examples/serve_decode.py
"""
import os
import pathlib
import subprocess
import sys

root = pathlib.Path(__file__).resolve().parents[1]
for arch in ("glm4-9b", "mamba2-2.7b"):
    print(f"=== {arch} (reduced config) ===")
    subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
         "--smoke", "--prompt-len", "8", "--new-tokens", "6", "--batch", "2"],
        env={**os.environ, "PYTHONPATH": str(root / "src")},
        check=True)
