"""The benchmark's own tests run on the host CPU at a small size: the
device solve is forced onto JAX's CPU backend (``KBT_SOLVER=jax``) and the
sparse path onto small waves (``KBT_SOLVER_TOPK=4``), as the program's
verify notes do. Set before anything imports JAX."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("KBT_SOLVER", "jax")
os.environ.setdefault("KBT_SOLVER_TOPK", "4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
