"""The benchmark's CPU tests: `python -m pytest bench/tests`.

They run the harness at tiny sizes on the CPU, where JAX is held."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
