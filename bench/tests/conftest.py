# The benchmark's tests run on the CPU wherever they are started: they never
# take a chip. The checkout root goes on the path for ``bench``, and the
# program's sources for ``repro``.
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
