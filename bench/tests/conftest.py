"""The benchmark's own tests run on the CPU from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
