"""The benchmark's own tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of tier-1 (ROADMAP's line names `tests/` only)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
