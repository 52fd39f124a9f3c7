"""The benchmark's own tests: ``python -m pytest benchmarks/tests -q``.
They run on the cpu backend and are no part of the repo's tier-1 count."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
