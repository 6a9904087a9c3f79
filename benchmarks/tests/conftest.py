"""The yardstick's own tests run on the CPU, at the rehearsal's sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)
