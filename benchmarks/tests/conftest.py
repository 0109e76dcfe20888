"""The harness's own tests: CPU, run by hand, not part of tier-1.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
