"""The benchmark's own tests (not part of tier-1, which runs tests/):
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
