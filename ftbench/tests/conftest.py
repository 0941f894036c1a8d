"""ftbench's own tests: the benchmark's arithmetic, its files and its CPU
rehearsal.  Run from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest ftbench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
