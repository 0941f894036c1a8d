"""ftbench's own tests: the benchmark's arithmetic, its files and its CPU
rehearsal.  Run from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest ftbench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the tests that lay two replica groups of two chips over virtual devices run
# in this process; tier-1's own conftest asks for eight too, before this one
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
