"""CPU tests of the benchmark: ``python -m pytest chipbench/tests``.

Four virtual CPU devices stand in for the four-chip host. They must be
set before JAX is first imported.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
