"""The benchmark's own tests: CPU, tiny widths, nothing of tier-1's.

    python -m pytest benchmark/tests -q

They are not under ``tests/`` (tier-1 collects that), so the benchmark's
yardstick and its tests travel together under ``paths``.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
