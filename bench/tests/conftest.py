"""The benchmark's own tests run on the CPU, with four virtual devices for
the mesh cell, at sizes a test run can hold:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
