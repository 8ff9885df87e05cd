"""The benchmark's self-tests (not part of the repo's test suite), on the
CPU with four host devices for the data-parallel cell:

    python -m pytest -q -p no:cacheprovider bench/selftest
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
