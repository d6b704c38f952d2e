"""Tests of the benchmark's own code.  Run by hand:

    python -m pytest benchmark/tests -q

They live outside ``tests/``, so the tier-1 count neither rises nor falls.
CPU only, eight virtual devices, like the repo's own tests.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
