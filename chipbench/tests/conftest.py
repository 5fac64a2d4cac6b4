"""Run by hand: `pytest chipbench/tests` (not part of `tests/`).  Everything here runs on
the CPU at the rehearsal sizes; four virtual devices stand in for the 2x2 host."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("THUNDER_TPU_PALLAS_INTERPRET", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
