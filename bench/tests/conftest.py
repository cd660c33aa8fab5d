"""Self-tests of the benchmark, on the CPU: ``pytest bench/tests``.

Four host devices stand in for the four-chip mesh; the harness's modules
are imported from ``bench/`` and the program from ``src/``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
