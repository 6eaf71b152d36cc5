"""Test configuration: run JAX on 8 virtual CPU devices (SURVEY.md §4.5).

Must run before jax is imported anywhere — pytest imports conftest first.
The chip is exercised separately by chip_smoke.py, not by the unit suite.
"""

import os

# force CPU whatever the surrounding environment pins: the unit suite runs
# on 8 virtual CPU devices by design
os.environ["JAX_PLATFORMS"] = "cpu"
# float64 support for the double-precision oracle parity harness
os.environ.setdefault("JAX_ENABLE_X64", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax may already have been imported by a pytest plugin, in which case the
# env vars above were read too late — force the settings through jax.config
# too (honoring an explicit env opt-out, e.g. JAX_ENABLE_X64=0 pytest).
import jax  # noqa: E402

if os.environ.get("JAX_ENABLE_X64", "1").lower() not in ("0", "false"):
    jax.config.update("jax_enable_x64", True)
jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

# validate every iterator-produced GraphBatch in the whole suite
# (SURVEY.md §5 sanitizers; the --check-invariants flag, forced on here)
from cgnn_tpu.data import invariants  # noqa: E402

invariants.enable()


# the files that take minutes start first: under ``--dist loadfile`` a file is
# one worker's from start to end, and the suite's wall time is the last
# file's end (``test_tpu_compile.py`` sorted near the end and ran alone for
# minutes after every other worker had finished)
LONGEST_FIRST = ("test_tpu_compile.py", "test_afmoe.py", "test_sdar.py",
                 "test_benchmark_harness.py", "test_entrypoints.py",
                 "test_forces.py", "test_batching.py",
                 "test_trinity_cell.py", "test_dp_ref.py", "test_ops.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.fspath.basename,
                                         len(LONGEST_FIRST)))
