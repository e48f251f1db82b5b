"""Test harness: force an 8-virtual-device CPU platform.

This is the TPU analog of the reference's parts>GPUs trick (numParts =
numMachines*numGPUs, gnn.cc:61-63, lets distributed code paths run on one
box): XLA's host platform is split into 8 virtual devices so every
mesh/collective path is exercised on CPU-only CI.  The platform is pinned
through jax.config as well as the flag, so the suite runs on the CPU even
on a machine that holds a chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "").split()
_flags.append("--xla_force_host_platform_device_count=8")
# XLA:CPU hard-kills the process (rendezvous.cc "Termination timeout ...
# Exiting") when a collective's device threads skew more than 40 s apart
# — on a 1-core box running 8 virtual devices over 1e8-edge shards that
# skew is routine, and the giant scale-guard programs aborted
# intermittently (~50%) until these were raised.  Pre-set values win
# (only appended when absent), so an operator can still tighten them.
for _d in ("--xla_cpu_collective_call_terminate_timeout_seconds=1200",
           "--xla_cpu_collective_call_warn_stuck_timeout_seconds=120"):
    if not any(f.startswith(_d.split("=")[0]) for f in _flags):
        _flags.append(_d)
os.environ["XLA_FLAGS"] = " ".join(_flags)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compile cache: the fast lane is dominated by XLA compiles of
# the sharded train steps (one-core box, ~70% of a cold 435 s run);
# repeated runs — the common case for a developer and the driver alike —
# hit the cache (README §Testing).  Keyed by HLO hash, so a code change
# that alters a program recompiles exactly that program.  Same location
# rule as every entry point (roc_tpu/cache.py);
# ROC_TEST_NO_COMPILE_CACHE=1 opts out (cold-timing runs).
if not os.environ.get("ROC_TEST_NO_COMPILE_CACHE"):
    from roc_tpu import cache  # noqa: E402

    cache.enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-epoch end-to-end runs (golden curves)")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def lock_witness():
    """Arm the runtime lock-order witness for one test: locks created
    inside the test become recording proxies, and at teardown every
    observed (outer, inner) acquisition pair must be an edge of the
    static graph in roc_tpu/analysis/threads.json.  The threaded suites
    (serve/delta/stream/fleet) wrap this in an autouse fixture, which is
    what pins the analyzer sound against reality, not just fixtures."""
    from roc_tpu.analysis import witness
    witness.reset()
    witness.arm(True)
    yield witness
    violations = witness.validate()
    witness.arm(False)
    witness.reset()
    assert violations == [], violations
