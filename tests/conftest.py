"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The test suite never touches a chip: sharding/collective tests run against
XLA's host platform with 8 virtual devices, and tests/test_chip_compile.py
compiles the main path's kernels for a described (not attached) v5e.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# JAX honours JAX_PLATFORMS; the config update is belt and braces for a
# caller that exported another platform (must happen before any
# computation touches a backend).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# persistent compilation cache: the STARK phase programs dominate test time
# on cold runs; cached XLA binaries make re-runs fast (where the cache
# lives: ethrex_tpu/utils/jax_cache.py).
from ethrex_tpu.utils.jax_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
# the serialized-executable store only pays off ACROSS processes (the
# in-process phase cache already amortizes within one pytest run), so
# inside the suite its serialize + round-trip validation per fresh
# compile is pure overhead — off by default; exec-cache tests opt back
# in through their own env fixtures.
os.environ.setdefault("ETHREX_EXEC_CACHE_OFF", "1")


# ---------------------------------------------------------------------------
# fault-injection hygiene: a test that installs a FaultPlan must clear it
# before returning — a leaked plan would fire nondeterministically inside
# whatever test runs next (tests/test_prover_chaos.py is the battery).
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fault_plan_guard():
    yield
    from ethrex_tpu.utils import faults

    plan = faults.active()
    faults.clear()
    if plan is not None and plan.rules:
        pytest.fail(
            "test leaked a non-empty active FaultPlan "
            f"({len(plan.rules)} rule(s)); call faults.clear() "
            "or use the faults.injected() context manager")


@pytest.fixture(autouse=True)
def _metrics_isolation():
    """Snapshot/restore the process-global METRICS registry around every
    test, so counters incremented by one test cannot leak into another's
    assertions.  The global time-series ENGINE (which samples METRICS)
    and the flight-recorder destination are reset alongside — a sampler
    or snapshot dir left configured by one test must not fire in the
    next."""
    import copy

    from ethrex_tpu.utils.metrics import METRICS

    with METRICS.lock:
        saved = (dict(METRICS.counters), dict(METRICS.gauges),
                 copy.deepcopy(METRICS.histograms), dict(METRICS.help),
                 copy.deepcopy(METRICS.lgauges),
                 copy.deepcopy(METRICS.lcounters))
    yield
    from ethrex_tpu.perf import profiler, roofline
    from ethrex_tpu.utils import snapshot, timeseries

    timeseries.ENGINE.stop(timeout=2.0)
    timeseries.ENGINE.clear()
    snapshot.configure(None)
    # perf accumulators are process-global like METRICS: reset so one
    # test's prove cannot leak stage/kernel rows into another's report
    profiler.PROFILER.reset()
    profiler.configure(None)
    roofline.ROOFLINE.reset()
    # the chain-path X-ray singleton accumulates stage-queue and
    # lifecycle state from any test that produces blocks — reset it so
    # explain_chain_path() in one test cannot see another's traffic
    from ethrex_tpu.perf.chain_path import CHAIN_PATH
    CHAIN_PATH.reset()
    with METRICS.lock:
        METRICS.counters = dict(saved[0])
        METRICS.gauges = dict(saved[1])
        METRICS.histograms = saved[2]
        METRICS.help = dict(saved[3])
        METRICS.lgauges = saved[4]
        METRICS.lcounters = saved[5]


@pytest.fixture(autouse=True)
def _close_leaked_kv_backends():
    """Close any persistent KV handle a test left open (and release its
    flock) so one leaked backend cannot wedge every later test that
    reopens the same tmp path.  Silent: leaking is untidy, not a
    failure — the handle guards make post-close access raise cleanly."""
    yield
    import sys

    persistent = sys.modules.get("ethrex_tpu.storage.persistent")
    if persistent is not None:
        persistent.close_leaked_backends()
