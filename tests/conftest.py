"""Test harness configuration.

Multi-chip sharding is tested on a virtual 8-device CPU mesh: the env vars must
be set before JAX initializes its backends, which is why they live at conftest
import time rather than in a fixture.  Real-TPU runs happen through the chip
tool: ``chip_smoke.py`` (and ``bench.py``), one process per chip.
"""

import os

# Force (not setdefault: a chip machine's env names the TPU) the CPU platform
# with 8 virtual devices for hermetic sharding tests.
os.environ["JAX_PLATFORMS"] = "cpu"
# CPU test runs persist no XLA:CPU AOT artifacts (ops/__init__.py): the
# persistent compile cache is for the chip
os.environ["BQUERYD_TPU_COMPILE_CACHE"] = "0"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()


import time

import pytest


def wait_until(predicate, timeout=30.0, interval=0.05, desc="condition"):
    """Poll ``predicate`` until truthy; the framework-wide replacement for the
    reference's sleep-based test synchronization (SURVEY.md §4)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError(f"timed out after {timeout}s waiting for {desc}")


@pytest.fixture(autouse=True)
def _disarmed_chaos():
    """Disarm fault injection and zero its counters between tests: an armed
    plan (or injected-fault stats) leaking out of one test must not fire
    inside another's cluster."""
    import sys

    yield
    if "bqueryd_tpu.chaos" in sys.modules:
        sys.modules["bqueryd_tpu.chaos"]._reset_for_tests()


@pytest.fixture(autouse=True, scope="session")
def _tiny_twins_of_later_configurations():
    """``tests/benchmark/test_perf_benchmark.TINY`` names the tiny twin of
    each configuration in ``BENCHMARK.json`` and every rehearsal indexes it
    with every shipped configuration; a ``model_config`` PR edits no file
    under ``tests/benchmark/``, so the twin of a configuration it adds is
    registered here, once the session has collected that module (the test
    modules share the one dict).  Not from a ``tests/benchmark/conftest.py``:
    without packages that file takes the name ``conftest`` in
    ``sys.modules``, and ``from conftest import wait_until`` breaks in nine
    modules of this directory (PR 38)."""
    import sys

    module = sys.modules.get("test_perf_benchmark")
    if module is not None:
        module.TINY.setdefault("taxi-1chip-dollars", "taxi-tiny-dollars.json")
    yield


@pytest.fixture
def mem_store_url():
    """A fresh, flushed mem:// coordination store per test."""
    from bqueryd_tpu.coordination import coordination_store

    url = f"mem://test-{os.urandom(4).hex()}"
    store = coordination_store(url)
    store.flushdb()
    return url


# Semantic serving (PR 16) is heat-triggered: whether a repeated test query
# crosses the rollup materialization threshold depends on wall-clock cadence,
# which would make assertions about effective strategies / admission counters
# timing-dependent.  Pin it OFF suite-wide (the documented kill switch is
# bit-identical); tests/test_serving.py opts back in per test.
os.environ.setdefault("BQUERYD_TPU_SERVE", "0")

# Host-kernel routing is latency-adaptive (measured device floor); on the CPU
# test backend the floor is noisy enough to flip small fixtures between the
# host and device paths run-to-run.  Pin tests to the device path; dedicated
# host-kernel tests opt in explicitly.
os.environ.setdefault("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")

# The MXU one-hot matmul route auto-disables on CPU backends (it emulates
# far slower than the scatter there); pin it ON for the suite so the CPU
# test backend keeps exercising the MXU kernel paths (limb plans, Pallas).
os.environ.setdefault("BQUERYD_TPU_FORCE_MATMUL", "1")


@pytest.fixture
def groupby_as_accelerator(monkeypatch):
    """Let ``ops.groupby`` — and nothing else — read the backend as "tpu"
    while it traces: its float64 sums then take the accelerator's forms
    (dense at few groups, sorted above) where this CPU backend scatter-adds
    them, and the MXU route needs no force flag.  A trace made under the
    patch must not answer an unpatched call of the same shapes, nor the
    reverse, so the kernel entries and the mesh-program cache start and end
    empty.  Yields the module."""
    import sys

    import jax

    import bqueryd_tpu.ops.groupby  # noqa: F401
    from bqueryd_tpu.parallel import executor

    module = sys.modules["bqueryd_tpu.ops.groupby"]

    class AsAccelerator:
        default_backend = staticmethod(lambda: "tpu")

        def __getattr__(self, name):
            return getattr(jax, name)

    def clear():
        module._partial_tables_mm.__wrapped__.clear_cache()
        module._partial_tables_scatter.__wrapped__.clear_cache()
        executor._mesh_program.cache_clear()

    clear()
    monkeypatch.setattr(module, "jax", AsAccelerator())
    yield module
    clear()
