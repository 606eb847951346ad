"""The start-up record: a model's ``init()`` and the three phases of building
a program, kept whether or not monitoring is on, read through
``monitoring.spans()``; bounded by a cap of its own; silent on a step that
builds nothing."""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.monitoring.compile import install_hooks

ROOT = Path(__file__).resolve().parent.parent
PHASES = ("compile.trace", "compile.lower", "compile")


def _mln(seed=11):
    from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr=0.1)).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf)


def _cg(seed=13):
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr=0.1)).graph_builder()
            .add_inputs("in").set_input_types(**{"in": InputType.feed_forward(4)})
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("o", OutputLayer(n_out=3, activation="softmax", loss="mcxent"), "d")
            .set_outputs("o").build())
    return ComputationGraph(conf)


CORES = {"mln": _mln, "cg": _cg}


def _xy(n=16):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, 4)).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def _step_phases(spans):
    return {s.name: s for s in spans if s.name in PHASES and s.args["program"] == "train_step"}


@pytest.mark.parametrize("core", sorted(CORES))
def test_init_and_the_steps_build_are_recorded_with_monitoring_off(core, monitoring_off):
    monitoring = monitoring_off
    install_hooks()
    model = CORES[core]().init()
    model.fit(*_xy())
    assert not monitoring.enabled() and monitoring.tracer() is None
    spans = monitoring.spans()
    assert spans == monitoring.startup().spans()
    (init,) = [s for s in spans if s.name == "setup.init"]
    assert init.args == {"leaves": len(jax.tree.leaves(model.params)),
                         "params": model.num_params()} == {"leaves": 4, "params": 4 * 8 + 8 + 8 * 3 + 3}
    assert init.end_ns > init.start_ns and init.parent is None
    # whatever init() builds (eager programs not yet in this process) names it
    assert all(s.parent == init.id for s in spans
               if s.name in PHASES and init.start_ns <= s.start_ns <= init.end_ns)
    step = _step_phases(spans)
    assert set(step) == set(PHASES)
    trace, lower, load = (step[name] for name in PHASES)
    assert all(s.end_ns > s.start_ns for s in step.values())
    # nothing is open on the thread: each phase names the one before it
    assert lower.parent == trace.id and load.parent == lower.id
    assert trace.end_ns <= lower.start_ns and lower.end_ns <= load.start_ns
    assert load.args["cache"] in ("hit", "miss")
    assert init.end_ns <= trace.start_ns


def test_under_monitoring_the_steps_build_is_the_dispatchs_child_and_compiles_once(monitoring_off):
    monitoring = monitoring_off
    install_hooks()
    model = _mln(seed=17).init()
    monitoring.enable()
    model.fit(*_xy())
    spans = monitoring.spans()
    (dispatch,) = [s for s in spans if s.name == "fit.dispatch"]
    step = _step_phases(spans)
    assert all(s.parent == dispatch.id for s in step.values())
    # one ``compile`` span a backend compile, in the start-up record only
    compiles = [s for s in spans if s.name == "compile"]
    assert monitoring.registry().get("dl4j_compiles_total").value == len(compiles)
    assert not [s for s in monitoring.tracer().spans() if s.name in PHASES]


def test_a_fit_that_builds_no_program_records_nothing(monitoring_off):
    monitoring = monitoring_off
    install_hooks()
    model = _mln(seed=19).init()
    model.fit(*_xy())
    before = monitoring.startup().events()
    model.fit(*_xy())
    model.fit(*_xy())
    assert monitoring.startup().events() == before


_CHILD = """
import json
from deeplearning4j_tpu.monitoring.compile import configure_compile_cache
configure_compile_cache()
import numpy as np
from deeplearning4j_tpu import monitoring
from tests.test_startup_spans import _mln, _xy
_mln().init().fit(*_xy())
print(json.dumps([s.args["cache"] for s in monitoring.spans()
                  if s.name == "compile" and s.args["program"] == "train_step"]))
"""


def test_a_second_process_on_the_same_cache_reads_the_step_back(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "PYTHONPATH": str(ROOT)}
    env.pop("DL4J_TPU_MONITORING", None)

    def child():
        done = subprocess.run([sys.executable, "-c", _CHILD], cwd=tmp_path, env=env,
                              capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        return json.loads(done.stdout.strip().splitlines()[-1])

    assert child() == ["miss"]
    assert child() == ["hit"]


def test_threads_that_build_at_once_keep_their_own_chains(monitoring_off):
    """Eight threads build eight programs at once under a short switch
    interval: every build is recorded whole, and each lowering and compile
    names the phase before it on its own thread."""
    monitoring = monitoring_off
    install_hooks()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda k=k: jax.jit(
            lambda a: jnp.cos(a) + k)(jnp.ones(40 + k))) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    spans = [s for s in monitoring.startup().spans() if s.args.get("program") == "<lambda>"]
    by_id = {s.id: s for s in spans}
    compiles = [s for s in spans if s.name == "compile"]
    assert len(compiles) == 8
    for c in compiles:
        lower = by_id[c.parent]
        assert lower.name == "compile.lower" and lower.tid == c.tid
        assert by_id[lower.parent].name == "compile.trace" and by_id[lower.parent].tid == c.tid


def test_past_its_cap_the_record_drops_the_oldest_and_counts_them(monitoring_off, monkeypatch):
    monitoring = monitoring_off
    install_hooks()
    with monkeypatch.context() as m:
        m.setattr(monitoring, "STARTUP_MAX_EVENTS", 4)
        monitoring.reset()
        for k in range(3):
            jax.jit(lambda a, k=k: jnp.sin(a) * (k + 2.5))(jnp.ones(3 + k))
        record = monitoring.startup()
        spans = record.spans()
        assert len(spans) == 4 and record.dropped >= 9 - 4
        assert spans[-1].name == "compile" and spans[-1].args["program"] == "<lambda>"
    monitoring.reset()
    assert monitoring.startup().spans() == [] and monitoring.startup().dropped == 0
