"""Async-dispatch training tests: lazy ScoreHandles, the bounded in-flight
window, bit-exact equivalence vs sync mode, drain-time error attribution,
tail-batch padding (loss witness + compile-counter witness), and the
zero-new-host-syncs spy guard on the hot path.

Reference analog: the reference's AsyncDataSetIterator tests proved the
prefetch queue preserved the stream; here the dispatch side must prove more —
that deferring the per-step host sync changes NOTHING observable (params,
loss trajectory, listener callbacks, error surfacing) except when the host
blocks.
"""

import os

import numpy as np
import pytest

from deeplearning4j_tpu import faults, monitoring
from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.nn import (
    InputType, MultiLayerNetwork, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    DenseLayer, LSTMLayer, OutputLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.optimize import Sgd
from deeplearning4j_tpu.optimize import async_dispatch
from deeplearning4j_tpu.optimize.async_dispatch import (
    AsyncStepError, ScoreHandle, _pow2_bucket, pad_tail_batch,
)
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresListener, TrainingListener,
)


@pytest.fixture(autouse=True)
def _reset_env(monkeypatch):
    """Each test starts from the async default (window=2, padding on) and
    leaves the process env flags untouched."""
    flags = ("DL4J_TPU_ASYNC_STEPS", "DL4J_TPU_PAD_TAIL")
    for var in flags:
        monkeypatch.delenv(var, raising=False)
    env.reload()
    yield
    # monkeypatch undoes a test's setenv only AFTER this teardown: clear the
    # flags first, or the reload bakes the last test's into the singleton and
    # the next file on this worker starts from them (test_monitoring in sync
    # mode: no dispatch or drain is counted)
    for var in flags:
        os.environ.pop(var, None)
    env.reload()


def _async(monkeypatch, steps):
    monkeypatch.setenv("DL4J_TPU_ASYNC_STEPS", str(steps))
    env.reload()


def _model(seed=5, n_in=4):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(lr=0.1)).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(lr=0.1)).graph_builder()
            .add_inputs("in")
            .set_input_types(**{"in": InputType.feed_forward(4)})
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("o", OutputLayer(n_out=3, activation="softmax",
                                        loss="mcxent"), "d")
            .set_outputs("o").build())
    return ComputationGraph(conf).init()


#: the same test on either core (the spine of both is nn/network.py's)
either_core = pytest.mark.parametrize("core", [_model, _graph],
                                      ids=["multilayer", "graph"])


def _data(n=16, rng_seed=0, n_in=4):
    rng = np.random.default_rng(rng_seed)
    x = rng.normal(size=(n, n_in)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _leaves(model):
    import jax

    return [np.asarray(l) for l in jax.tree_util.tree_leaves(model.params)]


# --------------------------------------------------------------- handles
class TestScoreHandle:
    def test_fit_batch_returns_lazy_handle(self):
        net = _model()
        x, y = _data()
        h = net.fit_batch((x, y))
        assert isinstance(h, ScoreHandle)
        assert not h.ready()
        assert "in-flight" in repr(h)
        v = float(h)                      # forces the drain
        assert h.ready() and np.isfinite(v)
        assert repr(h).endswith(f"{v!r})")

    def test_handle_numeric_surface(self):
        net = _model()
        x, y = _data()
        h = net.fit_batch((x, y))
        v = h.value()
        assert h + 1 == v + 1 and 1 + h == 1 + v
        assert h - 1 == v - 1 and 1 - h == 1 - v
        assert h * 2 == v * 2 and -h == -v and abs(h) == abs(v)
        assert h / 2 == v / 2 and round(h, 3) == round(v, 3)
        assert (h < v + 1) and (h <= v) and (h > v - 1) and (h >= v)
        assert h == v and not (h != v)
        assert f"{h:.4f}" == f"{v:.4f}"
        assert np.isfinite(np.asarray(h))

    def test_window_caps_in_flight_steps(self):
        net = _model()
        x, y = _data()
        handles = [net.fit_batch((x, y)) for _ in range(5)]
        window = net._score_window
        # window=2 (default): 5 submits leave exactly 2 in flight
        assert len(window) == 2
        assert [h.ready() for h in handles] == [True, True, True, False, False]
        assert float(handles[4]) == net._score_value
        assert len(window) == 0

    @either_core
    def test_sync_mode_returns_floats(self, monkeypatch, core):
        _async(monkeypatch, 0)
        net = core()
        x, y = _data()
        out = net.fit_batch((x, y))
        assert isinstance(out, float)
        assert getattr(net, "_score_window", None) is None


# ----------------------------------------------------------- equivalence
class TestBitExactEquivalence:
    def test_multilayer_params_and_trajectory(self, monkeypatch):
        x, y = _data(48)
        it = lambda: ArrayDataSetIterator(x, y, batch_size=16)  # noqa: E731

        _async(monkeypatch, 0)
        sync_net, sync_l = _model(), CollectScoresListener()
        sync_net.set_listeners(sync_l)
        sync_net.fit(it(), epochs=3)

        _async(monkeypatch, 3)
        async_net, async_l = _model(), CollectScoresListener()
        async_net.set_listeners(async_l)
        async_net.fit(it(), epochs=3)

        # the exact same floats, the exact same (iteration, score) pairs,
        # the exact same bits in every param leaf
        assert async_l.scores == sync_l.scores
        for a, b in zip(_leaves(async_net), _leaves(sync_net)):
            np.testing.assert_array_equal(a, b)

    def test_graph_params_and_trajectory(self, monkeypatch):
        x, y = _data(32, rng_seed=7)
        it = lambda: ArrayDataSetIterator(x, y, batch_size=8)  # noqa: E731

        _async(monkeypatch, 0)
        sync_net, sync_l = _graph(), CollectScoresListener()
        sync_net.set_listeners(sync_l)
        sync_net.fit(it(), epochs=2)

        _async(monkeypatch, 2)
        async_net, async_l = _graph(), CollectScoresListener()
        async_net.set_listeners(async_l)
        async_net.fit(it(), epochs=2)

        assert async_l.scores == sync_l.scores
        for a, b in zip(_leaves(async_net), _leaves(sync_net)):
            np.testing.assert_array_equal(a, b)

    def test_equivalence_under_injected_data_io_fault(self, monkeypatch):
        """Retried data_io faults must not perturb the async trajectory:
        the retry re-reads the same batch, the window sees the same
        stream."""
        x, y = _data(32, rng_seed=1)

        def run(steps):
            _async(monkeypatch, steps)
            net, lst = _model(seed=11), CollectScoresListener()
            net.set_listeners(lst)
            it = ArrayDataSetIterator(x, y, batch_size=8)
            it._retry = faults.RetryPolicy(max_attempts=4, base_delay_s=0.001)
            with faults.injected("data_io:2") as plan:
                net.fit(it, epochs=2)
            assert plan.injected["data_io"] == 2
            return lst.scores, _leaves(net)

        sync_scores, sync_params = run(0)
        async_scores, async_params = run(2)
        assert async_scores == sync_scores
        for a, b in zip(async_params, sync_params):
            np.testing.assert_array_equal(a, b)

    def test_tbptt_single_fetch_per_call(self, monkeypatch):
        """Satellite: _fit_tbptt accumulates chunk losses on device — ONE
        host fetch per fit_batch call regardless of chunk count."""
        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.05)).list()
                .layer(LSTMLayer(n_out=8))
                .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .backprop_type_tbptt(4)
                .set_input_type(InputType.recurrent(3)).build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(2)
        x = rng.normal(size=(4, 12, 3)).astype(np.float32)  # 3 chunks of 4
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 12))]

        fetches = []
        real = async_dispatch._fetch_scalar
        monkeypatch.setattr(async_dispatch, "_fetch_scalar",
                            lambda a: (fetches.append(1), real(a))[1])
        _async(monkeypatch, 0)      # eager: the fetch happens inside the call
        net.fit_batch((x, y))
        assert len(fetches) == 1


# ------------------------------------------------------ error attribution
class TestDrainErrors:
    def test_in_flight_failure_surfaces_with_original_step(self, monkeypatch):
        """A failure inside an in-flight step must raise AT DRAIN with the
        step it belongs to, not the step the host had reached."""
        net = _model()
        x, y = _data()
        real = async_dispatch._fetch_scalar

        def failing_fetch(arr):
            if failing_fetch.calls == 1:   # second drained step (step 1)
                failing_fetch.calls += 1
                raise FloatingPointError("injected device failure")
            failing_fetch.calls += 1
            return real(arr)

        failing_fetch.calls = 0
        monkeypatch.setattr(async_dispatch, "_fetch_scalar", failing_fetch)
        _async(monkeypatch, 2)
        h0 = net.fit_batch((x, y))
        h1 = net.fit_batch((x, y))
        h2 = net.fit_batch((x, y))      # drains step 0 (ok)
        assert h0.ready()
        with pytest.raises(AsyncStepError) as exc_info:
            net.fit_batch((x, y))       # drains step 1 -> boom
        err = exc_info.value
        assert err.step == 1 and err.epoch == 0
        assert isinstance(err.__cause__, FloatingPointError)
        # the failed handle replays the error; later handles still drain
        with pytest.raises(AsyncStepError):
            h1.value()
        assert np.isfinite(float(h2))

    @either_core
    def test_drain_error_does_not_poison_later_deliveries(self, monkeypatch,
                                                          core):
        """Regression: the step being SUBMITTED when an older step's drain
        error surfaces is already queued — its id must be consumed, or the
        next fit_batch re-dispatches under the same step number and
        listeners see a duplicate iteration. After one failed step, every
        other iteration fires its listener exactly once, in order."""
        net, lst = core(), CollectScoresListener()
        net.set_listeners(lst)
        x, y = _data()
        real = async_dispatch._fetch_scalar

        def failing_fetch(arr):
            failing_fetch.calls += 1
            if failing_fetch.calls == 2:     # second drained step (step 1)
                raise FloatingPointError("injected device failure")
            return real(arr)

        failing_fetch.calls = 0
        monkeypatch.setattr(async_dispatch, "_fetch_scalar", failing_fetch)
        _async(monkeypatch, 2)
        errors = []
        for _ in range(8):
            try:
                net.fit_batch((x, y))
            except AsyncStepError as e:
                errors.append(e)
        net._score_window.drain()
        assert [e.step for e in errors] == [1]
        assert net.step_count == 8
        assert [i for i, _ in lst.scores] == [i for i in range(8) if i != 1]

    @either_core
    def test_fit_drains_at_epoch_end_before_epoch_listeners(self, core):
        events = []

        class Recorder(TrainingListener):
            def iteration_done(self, model, iteration, epoch, score):
                events.append(("iter", iteration, epoch))

            def on_epoch_end(self, model, epoch):
                events.append(("epoch_end", epoch))

        net = core()
        net.set_listeners(Recorder())
        x, y = _data(24)
        net.fit(ArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        assert events == [
            ("iter", 0, 0), ("iter", 1, 0), ("iter", 2, 0), ("epoch_end", 0),
            ("iter", 3, 1), ("iter", 4, 1), ("iter", 5, 1), ("epoch_end", 1),
        ]


# ------------------------------------------------------------- listeners
class TestEagerListeners:
    def test_eager_listener_forces_sync_path(self):
        """CI guard: a listener declaring needs_eager_score gets the scalar
        at every iteration, synchronously — fit_batch returns floats."""

        class Eager(TrainingListener):
            needs_eager_score = True

            def __init__(self):
                self.seen = []

            def iteration_done(self, model, iteration, epoch, score):
                assert isinstance(score, float)
                self.seen.append((iteration, score))

        net = _model()
        eager = Eager()
        net.set_listeners(eager)
        x, y = _data()
        out = net.fit_batch((x, y))
        assert isinstance(out, float)
        assert eager.seen == [(0, out)]
        assert getattr(net, "_score_window", None) is None

    def test_early_stopping_sees_per_iteration_scalars(self):
        """CI guard: EarlyStoppingTrainer's per-iteration float(score)
        keeps eager semantics under the async default — every iteration's
        termination check runs against that iteration's scalar."""
        from deeplearning4j_tpu.optimize.earlystopping import (
            EarlyStoppingConfiguration, EarlyStoppingTrainer,
            MaxEpochsTerminationCondition, MaxScoreIterationTerminationCondition,
        )

        net = _model()
        x, y = _data(32)
        cfg = EarlyStoppingConfiguration(
            epoch_termination_conditions=[MaxEpochsTerminationCondition(2)],
            iteration_termination_conditions=[
                MaxScoreIterationTerminationCondition(1e9)],
        )
        result = EarlyStoppingTrainer(
            cfg, net, ArrayDataSetIterator(x, y, batch_size=8)).fit()
        assert result.total_epochs == 2
        assert np.isfinite(result.best_score)
        # nothing left in flight once the trainer returns
        assert len(getattr(net, "_score_window", [])) == 0

    def test_attaching_eager_listener_drains_existing_window(self):
        class Eager(TrainingListener):
            needs_eager_score = True

        net = _model()
        x, y = _data()
        h = net.fit_batch((x, y))
        assert not h.ready()
        net.set_listeners(Eager())
        out = net.fit_batch((x, y))     # mode flip drains the old window
        assert h.ready() and isinstance(out, float)


# ---------------------------------------------------------- host syncs
class TestZeroHostSyncs:
    def test_dispatch_hot_path_never_fetches(self, monkeypatch):
        """Spy guard: while the window has capacity, fit_batch performs
        ZERO host<-device scalar fetches; every fetch happens at drain."""
        fetches = []
        real = async_dispatch._fetch_scalar
        monkeypatch.setattr(async_dispatch, "_fetch_scalar",
                            lambda a: (fetches.append(1), real(a))[1])
        _async(monkeypatch, 8)
        net = _model()
        x, y = _data()
        for _ in range(5):              # all within the window of 8
            net.fit_batch((x, y))
        assert fetches == []
        async_dispatch.drain_scores(net)
        assert len(fetches) == 5        # exactly one fetch per step, at drain

    def test_monitoring_off_async_on_zero_registry_calls(self, monkeypatch):
        """CI guard: monitoring-off + async-on makes NO registry/tracer
        calls anywhere in fit_batch/submit/drain."""
        from deeplearning4j_tpu.monitoring import (
            Counter, Gauge, Histogram, SpanTracer,
        )

        assert not monitoring.enabled()
        calls = []

        def spy(name):
            def record(self, *a, **k):
                calls.append(name)
            return record

        monkeypatch.setattr(Counter, "inc", spy("Counter.inc"))
        monkeypatch.setattr(Gauge, "set", spy("Gauge.set"))
        monkeypatch.setattr(Histogram, "observe", spy("Histogram.observe"))
        monkeypatch.setattr(SpanTracer, "span", spy("SpanTracer.span"))

        net = _model()
        x, y = _data(24)
        net.fit(ArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        assert calls == []
        # the start-up record, on with monitoring off, took the first fit's
        # builds; a fit that builds no program appends to no tracer at all
        monkeypatch.setattr(SpanTracer, "_append", spy("SpanTracer._append"))
        net.fit(ArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        assert calls == []


# --------------------------------------------------------- tail padding
class TestTailPadding:
    def test_pow2_bucket(self):
        assert _pow2_bucket(1, 32) == 1
        assert _pow2_bucket(5, 32) == 8
        assert _pow2_bucket(20, 32) == 32
        assert _pow2_bucket(33, 32) == 32   # clamped
        assert _pow2_bucket(32, 32) == 32

    def test_pad_tail_batch_shapes_and_masks(self):
        x = np.ones((5, 4), np.float32)
        y = np.ones((5, 3), np.float32)
        px, py, pm, plm = pad_tail_batch(x, y, None, None, 32)
        assert px.shape == (8, 4) and py.shape == (8, 3)
        assert pm is None
        np.testing.assert_array_equal(np.asarray(plm),
                                      [1, 1, 1, 1, 1, 0, 0, 0])
        # padded rows are zeros
        assert not np.asarray(px)[5:].any()

    def test_pad_passthrough_cases(self):
        x = np.ones((5, 4), np.float32)
        y = np.ones((5, 3), np.float32)
        # full batch
        assert pad_tail_batch(x, y, None, None, 5)[0] is x
        # dual-role single mask: not shape-safe, passes through
        m = np.ones((5, 4), np.float32)
        assert pad_tail_batch(x, y, m, None, 32)[0] is x
        # already at a bucket size
        x4, y4 = np.ones((4, 4), np.float32), np.ones((4, 3), np.float32)
        assert pad_tail_batch(x4, y4, None, None, 32)[0] is x4

    def test_padded_loss_bit_exact_vs_unpadded(self, monkeypatch):
        """The witness: label-mask zeroing + valid-count normalization give
        the padded batch the EXACT loss of the raw batch. Params match to
        float32 reduction-order noise (the padded matmul reduces over more
        rows — all exact zeros — which XLA may sum in a different order)."""
        x, y = _data(32, rng_seed=5)
        sizes = (32, 32, 20, 9)

        monkeypatch.setenv("DL4J_TPU_PAD_TAIL", "0")
        env.reload()
        raw_net = _model(seed=13)
        raw = [float(raw_net.fit_batch((x[:n], y[:n]))) for n in sizes]

        monkeypatch.setenv("DL4J_TPU_PAD_TAIL", "1")
        env.reload()
        pad_net = _model(seed=13)
        padded = [float(pad_net.fit_batch((x[:n], y[:n]))) for n in sizes]

        assert padded == raw
        for a, b in zip(_leaves(pad_net), _leaves(raw_net)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)

    def test_compile_counter_witness(self, monkeypatch):
        """Acceptance: an epoch with ragged tails compiles exactly one
        train program per LOGICAL shape (= pow2 bucket) — every distinct
        tail size in a bucket lands in that bucket's single masked program
        instead of its own."""
        x, y = _data(32, rng_seed=6)
        tails = (20, 17, 25, 9)     # buckets: 32, 32, 32, 16

        pad_net = _model(seed=21)
        pad_net.fit_batch((x, y))               # sets the bucket ceiling
        for n in tails:
            pad_net.fit_batch((x[:n], y[:n]))
        async_dispatch.drain_scores(pad_net)
        # one unmasked full-batch program + one masked program PER BUCKET
        # (32 and 16) — 4 distinct ragged sizes collapse into 2 programs
        assert pad_net._jit_cache["train"]._cache_size() == 3

        monkeypatch.setenv("DL4J_TPU_PAD_TAIL", "0")
        env.reload()
        raw_net = _model(seed=21)
        raw_net.fit_batch((x, y))
        for n in tails:
            raw_net.fit_batch((x[:n], y[:n]))
        async_dispatch.drain_scores(raw_net)
        # without padding: one program PER ragged shape
        assert raw_net._jit_cache["train"]._cache_size() == 1 + len(tails)

    def test_graph_tail_padding_loss_exact(self, monkeypatch):
        x, y = _data(16, rng_seed=8)
        sizes = (16, 10)

        monkeypatch.setenv("DL4J_TPU_PAD_TAIL", "0")
        env.reload()
        raw_net = _graph(seed=17)
        raw = [float(raw_net.fit_batch((x[:n], y[:n]))) for n in sizes]

        monkeypatch.setenv("DL4J_TPU_PAD_TAIL", "1")
        env.reload()
        pad_net = _graph(seed=17)
        padded = [float(pad_net.fit_batch((x[:n], y[:n]))) for n in sizes]
        # equal up to float32 summation-order rounding (the masked mean
        # reduces over the padded rows' exact zeros in a different order)
        assert padded == pytest.approx(raw, rel=1e-6)
        for a, b in zip(_leaves(pad_net), _leaves(raw_net)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)

    def test_batchnorm_gates_padding_off(self):
        from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer

        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Sgd(lr=0.1)).list()
                .layer(DenseLayer(n_out=8, activation="relu"))
                .layer(BatchNormalizationLayer())
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        assert not net._tail_padding_ok()
        x, y = _data(16)
        net.fit_batch((x, y))
        h = net.fit_batch((x[:5], y[:5]))   # tail runs UNPADDED
        assert np.isfinite(float(h))
        assert net._jit_cache["train"]._cache_size() == 2


# ------------------------------------------------------- prefetch/sharder
class TestPrefetchSharding:
    def test_prefetch_iterator_device_puts_batches(self):
        import jax

        from deeplearning4j_tpu.datasets.iterators import AsyncPrefetchIterator

        x, y = _data(16)
        it = AsyncPrefetchIterator(ArrayDataSetIterator(x, y, batch_size=8))
        batches = list(it)
        assert len(batches) == 2
        assert all(isinstance(b.features, jax.Array) for b in batches)

    def test_prefetch_iterator_applies_sharder(self):
        from deeplearning4j_tpu.datasets.iterators import AsyncPrefetchIterator
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh

        mesh = DeviceMesh()
        x, y = _data(16)
        it = AsyncPrefetchIterator(ArrayDataSetIterator(x, y, batch_size=8),
                                   device_put=False, sharder=mesh.shard_batch)
        batches = list(it)
        sh = mesh.batch_sharding(2)
        assert all(b.features.sharding == sh for b in batches)
        # shard_batch fast-path: an already-sharded array passes through
        again = mesh.shard_batch(batches[0].features)
        assert again is batches[0].features

    def test_prefetch_propagates_source_errors(self):
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncPrefetchIterator, DataSetIterator,
        )

        class Exploding(DataSetIterator):
            def __init__(self):
                super().__init__(4)

            def _produce(self):
                yield from []
                raise RuntimeError("unreachable")

            def __iter__(self):
                x, y = _data(8)
                from deeplearning4j_tpu.datasets.dataset import DataSet

                yield DataSet(x[:4], y[:4])
                raise OSError("storage gone")

        it = AsyncPrefetchIterator(Exploding(), device_put=False)
        with pytest.raises(OSError, match="storage gone"):
            list(it)

    def test_parallel_wrapper_async_fit_matches_sync(self, monkeypatch):
        from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh

        x, y = _data(64, rng_seed=9)

        def run(steps):
            _async(monkeypatch, steps)
            net = _model(seed=23)
            w = ParallelWrapper(net, DeviceMesh(data=8), prefetch_buffer=2)
            w.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2)
            return _leaves(net)

        for a, b in zip(run(2), run(0)):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------- compile cache
class TestCompileCache:
    def test_checkout_cache_and_metrics_bridge(self):
        """One rule: without JAX_COMPILATION_CACHE_DIR the cache is the
        fixed <checkout>/.jax_cache (what conftest already configured), and
        the call wires the dl4j_compile_* monitoring tier — backend compiles
        show up in the registry when monitoring is on."""
        import os

        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu import monitoring
        from deeplearning4j_tpu.monitoring.compile import (
            CHECKOUT_CACHE_DIR, configure_compile_cache,
        )

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            assert configure_compile_cache() == CHECKOUT_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
        try:
            monitoring.reset()
            monitoring.enable()

            @jax.jit
            def f(a):
                return a * 3.0 + 1.0

            f(jnp.arange(7.0)).block_until_ready()
            reg = monitoring.registry()
            assert reg.get("dl4j_compiles_total").value >= 1
            assert reg.get("dl4j_compile_seconds").count >= 1
        finally:
            monitoring.reset()

    def test_outside_directory_is_left_alone(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, JAX already uses it: the
        function sets no other directory and never trims one it does not
        own. Checked in a fresh interpreter, where JAX reads the variable."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        outside = tmp_path / "outside_cache"
        outside.mkdir()
        (outside / "theirs.bin").write_bytes(b"x" * 4096)
        code = (
            "import jax\n"
            "from deeplearning4j_tpu.monitoring import compile as c\n"
            "c.CHECKOUT_CACHE_CAP_BYTES = 1\n"
            "d = c.configure_compile_cache()\n"
            "assert d == jax.config.jax_compilation_cache_dir, d\n"
            "print(d)\n")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(outside))
        out = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == str(outside)
        assert (outside / "theirs.bin").exists()

    def test_bridge_silent_when_monitoring_off(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu import monitoring
        from deeplearning4j_tpu.monitoring.compile import install_hooks

        install_hooks()
        assert not monitoring.enabled()

        @jax.jit
        def g(a):
            return a - 2.0

        g(jnp.arange(5.0)).block_until_ready()
        # disabled: the hook must not have materialized any compile metrics
        assert monitoring.registry().get("dl4j_compiles_total") is None


# ------------------------------------------------------------ score reads
class TestScoreSemantics:
    @either_core
    def test_score_value_read_drains(self, core):
        net = core()
        x, y = _data()
        net.fit_batch((x, y))
        net.fit_batch((x, y))
        assert len(net._score_window) == 2
        v = net.score_value
        assert np.isfinite(v) and len(net._score_window) == 0

    def test_score_on_dataset_unaffected(self):
        net = _model()
        x, y = _data()
        net.fit_batch((x, y))
        s = net.score((x, y))           # fresh forward, not the fit score
        assert isinstance(s, float) and np.isfinite(s)

    def test_window_resize_via_env(self, monkeypatch):
        net = _model()
        x, y = _data()
        net.fit_batch((x, y))
        _async(monkeypatch, 1)
        net.fit_batch((x, y))           # resized window drains down to 1
        assert len(net._score_window) == 1


# ------------------------------------------------------------- one spine
class TestOneSpine:
    @either_core
    def test_the_step_the_dispatch_and_the_loop_are_networks(self, core,
                                                             monkeypatch):
        """Neither core may grow a ``fit``, ``fit_batch``, train step or
        ``score_value`` of its own again; and what a monitored step leaves
        behind is the same whichever core ran it."""
        from deeplearning4j_tpu.nn.network import Network

        net = core()
        for name in ("fit", "fit_batch", "_make_train_step", "score_value"):
            assert getattr(type(net), name) is getattr(Network, name), name
        x, y = _data(8)
        float(net.fit_batch((x, y)))     # compiled and drained before the spans
        monitoring.reset()
        monitoring.enable()
        try:
            names = {}
            for mode, steps in (("async", 2), ("sync", 0)):
                _async(monkeypatch, steps)
                ring = monitoring.start_tracing()
                net.fit(ArrayDataSetIterator(x, y, batch_size=8))
                names[mode] = sorted(s.name for s in ring.spans())
        finally:
            monitoring.reset()
        # one batch: its pull and the pull that ends the epoch
        assert names["async"] == ["fit.data_wait", "fit.data_wait",
                                  "fit.dispatch", "fit.drain", "fit.listeners"]
        assert names["sync"] == ["fit.data_wait", "fit.data_wait",
                                 "fit.device_step", "fit.listeners"]
