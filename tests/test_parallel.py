"""Parallelism tests on the virtual 8-device CPU mesh.

Reference analog: ParallelWrapperTest (threads-as-devices) and the Spark
local[N] tests — here the mesh itself is virtualized
(--xla_force_host_platform_device_count=8, set in conftest).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize import Sgd
from deeplearning4j_tpu.parallel import DeviceMesh, ParallelInference, ParallelWrapper
from deeplearning4j_tpu.parallel.sequence import ring_attention, ulysses_attention


def _model(seed=9):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Sgd(lr=0.1))
        .list()
        .layer(DenseLayer(n_out=16, activation="relu"))
        .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(8))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _graph(seed=9):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Sgd(lr=0.1))
        .graph_builder()
        .add_inputs("in")
        .set_input_types(**{"in": InputType.feed_forward(8)})
        .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
        .add_layer("o", OutputLayer(n_out=4, activation="softmax", loss="mcxent"), "d")
        .set_outputs("o")
        .build()
    )
    return ComputationGraph(conf).init()


def _fit_through(entry, model, data, epochs):
    """``fit`` by the entry point a user calls: the network's own, or the wrapper's."""
    if entry == "wrapper":
        return ParallelWrapper(model, DeviceMesh(data=8)).fit(data, epochs=epochs)
    return model.fit(data, epochs=epochs)


class TestDeviceMesh:
    def test_eight_devices(self):
        assert len(jax.devices()) == 8
        mesh = DeviceMesh()
        assert mesh.shape["data"] == 8

    def test_axes(self):
        mesh = DeviceMesh(data=2, model=4)
        assert mesh.shape == {"data": 2, "model": 4, "pipe": 1, "seq": 1}


class TestDataParallel:
    def test_dp_matches_single_device(self, rng):
        """The §2.4 collapse proof: DP-sharded training == single-device training."""
        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]

        single = _model()
        for _ in range(5):
            single.fit_batch((x, y))

        dp_model = _model()
        wrapper = ParallelWrapper(dp_model, DeviceMesh(data=8), prefetch_buffer=0)
        for _ in range(5):
            wrapper.fit_batch((x, y))

        np.testing.assert_allclose(
            np.asarray(single.params[0]["W"]), np.asarray(dp_model.params[0]["W"]),
            rtol=2e-4, atol=1e-6,
        )

    def test_wrapper_fit_calls_the_listener_hooks_the_bare_network_calls(self, rng):
        """``ParallelWrapper.fit`` drives the network's own epoch loop: epoch
        start and end once an epoch, fit end once, in the bare network's order."""
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.optimize.listeners import TrainingListener

        class Recorder(TrainingListener):
            def __init__(self):
                self.events = []

            def iteration_done(self, model, iteration, epoch, score):
                self.events.append(("iter", iteration, epoch))

            def on_epoch_start(self, model, epoch):
                self.events.append(("epoch_start", epoch))

            def on_epoch_end(self, model, epoch):
                self.events.append(("epoch_end", epoch))

            def on_fit_end(self, model):
                self.events.append(("fit_end", model.step_count))

        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
        seen = {}
        for entry in ("bare", "wrapper"):
            model, rec = _model(), Recorder()
            model.set_listeners(rec)
            out = _fit_through(entry, model, ArrayDataSetIterator(x, y, batch_size=16), 2)
            assert out is model and model.epoch_count == 2
            seen[entry] = rec.events
        assert seen["wrapper"] == seen["bare"] == [
            ("epoch_start", 0), ("iter", 0, 0), ("iter", 1, 0), ("epoch_end", 0),
            ("epoch_start", 1), ("iter", 2, 1), ("iter", 3, 1), ("epoch_end", 1),
            ("fit_end", 4)]

    @pytest.mark.parametrize("entry,build", [("bare", _model), ("bare", _graph),
                                             ("wrapper", _model)],
                             ids=["multilayer", "graph", "wrapper"])
    def test_the_final_step_is_on_disk_when_fit_returns(self, tmp_path, rng, entry, build):
        """``AsyncCheckpointListener`` saves a run's last step in ``on_fit_end``:
        whichever entry point ran ``fit``, that step can be restored."""
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.util.checkpoints import AsyncCheckpointListener

        x = rng.normal(size=(48, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 48)]
        model = build()
        lst = AsyncCheckpointListener(tmp_path / "ck", save_every_n_iterations=2)
        model.set_listeners(lst)
        try:
            _fit_through(entry, model, ArrayDataSetIterator(x, y, batch_size=16), 1)
            # three steps: the cadence saved step 2, only fit's end saves step 3
            assert model.step_count == 3
            assert lst.checkpointer.all_steps() == [2, 3]
        finally:
            lst.checkpointer.close()

    @pytest.mark.slow  # ~110s: spawned dryrun process recompiles cold
    def test_dryrun_multichip(self):
        import sys, pathlib

        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
        import __graft_entry__

        __graft_entry__.dryrun_multichip(8)


class TestParallelInference:
    def test_batched_async(self, rng):
        model = _model()
        pi = ParallelInference(model, batch_limit=8).start()
        try:
            xs = [rng.normal(size=(8,)).astype(np.float32) for _ in range(16)]
            queues = [pi.submit(x) for x in xs]
            outs = [q.get(timeout=30) for q in queues]
            direct = np.asarray(model.output(np.stack(xs)))
            np.testing.assert_allclose(np.stack(outs), direct, rtol=1e-5)
        finally:
            pi.stop()


class TestRingAttention:
    def _reference_attention(self, q, k, v, causal=False):
        d = q.shape[-1]
        logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        if causal:
            T = logits.shape[-1]
            mask = np.tril(np.ones((T, T), bool))
            logits = np.where(mask, logits, -1e30)
        w = np.exp(logits - logits.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        return np.einsum("bhqk,bhkd->bhqd", w, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_matches_reference(self, rng, causal):
        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 2, 4, 32, 8  # T sharded 8-way -> blocks of 4
        q = rng.normal(size=(B, H, T, D)).astype(np.float32)
        k = rng.normal(size=(B, H, T, D)).astype(np.float32)
        v = rng.normal(size=(B, H, T, D)).astype(np.float32)
        out = np.asarray(ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                        mesh.mesh, causal=causal))
        ref = self._reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)

    def test_ulysses_matches_reference(self, rng):
        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 2, 8, 32, 4  # H divisible by 8
        q = rng.normal(size=(B, H, T, D)).astype(np.float32)
        k = rng.normal(size=(B, H, T, D)).astype(np.float32)
        v = rng.normal(size=(B, H, T, D)).astype(np.float32)
        out = np.asarray(ulysses_attention(jnp.asarray(q), jnp.asarray(k),
                                           jnp.asarray(v), mesh.mesh))
        ref = self._reference_attention(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_masked_ring_einsum_core(self, rng, causal):
        """r4: a key-padding mask shard travels the ring with its K/V
        block — padded-batch long context without a [T, T] mask. Einsum
        core (unaligned head_dim), fwd + dq, vs the plain XLA lowering."""
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 2, 2, 64, 16
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        m = np.ones((B, T), np.float32)
        m[0, 40:] = 0                   # pads span shard boundaries
        m[1, :8] = 0                    # a fully-masked LEADING shard
        mask = jnp.asarray(m)
        out = ring_attention(q, k, v, mesh.mesh, causal=causal, mask=mask)
        ref = dot_product_attention(q, k, v, mask=mask[:, None, None, :],
                                    causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        if not causal:
            g1 = jax.grad(lambda q: ring_attention(
                q, k, v, mesh.mesh, mask=mask).sum())(q)
            g2 = jax.grad(lambda q: dot_product_attention(
                q, k, v, mask=mask[:, None, None, :]).sum())(q)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.slow  # ~30s/case: 8-shard flash ring fwd+bwd compile
    @pytest.mark.parametrize("causal", [False, True])
    def test_masked_ring_flash_core(self, rng, causal):
        """The flash-kernel ring core with a traveling mask shard: fwd and
        the true ring backward (dk/dv travel with their blocks), including
        the causal branch's lax.cond mask plumbing."""
        from deeplearning4j_tpu.ops.attention import dot_product_attention

        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 1, 1, 128, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        m = np.ones((B, T), np.float32)
        m[0, 96:] = 0                   # last two shards fully masked
        mask = jnp.asarray(m)
        out = ring_attention(q, q, q, mesh.mesh, impl="flash", mask=mask,
                             causal=causal)
        ref = dot_product_attention(q, q, q, mask=mask[:, None, None, :],
                                    causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        gf = jax.grad(lambda q: ring_attention(
            q, q, q, mesh.mesh, impl="flash", mask=mask,
            causal=causal).sum())(q)
        gr = jax.grad(lambda q: dot_product_attention(
            q, q, q, mask=mask[:, None, None, :], causal=causal).sum())(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-4)

    def test_masked_ring_rejects_bad_mask_shape(self, rng):
        mesh = DeviceMesh(data=1, seq=8)
        q = jnp.zeros((2, 2, 64, 16), jnp.float32)
        with pytest.raises(ValueError, match="key-padding"):
            ring_attention(q, q, q, mesh.mesh,
                           mask=jnp.ones((2, 2, 64, 64)))


class TestTensorParallel:
    def test_tp_matches_single_device(self, rng):
        from deeplearning4j_tpu.parallel import TensorParallel

        x = rng.normal(size=(16, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]

        single = _model()
        for _ in range(3):
            single.fit_batch((x, y))

        tp_model = _model()
        tp = TensorParallel(tp_model, DeviceMesh(data=2, model=4))
        for _ in range(3):
            tp.fit_batch((x, y))

        for p_s, p_t in zip(single.params, tp_model.params):
            for k in p_s:
                np.testing.assert_allclose(
                    np.asarray(p_s[k]), np.asarray(p_t[k]), rtol=2e-4, atol=1e-5)

    def test_param_placement(self, rng):
        from deeplearning4j_tpu.parallel import TensorParallel

        model = _model()
        tp = TensorParallel(model, DeviceMesh(data=2, model=4)).place()
        # dense W [8,16] should be sharded over model on its last dim
        w = model.params[0]["W"]
        spec = w.sharding.spec
        assert tuple(spec) == (None, "model")

    @staticmethod
    def _tiny_bert(seed=3):
        from deeplearning4j_tpu.zoo import Bert

        return Bert(vocab_size=64, max_len=8, d_model=32, n_layers=2,
                    n_heads=4, d_ff=64, num_classes=2, dropout=0.0,
                    dtype="float32", seed=seed).init()

    def test_tp_bert_matches_single_device(self, rng):
        """r4 (VERDICT r3 #5): megatron structure-based rules exercised on
        the BERT zoo model — QKV/W1 column-parallel, Wo/W2 row-parallel —
        with exact parity against the single-device trajectory on the
        8-device mesh."""
        from deeplearning4j_tpu.parallel import TensorParallel

        ids = rng.integers(0, 64, (16, 8)).astype(np.int32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]

        single = self._tiny_bert()
        for _ in range(2):
            single.fit_batch((ids, y))

        tp_model = self._tiny_bert()
        tp = TensorParallel(tp_model, DeviceMesh(data=2, model=4)).place()

        # the block structure landed megatron-style at placement (after a
        # step, params adopt GSPMD's propagated output shardings instead)
        from deeplearning4j_tpu.nn.layers.attention import \
            TransformerEncoderLayer

        enc_idx = next(i for i, l in enumerate(tp_model.layers)
                       if isinstance(l, TransformerEncoderLayer))
        p = tp_model.params[enc_idx]
        # (PartitionSpec normalizes trailing Nones away)
        assert tuple(p["Wq"].sharding.spec) == (None, "model")
        assert tuple(p["Wo"].sharding.spec)[:1] == ("model",)
        assert tuple(p["W1"].sharding.spec) == (None, "model")
        assert tuple(p["W2"].sharding.spec)[:1] == ("model",)
        assert tuple(p["b2"].sharding.spec) == ()

        for _ in range(2):
            tp.fit_batch((ids, y))

        for p_s, p_t in zip(single.params, tp_model.params):
            for k in p_s:
                np.testing.assert_allclose(
                    np.asarray(p_s[k]), np.asarray(p_t[k]),
                    rtol=5e-4, atol=5e-5, err_msg=k)


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self, rng):
        from deeplearning4j_tpu.parallel import GPipe, stack_stage_params

        mesh = DeviceMesh(data=1, pipe=8)
        D = 16

        def stage_fn(p, x):
            return jnp.tanh(x @ p["W"] + p["b"])

        stages = [{"W": rng.normal(size=(D, D)).astype(np.float32) * 0.3,
                   "b": np.zeros(D, np.float32)} for _ in range(8)]
        stacked = stack_stage_params([
            {k: jnp.asarray(v) for k, v in s.items()} for s in stages])
        x = rng.normal(size=(16, D)).astype(np.float32)

        pipe = GPipe(stage_fn, mesh, n_microbatches=4)
        with mesh.mesh:
            out = np.asarray(pipe(stacked, jnp.asarray(x)))
        ref = np.asarray(pipe.sequential_reference(stacked, jnp.asarray(x)))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)

    def test_gpipe_backward_trains(self, rng):
        from deeplearning4j_tpu.optimize import Sgd
        from deeplearning4j_tpu.parallel import (GPipe, pipeline_train_step,
                                                 stack_stage_params)

        mesh = DeviceMesh(data=1, pipe=4, devices=jax.devices()[:4])
        D = 8

        def stage_fn(p, x):
            return jnp.tanh(x @ p["W"] + p["b"])

        key = jax.random.key(0)
        stages = [{"W": jax.random.normal(jax.random.fold_in(key, i), (D, D)) * 0.4,
                   "b": jnp.zeros(D)} for i in range(4)]
        params = {"stages": stack_stage_params(stages),
                  "head": {"W": jax.random.normal(jax.random.fold_in(key, 9), (D, 2))}}

        def head_fn(hp, h):
            return h @ hp["W"]

        def loss_fn(pred, y):
            return jnp.mean((pred - y) ** 2)

        opt = Sgd(lr=0.2)
        opt_state = opt.init_state(params)
        pipe = GPipe(stage_fn, mesh, n_microbatches=4)
        step = pipeline_train_step(pipe, loss_fn, opt, head_fn)

        x = jnp.asarray(rng.normal(size=(8, D)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(8, 2)).astype(np.float32))
        losses = []
        with mesh.mesh:
            for i in range(10):
                params, opt_state, l = step(params, opt_state,
                                            jnp.asarray(i, jnp.int32), x, y)
                losses.append(float(l))
        assert losses[-1] < losses[0] * 0.7, losses

    def test_gpipe_bert_encoder_stack(self, rng):
        """r4 (VERDICT r3 #5): PP over a REAL architecture — the BERT zoo
        model's TransformerEncoderLayer stack, one block per pipe stage,
        with parity against applying the same zoo params sequentially and
        a pipelined gradient through the stack."""
        from deeplearning4j_tpu.nn.layers.attention import \
            TransformerEncoderLayer
        from deeplearning4j_tpu.parallel import GPipe, stack_stage_params
        from deeplearning4j_tpu.zoo import Bert

        net = Bert(vocab_size=64, max_len=8, d_model=32, n_layers=4,
                   n_heads=4, d_ff=64, num_classes=2, dropout=0.0,
                   dtype="float32", seed=5).init()
        enc_layers = [(l, p) for l, p in zip(net.layers, net.params)
                      if isinstance(l, TransformerEncoderLayer)]
        assert len(enc_layers) == 4
        enc = enc_layers[0][0]            # identical config across stages

        def stage_fn(p, h):
            out, _ = enc.apply(p, {}, h, train=False)
            return out

        stacked = stack_stage_params([p for _, p in enc_layers])
        mesh = DeviceMesh(data=1, pipe=4, devices=jax.devices()[:4])
        pipe = GPipe(stage_fn, mesh, n_microbatches=4)
        h = jnp.asarray(rng.normal(size=(8, 8, 32)).astype(np.float32))
        with mesh.mesh:
            out = np.asarray(pipe(stacked, h))
        ref = np.asarray(pipe.sequential_reference(stacked, h))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        # pipelined backward through the real blocks
        with mesh.mesh:
            g = jax.jit(jax.grad(
                lambda sp: (pipe(sp, h) ** 2).sum()))(stacked)
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in jax.tree_util.tree_leaves(g))


class TestExpertParallel:
    def test_moe_matches_reference(self, rng):
        from deeplearning4j_tpu.parallel import (DeviceMesh, init_moe_params,
                                                 place_moe_params, switch_moe)
        from deeplearning4j_tpu.parallel.expert import switch_moe_reference

        mesh = DeviceMesh(data=2, model=4)
        params = init_moe_params(jax.random.key(0), d_model=16, d_hidden=32,
                                 n_experts=4)
        params = place_moe_params(params, mesh)
        x = rng.normal(size=(64, 16)).astype(np.float32)
        with mesh.mesh:
            y, aux = jax.jit(switch_moe)(params, jnp.asarray(x))
        ref = switch_moe_reference(params, x)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-3, atol=2e-4)
        assert float(aux) >= 1.0 - 1e-3  # balanced routing lower bound is 1

    def test_moe_trains_with_aux_loss(self, rng):
        from deeplearning4j_tpu.parallel import (DeviceMesh, init_moe_params,
                                                 place_moe_params, switch_moe)

        mesh = DeviceMesh(data=2, model=4)
        params = init_moe_params(jax.random.key(1), d_model=8, d_hidden=16,
                                 n_experts=4)
        params = place_moe_params(params, mesh)
        x = rng.normal(size=(32, 8)).astype(np.float32)
        w_target = rng.normal(size=(8, 8)).astype(np.float32)
        y_target = jnp.asarray(x @ w_target)
        xj = jnp.asarray(x)

        @jax.jit
        def step(params):
            def loss_fn(p):
                y, aux = switch_moe(p, xj)
                return ((y + xj - y_target) ** 2).mean() + 0.01 * aux
            loss, grads = jax.value_and_grad(loss_fn)(params)
            return jax.tree_util.tree_map(lambda p, g: p - 0.05 * g,
                                          params, grads), loss

        with mesh.mesh:
            losses = []
            for _ in range(80):
                params, l = step(params)
                losses.append(float(l))
        assert losses[-1] < losses[0] * 0.75, (losses[0], losses[-1])


class TestSparkShims:
    def test_spark_dl4j_multilayer(self, rng):
        """SparkDl4jMultiLayer surface trains DP over the mesh (the reference
        Spark stack collapsed into SPMD)."""
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer,
        )

        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(lr=0.3))
                .list()
                .layer(DenseLayer(n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(5).build())
        x = rng.normal(size=(64, 4)).astype(np.float32)
        w = rng.normal(size=(4, 3)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[np.argmax(x @ w, axis=1)]
        it = ArrayDataSetIterator(x, y, batch_size=32)
        spark_net = SparkDl4jMultiLayer(DeviceMesh(data=8), conf, tm)
        net = spark_net.fit(it, epochs=15)
        ev = net.evaluate(it)
        assert ev.accuracy() > 0.8


class TestSequenceParallelExtended:
    """Gradient flow through the ring, causal Ulysses, and the full
    sequence-sharded encoder block vs the single-device layer."""

    def test_ring_gradient_matches_reference(self, rng):
        from deeplearning4j_tpu.parallel.sequence import ring_attention

        mesh = DeviceMesh(data=2, seq=4)
        B, H, T, D = 1, 2, 16, 4
        q = rng.normal(size=(B, H, T, D)).astype(np.float32)
        k = rng.normal(size=(B, H, T, D)).astype(np.float32)
        v = rng.normal(size=(B, H, T, D)).astype(np.float32)

        def ring_loss(q, k, v):
            return (ring_attention(q, k, v, mesh.mesh, causal=True) ** 2).sum()

        def ref_loss(q, k, v):
            d = q.shape[-1]
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(1.0 * d)
            mask = jnp.tril(jnp.ones((q.shape[2], q.shape[2]), bool))
            logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
            w = jax.nn.softmax(logits, -1)
            return (jnp.einsum("bhqk,bhkd->bhqd", w, v) ** 2).sum()

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_ring_backward_fused_block_kernel_equals_the_two_calls(self, rng, monkeypatch):
        """Every ring step's block backward (``flash_block_bwd`` under
        ``shard_map``, ``vma`` given, ``causal`` only on the diagonal) through
        the fused kernel, against the dq and dk/dv pair forced by
        ``bwd_tiles``: 4 devices, causal, two tiles along each block's axes."""
        import importlib

        from deeplearning4j_tpu.parallel.sequence import ring_attention

        flash = importlib.import_module("deeplearning4j_tpu.ops.pallas.flash_attention")
        mesh = DeviceMesh(data=1, seq=4, devices=jax.devices()[:4])
        q, k, v, do = (jnp.asarray(rng.normal(size=(1, 2, 128, 128)).astype(np.float32))
                       for _ in range(4))
        ran = []

        def gradients(fused):
            def tiles(block_q, block_k, head_dim, seq_q, seq_k, itemsize):
                assert (seq_q, seq_k) == (32, 32)           # the local block's
                ran.append(fused)
                return flash.BwdTiles(16, 16, fused)

            monkeypatch.setattr(flash, "bwd_tiles", tiles)
            return jax.grad(lambda q, k, v: (ring_attention(
                q, k, v, mesh.mesh, causal=True, impl="flash") * do).sum(),
                argnums=(0, 1, 2))(q, k, v)

        fused, two = gradients(True), gradients(False)
        assert True in ran and False in ran
        for name, a, b in zip("qkv", fused, two):
            assert float(jnp.abs(a).max()) > 0
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f"d{name}")

    def test_ulysses_causal(self, rng):
        from deeplearning4j_tpu.parallel.sequence import ulysses_attention

        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 2, 8, 32, 4
        q = rng.normal(size=(B, H, T, D)).astype(np.float32)
        k = rng.normal(size=(B, H, T, D)).astype(np.float32)
        v = rng.normal(size=(B, H, T, D)).astype(np.float32)
        out = np.asarray(ulysses_attention(jnp.asarray(q), jnp.asarray(k),
                                           jnp.asarray(v), mesh.mesh, causal=True))
        ref = TestRingAttention()._reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_encoder_block_matches_layer(self, rng, impl):
        import jax as _jax

        from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderLayer
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.parallel.sequence import sequence_parallel_encoder

        D, H, T, B = 16, 8, 32, 2
        layer = TransformerEncoderLayer(d_model=D, n_heads=H, causal=True)
        params, state = layer.init(_jax.random.key(0),
                                   InputType.recurrent(D, T))
        x = rng.normal(size=(B, T, D)).astype(np.float32)
        want, _ = layer.apply(params, state, jnp.asarray(x))

        mesh = DeviceMesh(data=1, seq=8)
        got = sequence_parallel_encoder(params, jnp.asarray(x), mesh.mesh,
                                        n_heads=H, causal=True, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_encoder_block_gradients(self, rng):
        import jax as _jax

        from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderLayer
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.parallel.sequence import sequence_parallel_encoder

        D, H, T, B = 8, 4, 16, 1
        layer = TransformerEncoderLayer(d_model=D, n_heads=H, causal=False)
        params, state = layer.init(_jax.random.key(1), InputType.recurrent(D, T))
        x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
        mesh = DeviceMesh(data=2, seq=4)

        g_sp = jax.grad(lambda p: (sequence_parallel_encoder(
            p, x, mesh.mesh, n_heads=H) ** 2).sum())(params)
        g_ref = jax.grad(lambda p: (layer.apply(p, state, x)[0] ** 2).sum())(params)
        for k in g_ref:
            np.testing.assert_allclose(np.asarray(g_sp[k]), np.asarray(g_ref[k]),
                                       rtol=1e-3, atol=1e-4, err_msg=k)


class TestEncodedGradientSharing:
    """EncodedGradientsAccumulator/ThresholdAlgorithm analog: ternary
    threshold encoding with error feedback over the data axis."""

    def test_encode_and_residual(self):
        from deeplearning4j_tpu.parallel import threshold_encode

        g = jnp.asarray([0.5, -0.002, 0.0009, -3.0, 0.001])
        q, r = threshold_encode(g, 0.001)
        np.testing.assert_allclose(np.asarray(q),
                                   [0.001, -0.001, 0, -0.001, 0.001])
        np.testing.assert_allclose(np.asarray(q + r), np.asarray(g), rtol=1e-6)

    def test_trainer_converges_and_stays_synced(self, rng):
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import EncodedGradientTrainer

        mesh = DeviceMesh(data=8)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        true_w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
        Y = X @ true_w

        def loss_fn(params, x, y):
            return ((x @ params["w"] - y) ** 2).mean()

        trainer = EncodedGradientTrainer(loss_fn, Sgd(lr=0.3), mesh.mesh,
                                         threshold=5e-3, adaptive=False)
        carry = trainer.init({"w": jnp.zeros((4, 1), jnp.float32)})
        losses = []
        for _ in range(400):
            carry, loss = trainer.fit_batch(carry, X, Y)
            losses.append(float(loss))
        # error feedback means encoded training still converges
        assert losses[-1] < 0.05 * losses[0], (losses[0], losses[-1])
        np.testing.assert_allclose(np.asarray(carry["params"]["w"]), true_w,
                                   atol=0.3)

    def test_adaptive_threshold_tracks_density(self, rng):
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import EncodedGradientTrainer

        mesh = DeviceMesh(data=8)
        X = rng.normal(size=(32, 16)).astype(np.float32)
        Y = rng.normal(size=(32, 1)).astype(np.float32)

        def loss_fn(params, x, y):
            return ((x @ params["w"] - y) ** 2).mean()

        trainer = EncodedGradientTrainer(loss_fn, Sgd(lr=0.01), mesh.mesh,
                                         threshold=1e-6,  # far too permissive
                                         target_density=0.25)
        carry = trainer.init({"w": jnp.zeros((16, 1), jnp.float32)})
        thr0 = float(carry["thr"])
        for _ in range(50):
            carry, _ = trainer.fit_batch(carry, X, Y)
        # density >> target at thr=1e-6, so the threshold must have grown
        assert float(carry["thr"]) > thr0 * 5

    def test_tuple_params_and_bf16_dtypes(self, rng):
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import EncodedGradientTrainer

        mesh = DeviceMesh(data=8)
        X = rng.normal(size=(32, 3)).astype(np.float32)
        Y = rng.normal(size=(32, 1)).astype(np.float32)

        # params tree CONTAINING a tuple + a bf16 leaf
        def loss_fn(params, x, y):
            w1, w2 = params["layers"]
            h = jnp.tanh(x @ w1.astype(jnp.float32))
            return ((h @ w2 - y) ** 2).mean()

        p0 = {"layers": (jnp.zeros((3, 4), jnp.bfloat16),
                         jnp.zeros((4, 1), jnp.float32))}
        tr = EncodedGradientTrainer(loss_fn, Sgd(lr=0.05), mesh.mesh,
                                    threshold=5e-3, adaptive=False)
        carry = tr.init(p0)
        for _ in range(5):
            carry, loss = tr.fit_batch(carry, X, Y)
        w1, w2 = carry["params"]["layers"]
        assert w1.dtype == jnp.bfloat16    # dtype preserved, no f32 creep
        assert w2.dtype == jnp.float32
        assert carry["residual"]["layers"][0].dtype == jnp.bfloat16
        assert np.isfinite(float(loss))


class TestLongContext:
    """Long-sequence sanity at scale: the memory the ring saves is the point
    — each device only ever holds T/n keys — but correctness must hold at
    realistic T too, not just toy blocks."""

    def test_ring_attention_t1024(self, rng):
        from deeplearning4j_tpu.parallel.sequence import ring_attention

        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 1, 2, 1024, 16
        q = rng.normal(size=(B, H, T, D)).astype(np.float32)
        k = rng.normal(size=(B, H, T, D)).astype(np.float32)
        v = rng.normal(size=(B, H, T, D)).astype(np.float32)
        out = np.asarray(ring_attention(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), mesh.mesh, causal=True))
        ref = TestRingAttention()._reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=5e-4, atol=5e-5)

    def test_flash_kernel_long_sequence(self, rng):
        """Flash kernel (interpret mode off-TPU) at T=1024, the registry's
        long-sequence regime."""
        from deeplearning4j_tpu.ops.attention import dot_product_attention
        from deeplearning4j_tpu.ops.pallas import flash_attention

        B, H, T, D = 1, 2, 1024, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        got = np.asarray(flash_attention(q, k, v, causal=True))
        want = np.asarray(dot_product_attention(q, k, v, causal=True))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.slow  # ~30s/case: flash-core ring grads over the 8-way mesh
class TestRingFlashCore:
    """Ring attention with the Pallas flash kernel as its per-shard core
    (VERDICT r1 #1): forward parity AND gradient parity vs the single-device
    XLA attention, at TPU-aligned shapes (head_dim 128). The backward is the
    true ring backward — dk/dv partials travel with their rotating blocks —
    so per-device memory stays O(T/n * D) for training, not just inference."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_and_grad_match_reference(self, rng, causal):
        from deeplearning4j_tpu.ops.attention import dot_product_attention
        from deeplearning4j_tpu.parallel.sequence import ring_attention

        mesh = DeviceMesh(data=1, seq=8)
        # shapes sized for the CPU interpreter (H=2/T=512 cost ~110 s per
        # variant and added no block-coverage over T=256: t_local=32 is
        # still multi-row, multi-ring-step); at-scale shapes run in the
        # driver dryrun and the on-chip longcontext bench
        B, H, T, D = 1, 1, 256, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        do = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))

        out = ring_attention(q, k, v, mesh.mesh, causal=causal, impl="flash")
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

        g_ring = jax.grad(lambda q, k, v: (ring_attention(
            q, k, v, mesh.mesh, causal=causal, impl="flash") * do).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: (dot_product_attention(
            q, k, v, causal=causal) * do).sum(), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=5e-5,
                                       err_msg=f"d{name} causal={causal}")

    def test_auto_selects_flash_when_aligned(self, rng):
        """impl=None picks the flash core for aligned shapes and einsum
        otherwise (head_dim not lane-aligned)."""
        import importlib

        seq_mod = importlib.import_module("deeplearning4j_tpu.parallel.sequence")
        assert seq_mod._flash_core_ok(128, 64)
        assert not seq_mod._flash_core_ok(64, 64)      # head_dim unaligned
        assert not seq_mod._flash_core_ok(128, 4)      # local seq too short


class TestMultiSlice:
    """Multi-slice (DCN) story: a 'dcn' x 'data' mesh on 8 virtual devices —
    2 simulated slices of 4 — with the encoded-update exchange crossing the
    slice boundary while gradients stay full-precision inside each slice
    (the reference's fast-local/Aeron-remote tier split, SURVEY §2.4)."""

    def test_multi_slice_mesh_shape(self):
        from deeplearning4j_tpu.parallel import multi_slice_mesh

        mesh = multi_slice_mesh(2)
        assert mesh.axis_names == ("dcn", "data")
        assert mesh.devices.shape == (2, 4)
        with pytest.raises(ValueError):
            multi_slice_mesh(3)  # 8 devices don't split into 3 slices

    def test_hierarchical_encoded_trainer_converges(self, rng):
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import (EncodedGradientTrainer,
                                                 multi_slice_mesh)

        mesh = multi_slice_mesh(2)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        true_w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
        Y = X @ true_w

        def loss_fn(params, x, y):
            return ((x @ params["w"] - y) ** 2).mean()

        trainer = EncodedGradientTrainer(loss_fn, Sgd(lr=0.3), mesh,
                                         axis="dcn", ici_axis="data",
                                         threshold=5e-3, adaptive=False)
        carry = trainer.init({"w": jnp.zeros((4, 1), jnp.float32)})
        # residual is per-SLICE in hierarchical mode
        assert carry["residual"]["w"].shape == (2, 4, 1)
        losses = []
        for _ in range(400):
            carry, loss = trainer.fit_batch(carry, X, Y)
            losses.append(float(loss))
        assert losses[-1] < 0.05 * losses[0], (losses[0], losses[-1])
        np.testing.assert_allclose(np.asarray(carry["params"]["w"]), true_w,
                                   atol=0.3)

    def test_hierarchical_matches_flat_when_one_slice_per_device(self, rng):
        """With slice size 1 the hierarchy is degenerate: the hierarchical
        trainer over ('dcn'=8, 'data'=1) must follow the flat trainer over
        ('data'=8) step for step."""
        import numpy as _np

        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import (EncodedGradientTrainer,
                                                 multi_slice_mesh)
        from jax.sharding import Mesh

        X = rng.normal(size=(32, 4)).astype(np.float32)
        Y = rng.normal(size=(32, 1)).astype(np.float32)

        def loss_fn(params, x, y):
            return ((x @ params["w"] - y) ** 2).mean()

        flat = EncodedGradientTrainer(
            loss_fn, Sgd(lr=0.1), DeviceMesh(data=8).mesh,
            threshold=1e-3, adaptive=False)
        hier = EncodedGradientTrainer(
            loss_fn, Sgd(lr=0.1), multi_slice_mesh(8), axis="dcn",
            ici_axis="data", threshold=1e-3, adaptive=False)
        cf = flat.init({"w": jnp.zeros((4, 1), jnp.float32)})
        ch = hier.init({"w": jnp.zeros((4, 1), jnp.float32)})
        for _ in range(20):
            cf, lf = flat.fit_batch(cf, X, Y)
            ch, lh = hier.fit_batch(ch, X, Y)
        _np.testing.assert_allclose(np.asarray(cf["params"]["w"]),
                                    np.asarray(ch["params"]["w"]),
                                    rtol=1e-5, atol=1e-6)


class TestParameterAveraging:
    """The reference's ParameterAveragingTrainingMaster semantics done
    honestly (r2): K genuinely-local steps per replica, then ONE pmean of
    params (+ updater state). Not equivalent to sync DP for K>1 — that
    divergence is the algorithm."""

    def _problem(self, rng):
        X = rng.normal(size=(4 * 64, 6)).astype(np.float32)
        w_true = rng.normal(size=(6, 1)).astype(np.float32)
        return X, w_true, X @ w_true

    @staticmethod
    def _loss(p, x, y):
        return ((x @ p["w"] - y) ** 2).mean()

    def test_local_sgd_converges(self, rng):
        from deeplearning4j_tpu.optimize.updaters import Adam
        from deeplearning4j_tpu.parallel import ParameterAveragingTrainer

        X, w_true, Y = self._problem(rng)
        tr = ParameterAveragingTrainer(self._loss, Adam(lr=0.05),
                                       DeviceMesh(data=8).mesh,
                                       averaging_frequency=4)
        carry = tr.init({"w": jnp.zeros((6, 1))})
        for _ in range(60):
            carry, loss = tr.fit_round(carry, X, Y)
        w = tr.params(carry)["w"]
        np.testing.assert_allclose(np.asarray(w), w_true, atol=1e-3)

    def test_k1_matches_sync_dp(self, rng):
        """averaging_frequency=1 IS synchronous data parallel: every round
        must match a single-device step on the global batch exactly."""
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import ParameterAveragingTrainer

        X = rng.normal(size=(64, 6)).astype(np.float32)
        Y = rng.normal(size=(64, 1)).astype(np.float32)
        tr = ParameterAveragingTrainer(self._loss, Sgd(lr=0.1),
                                       DeviceMesh(data=8).mesh,
                                       averaging_frequency=1)
        carry = tr.init({"w": jnp.zeros((6, 1))})
        w_ref = jnp.zeros((6, 1))
        for i in range(10):
            carry, _ = tr.fit_round(carry, X, Y)
            g = jax.grad(lambda p: self._loss({"w": p}, X, Y))(w_ref)
            w_ref = w_ref - 0.1 * g
        np.testing.assert_allclose(np.asarray(tr.params(carry)["w"]),
                                   np.asarray(w_ref), rtol=1e-5, atol=1e-6)

    def test_k4_differs_from_sync_but_replicas_resync(self, rng):
        """K>1 must (a) differ from the K=1 trajectory (the local steps are
        real) and (b) leave all replica slots identical after the average."""
        from deeplearning4j_tpu.optimize.updaters import Sgd
        from deeplearning4j_tpu.parallel import ParameterAveragingTrainer

        X, _, Y = self._problem(rng)
        mesh = DeviceMesh(data=8).mesh
        t1 = ParameterAveragingTrainer(self._loss, Sgd(lr=0.1), mesh,
                                       averaging_frequency=1)
        t4 = ParameterAveragingTrainer(self._loss, Sgd(lr=0.1), mesh,
                                       averaging_frequency=4)
        c1, c4 = (t.init({"w": jnp.zeros((6, 1))}) for t in (t1, t4))
        for _ in range(3):
            c4, _ = t4.fit_round(c4, X, Y)
            # K=1 consumes the same data as 4 sequential global batches
            for k in range(4):
                c1, _ = t1.fit_round(c1, X[k * 64:(k + 1) * 64],
                                     Y[k * 64:(k + 1) * 64])
        w1, w4 = t1.params(c1)["w"], t4.params(c4)["w"]
        assert not np.allclose(np.asarray(w1), np.asarray(w4), atol=1e-6)
        # all replica slots identical post-average
        reps = np.asarray(c4["params"]["w"])
        assert np.allclose(reps, reps[:1], atol=0)


@pytest.mark.slow  # ~70s: zigzag ring fwd+bwd compile on the 8-way mesh
class TestZigzagRing:
    """Load-balanced causal ring attention (zig-zag stripe sharding): with
    contiguous blocks causal work is triangular across the ring (last device
    does n tiles while the first idles); zig-zag gives every device one
    stripe from each end so every ring step runs exactly two visible tiles
    per device. Correctness: exact parity (fwd and grads) with the
    single-device causal attention through the stripe permutation."""

    def test_fwd_and_grads_match_reference(self, rng):
        from deeplearning4j_tpu.ops.attention import dot_product_attention
        from deeplearning4j_tpu.parallel.sequence import ring_attention_zigzag

        mesh = DeviceMesh(data=1, seq=8)
        # interpreter-sized (was H=2/T=512 at ~550 s): T=256 still gives
        # 16-row zigzag stripes and 2 visible tiles/device/step — the
        # balance property under test is shape-independent beyond that
        B, H, T, D = 1, 1, 256, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        do = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))

        out = ring_attention_zigzag(q, k, v, mesh.mesh)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        gz = jax.grad(lambda q, k, v: (ring_attention_zigzag(
            q, k, v, mesh.mesh) * do).sum(), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: (dot_product_attention(
            q, k, v, causal=True) * do).sum(), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gz, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=5e-5,
                                       err_msg=f"d{name}")

    def test_permutation_is_involution_partition(self):
        from deeplearning4j_tpu.parallel.sequence import zigzag_permutation

        perm, inv = zigzag_permutation(64, 4)
        assert sorted(perm) == list(range(64))
        np.testing.assert_array_equal(perm[inv], np.arange(64))
        # device 0's local block = stripes 0 and 7
        assert list(perm[:8]) == list(range(8))
        assert list(perm[8:16]) == list(range(56, 64))

    def test_shape_guards(self, rng):
        from deeplearning4j_tpu.parallel.sequence import ring_attention_zigzag

        mesh = DeviceMesh(data=1, seq=8)
        q = jnp.zeros((1, 1, 100, 128))  # T not divisible into 16 stripes
        with pytest.raises(ValueError, match="divisible"):
            ring_attention_zigzag(q, q, q, mesh.mesh)
        q2 = jnp.zeros((1, 1, 512, 64))  # head_dim unaligned
        with pytest.raises(ValueError, match="flash core"):
            ring_attention_zigzag(q2, q2, q2, mesh.mesh)


class TestRingFlashShapeGuard:
    def test_forced_flash_on_unaligned_shapes_raises(self):
        """ADVICE r2: impl='flash' on shapes failing _flash_core_ok must be
        a clear ValueError, not a Mosaic internal error."""
        import pytest as _pytest

        from deeplearning4j_tpu.parallel import ring_attention

        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 1, 2, 64, 64          # D % 128 != 0
        q = jnp.ones((B, H, T, D))
        with _pytest.raises(ValueError, match="head_dim"):
            ring_attention(q, q, q, mesh.mesh, impl="flash")

    def test_merge_lse_posinf_guard(self):
        """A +inf lse (flash kernel's fully-masked-row sentinel) must mean
        'no contribution', not poison the other side of the merge."""
        from deeplearning4j_tpu.parallel.sequence import _merge_lse

        o = jnp.ones((1, 1, 4, 8))
        lse = jnp.zeros((1, 1, 4, 1))
        o_bad = jnp.full((1, 1, 4, 8), 7.0)
        lse_bad = jnp.full((1, 1, 4, 1), jnp.inf)
        merged, lse_new = _merge_lse(o, lse, o_bad, lse_bad)
        np.testing.assert_allclose(np.asarray(merged), np.asarray(o))
        np.testing.assert_allclose(np.asarray(lse_new), np.asarray(lse))


@pytest.mark.slow  # ~110s total: three permuted-domain compile-heavy cases
class TestZigzagAtScale:
    """r3 (VERDICT #7): the at-scale zigzag path — permute ONCE via
    zigzag_shard, run everything in the permuted domain (pre_permuted
    attention / impl='zigzag' encoder), no per-step gathers."""

    def test_shard_unshard_roundtrip(self, rng):
        from deeplearning4j_tpu.parallel import zigzag_shard, zigzag_unshard

        mesh = DeviceMesh(data=1, seq=8)
        x = jnp.asarray(rng.normal(size=(2, 3, 64, 4)).astype(np.float32))
        xz = zigzag_shard(x, mesh.mesh, seq_axis=2)
        assert not np.allclose(np.asarray(xz), np.asarray(x))
        np.testing.assert_array_equal(
            np.asarray(zigzag_unshard(xz, mesh.mesh, seq_axis=2)), np.asarray(x))

    def test_pre_permuted_attention_matches_reference(self, rng):
        from deeplearning4j_tpu.ops.attention import dot_product_attention
        from deeplearning4j_tpu.parallel import (ring_attention_zigzag,
                                                 zigzag_shard, zigzag_unshard)

        mesh = DeviceMesh(data=1, seq=8)
        B, H, T, D = 1, 1, 256, 128
        q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
        sh = lambda a: zigzag_shard(a, mesh.mesh, seq_axis=2)
        out_z = ring_attention_zigzag(sh(q), sh(k), sh(v), mesh.mesh,
                                      pre_permuted=True)
        out = zigzag_unshard(out_z, mesh.mesh, seq_axis=2)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_encoder_zigzag_matches_layer(self, rng):
        """Encoder block through the balanced causal ring core, whole
        computation in the permuted domain."""
        import jax as _jax

        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderLayer
        from deeplearning4j_tpu.parallel import (sequence_parallel_encoder,
                                                 zigzag_shard, zigzag_unshard)

        Hh, D, T, B = 1, 128, 128, 1
        layer = TransformerEncoderLayer(d_model=D, n_heads=Hh, causal=True)
        params, state = layer.init(_jax.random.key(0),
                                   InputType.recurrent(D, T))
        x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32) * 0.3)
        want, _ = layer.apply(params, state, x)

        mesh = DeviceMesh(data=1, seq=8)
        xz = zigzag_shard(x, mesh.mesh, seq_axis=1)
        got_z = sequence_parallel_encoder(params, xz, mesh.mesh, n_heads=Hh,
                                          causal=True, impl="zigzag")
        got = zigzag_unshard(got_z, mesh.mesh, seq_axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)

    def test_encoder_zigzag_gradients_in_permuted_domain(self, rng):
        """A permutation-invariant loss on the PERMUTED output gives the
        same param grads as the reference layer — i.e. training never needs
        to leave the zigzag domain."""
        import jax as _jax

        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.attention import TransformerEncoderLayer
        from deeplearning4j_tpu.parallel import (sequence_parallel_encoder,
                                                 zigzag_shard)

        Hh, D, T, B = 1, 128, 128, 1
        layer = TransformerEncoderLayer(d_model=D, n_heads=Hh, causal=True)
        params, state = layer.init(_jax.random.key(1),
                                   InputType.recurrent(D, T))
        x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32) * 0.3)
        mesh = DeviceMesh(data=1, seq=8)
        xz = zigzag_shard(x, mesh.mesh, seq_axis=1)

        g_sp = jax.grad(lambda p: (sequence_parallel_encoder(
            p, xz, mesh.mesh, n_heads=Hh, causal=True,
            impl="zigzag") ** 2).sum())(params)
        g_ref = jax.grad(lambda p: (layer.apply(p, state, x)[0] ** 2).sum())(params)
        for k in g_ref:
            np.testing.assert_allclose(np.asarray(g_sp[k]), np.asarray(g_ref[k]),
                                       rtol=2e-3, atol=2e-4, err_msg=k)

    def test_zigzag_encoder_requires_causal(self):
        from deeplearning4j_tpu.parallel import sequence_parallel_encoder

        mesh = DeviceMesh(data=1, seq=8)
        with pytest.raises(ValueError, match="CAUSAL"):
            sequence_parallel_encoder({}, jnp.zeros((1, 128, 128)), mesh.mesh,
                                      n_heads=1, causal=False, impl="zigzag")


class TestSparkLocalSgdRouting:
    """r3: the Spark facade HONORS averaging_frequency — K>1 routes fit()
    to the real local-SGD ParameterAveragingTrainer over the model's
    functional loss and writes averaged params back into the network."""

    def _data(self, rng, n=256):
        x = rng.normal(size=(n, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

        return x, y, ArrayDataSetIterator(x, y, batch_size=64)

    def test_k4_trains_and_syncs_back(self, rng):
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        x, y, it = self._data(rng)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        net = _model(seed=11)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), net, tm)
        l0 = net.score((x, y))
        spark.fit(it, epochs=12)
        l1 = net.score((x, y))
        assert l1 < l0 * 0.8, (l0, l1)

    def test_k1_unchanged_sync_path(self, rng):
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        x, y, it = self._data(rng)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(1).build())
        net = _model(seed=11)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), net, tm)
        l0 = net.score((x, y))
        spark.fit(it, epochs=3)
        assert net.score((x, y)) < l0

    def test_k1_bn_model_stays_exact_sync(self, rng):
        """averaging_frequency=1 with a BN model routes through the
        ParallelWrapper SPMD path — the model's OWN train step (global
        batch statistics, fused updater), i.e. exactly what single-device
        fit computes on the global batch. BN is no reason to reject K=1."""
        from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(lr=0.1))
                .list()
                .layer(DenseLayer(n_out=16, activation="relu"))
                .layer(BatchNormalizationLayer())
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        x, y, it = self._data(rng)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(1).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), conf, tm)
        net = spark.network
        l0 = net.score((x, y))
        spark.fit(it, epochs=3)
        assert np.isfinite(net.score((x, y))) and net.score((x, y)) < l0

    def test_bn_dropout_l2_train_on_k4_path(self, rng):
        """r4 (VERDICT r3 #4): the stateful functional surface — BN
        running stats and the dropout rng thread through as_loss_fn, and
        l1/l2 lands in the loss — so the configs the r3 guards rejected
        now genuinely TRAIN with averaging_frequency > 1, and the synced
        running stats flow back into the network."""
        from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(lr=0.1))
                .list()
                .layer(DenseLayer(n_out=16, activation="relu", dropout=0.25,
                                  l2=1e-4))
                .layer(BatchNormalizationLayer())
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        x, y, it = self._data(rng, n=256)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), conf, tm)
        net = spark.network
        state_before = jax.tree_util.tree_map(np.asarray, net.state)
        l0 = net.score((x, y))
        spark.fit(it, epochs=12)
        l1 = net.score((x, y))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)
        # BN running stats moved and were written back
        moved = jax.tree_util.tree_reduce(
            lambda a, b: a or b,
            jax.tree_util.tree_map(
                lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
                state_before, jax.tree_util.tree_map(np.asarray, net.state)),
            False)
        assert moved, "BN running stats did not flow back after local SGD"

    def test_frozen_and_per_layer_updaters_train_on_local_sgd(self, rng):
        """r5: PerEntryUpdater carries the network's own updater selection
        onto the functional trainer — frozen layers stay bit-identical
        while the rest trains, and per-layer overrides apply (reference:
        the master averages transfer-learned models like any other)."""
        from deeplearning4j_tpu.optimize import Adam
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(lr=0.1))
                .list()
                .layer(DenseLayer(n_out=8, activation="relu",
                                  trainable=False))
                .layer(DenseLayer(n_out=8, activation="relu",
                                  updater=Adam(lr=0.01)))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        x, y, it = self._data(rng, n=256)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), conf, tm)
        net = spark.network
        frozen_before = jax.tree_util.tree_map(np.asarray, net.params[0])
        middle_before = jax.tree_util.tree_map(np.asarray, net.params[1])
        l0 = net.score((x, y))
        spark.fit(it, epochs=8)
        l1 = net.score((x, y))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            frozen_before, net.params[0])     # frozen: bit-identical
        moved = jax.tree_util.tree_reduce(
            lambda a, b: a or b,
            jax.tree_util.tree_map(
                lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
                middle_before, net.params[1]), False)
        assert moved, "per-layer-updater layer did not train"

    def test_grad_clipping_trains_on_local_sgd(self, rng):
        """r5: conf.max_grad_norm rides the local steps (global-norm clip
        before the per-entry update, mirroring the fit path)."""
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(lr=0.1))
                .gradient_clipping(1.0).list()
                .layer(DenseLayer(n_out=8, activation="relu"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        x, y, it = self._data(rng, n=256)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), conf, tm)
        l0 = spark.network.score((x, y))
        spark.fit(it, epochs=8)
        l1 = spark.network.score((x, y))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)

    def test_multi_input_output_graph_on_local_sgd(self, rng):
        """r5: SparkComputationGraph analog — a 2-input/2-output graph
        trains at averaging_frequency>1 from a MultiDataSet stream (the
        reference's SparkComputationGraph + MultiDataSet RDDs); dict
        rounds flow through the same trainer."""
        from deeplearning4j_tpu.datasets import MultiDataSet
        from deeplearning4j_tpu.nn.conf.graph import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.05)).graph_builder()
                .add_inputs("a", "b")
                .set_input_types(**{"a": InputType.feed_forward(3),
                                    "b": InputType.feed_forward(5)})
                .add_layer("fa", DenseLayer(n_out=8, activation="relu"), "a")
                .add_layer("fb", DenseLayer(n_out=8, activation="relu"), "b")
                .add_vertex("m", MergeVertex(), "fa", "fb")
                .add_layer("o1", OutputLayer(n_out=2, activation="softmax",
                                             loss="mcxent"), "m")
                .add_layer("o2", OutputLayer(n_out=1, activation="identity",
                                             loss="mse"), "m")
                .set_outputs("o1", "o2")
                .build())
        n = 256
        a = rng.normal(size=(n, 3)).astype(np.float32)
        b = rng.normal(size=(n, 5)).astype(np.float32)
        cls = (a[:, 0] + b[:, 0] > 0).astype(np.int64)
        y1 = np.eye(2, dtype=np.float32)[cls]
        y2 = (a[:, :1] - b[:, :1]).astype(np.float32)

        class _Stream:
            def __iter__(self):
                mds = MultiDataSet([a, b], [y1, y2])
                return iter(mds.batches(64))

            def reset(self):
                pass

        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                    ComputationGraph(conf).init(), tm)
        net = spark.network
        l0 = float(net.score(MultiDataSet([a, b], [y1, y2])))
        spark.fit(_Stream(), epochs=16)
        l1 = float(net.score(MultiDataSet([a, b], [y1, y2])))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)
        out1 = np.asarray(net.output({"a": a, "b": b})[0])
        assert (out1.argmax(1) == cls).mean() > 0.7

    def test_single_io_graph_with_multidataset_stream(self, rng):
        """A 1-input/1-output ComputationGraph fed a MultiDataSet stream
        (the reference's SparkComputationGraph shape) must route through
        the multi path — the DataSet rebatcher would mis-shard its
        list-of-arrays features."""
        from deeplearning4j_tpu.datasets import MultiDataSet
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.1)).graph_builder()
                .add_inputs("in")
                .set_input_types(**{"in": InputType.feed_forward(8)})
                .add_layer("d", DenseLayer(n_out=8, activation="relu"),
                           "in")
                .add_layer("o", OutputLayer(n_out=4, activation="softmax",
                                            loss="mcxent"), "d")
                .set_outputs("o")
                .build())
        x = rng.normal(size=(256, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 256)]

        class _Stream:
            def __iter__(self):
                return iter(MultiDataSet([x], [y]).batches(64))

            def reset(self):
                pass

        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                    ComputationGraph(conf).init(), tm)
        net = spark.network
        l0 = float(net.score((x, y)))
        spark.fit(_Stream(), epochs=12)
        l1 = float(net.score((x, y)))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)

    def test_k1_sync_path_with_multidataset_stream(self, rng):
        """averaging_frequency=1 (sync SPMD) fed a MultiDataSet stream:
        the slot-aware rebatcher must route it — the DataSet rebatcher
        mis-sharded list features into a stacked mess (r5 bug, fixed)."""
        from deeplearning4j_tpu.datasets import MultiDataSet
        from deeplearning4j_tpu.nn.conf.graph import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.1)).graph_builder()
                .add_inputs("a", "b")
                .set_input_types(**{"a": InputType.feed_forward(3),
                                    "b": InputType.feed_forward(5)})
                .add_layer("fa", DenseLayer(n_out=8, activation="relu"), "a")
                .add_layer("fb", DenseLayer(n_out=8, activation="relu"), "b")
                .add_vertex("m", MergeVertex(), "fa", "fb")
                .add_layer("o", OutputLayer(n_out=2, activation="softmax",
                                            loss="mcxent"), "m")
                .set_outputs("o")
                .build())
        a = rng.normal(size=(128, 3)).astype(np.float32)
        b = rng.normal(size=(128, 5)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[
            (a[:, 0] + b[:, 0] > 0).astype(np.int64)]

        class _Stream:
            def __iter__(self):
                return iter(MultiDataSet([a, b], [y]).batches(64))

            def reset(self):
                pass

        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(1).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                    ComputationGraph(conf).init(), tm)
        net = spark.network
        l0 = float(net.score(MultiDataSet([a, b], [y])))
        spark.fit(_Stream(), epochs=8)
        l1 = float(net.score(MultiDataSet([a, b], [y])))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)

    def test_multi_rebatcher_pins_dict_slot_order_and_counts_drops(
            self, rng):
        from deeplearning4j_tpu.datasets import MultiDataSet
        from deeplearning4j_tpu.parallel.spark import \
            _RebatchingMultiIterator

        a1 = np.full((3, 2), 1.0, np.float32)
        b1 = np.full((3, 2), 10.0, np.float32)
        a2 = np.full((3, 2), 2.0, np.float32)
        b2 = np.full((3, 2), 20.0, np.float32)
        y = np.zeros((3, 1), np.float32)

        # second item's dict iterates in the REVERSE key order — slots
        # must still pool by key, not by position
        stream = [MultiDataSet({"a": a1, "b": b1}, [y]),
                  MultiDataSet({"b": b2, "a": a2}, [y])]
        out = list(_RebatchingMultiIterator(stream, 4, dp=2))
        got_a = np.concatenate([np.asarray(o.features["a"]) for o in out])
        got_b = np.concatenate([np.asarray(o.features["b"]) for o in out])
        assert (got_a < 5).all(), got_a       # only 1.0/2.0 values
        assert (got_b >= 10).all(), got_b     # only 10/20 values
        # mismatched key sets fail loud
        bad = [MultiDataSet({"a": a1, "b": b1}, [y]),
               MultiDataSet({"a": a2, "c": b2}, [y])]
        with pytest.raises(ValueError, match="slot keys changed"):
            list(_RebatchingMultiIterator(bad, 4, dp=2))

    def test_multi_local_sgd_pools_across_epochs_and_warns(self, rng):
        """60-row stream with global_batch=64: single epochs drop
        everything, but rounds must complete by pooling rows ACROSS
        epochs (the r4 accumulator semantics) and leftovers must warn."""
        import warnings as _w

        from deeplearning4j_tpu.datasets import MultiDataSet
        from deeplearning4j_tpu.nn.conf.graph import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.1)).graph_builder()
                .add_inputs("a", "b")
                .set_input_types(**{"a": InputType.feed_forward(3),
                                    "b": InputType.feed_forward(5)})
                .add_layer("fa", DenseLayer(n_out=8, activation="relu"), "a")
                .add_layer("fb", DenseLayer(n_out=8, activation="relu"), "b")
                .add_vertex("m", MergeVertex(), "fa", "fb")
                .add_layer("o", OutputLayer(n_out=2, activation="softmax",
                                            loss="mcxent"), "m")
                .set_outputs("o")
                .build())
        a = rng.normal(size=(60, 3)).astype(np.float32)
        b = rng.normal(size=(60, 5)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 60)]

        class _Stream:
            def __iter__(self):
                return iter(MultiDataSet([a, b], [y]).batches(60))

            def reset(self):
                pass

        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(2).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                    ComputationGraph(conf).init(), tm)
        net = spark.network
        p0 = jax.tree_util.tree_map(np.asarray, net.params)
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            # 4 epochs x 60 rows = 240 rows = 3 global batches of 64 ->
            # one full K=2 round runs (params move), 1 pending batch +
            # 48 leftover rows -> warning
            spark.fit(_Stream(), epochs=4)
        moved = any(
            bool(np.any(np.asarray(x1) != np.asarray(x0)))
            for x0, x1 in zip(jax.tree_util.tree_leaves(p0),
                              jax.tree_util.tree_leaves(net.params)))
        assert moved, "rounds never completed despite cross-epoch pooling"
        assert any("dropped" in str(r.message) for r in rec)

    def test_one_shot_generator_keeps_first_batch_at_k1(self, rng):
        """The multi-stream peek must not consume a one-shot generator's
        first (and only) DataSet on the K=1 path."""
        from deeplearning4j_tpu.datasets import DataSet
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.1)).graph_builder()
                .add_inputs("in")
                .set_input_types(**{"in": InputType.feed_forward(8)})
                .add_layer("d", DenseLayer(n_out=8, activation="relu"),
                           "in")
                .add_layer("o", OutputLayer(n_out=4, activation="softmax",
                                            loss="mcxent"), "d")
                .set_outputs("o")
                .build())
        x = rng.normal(size=(64, 8)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(1).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                    ComputationGraph(conf).init(), tm)
        net = spark.network
        p0 = jax.tree_util.tree_map(np.asarray, net.params)
        spark.fit(iter([DataSet(x, y)]), epochs=1)   # one-shot generator
        moved = any(
            bool(np.any(np.asarray(x1) != np.asarray(x0)))
            for x0, x1 in zip(jax.tree_util.tree_leaves(p0),
                              jax.tree_util.tree_leaves(net.params)))
        assert moved, "the peek swallowed the only batch"

    def test_masked_multidataset_trains_on_local_sgd(self, rng):
        from deeplearning4j_tpu.datasets import MultiDataSet
        from deeplearning4j_tpu.nn.conf.graph import MergeVertex
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)
        from deeplearning4j_tpu.nn.layers import (GravesLSTMLayer,
                                                  RnnOutputLayer)

        conf = (NeuralNetConfiguration.builder().seed(4)
                .updater(Sgd(lr=0.05)).graph_builder()
                .add_inputs("s", "t")
                .set_input_types(**{"s": InputType.recurrent(2, None),
                                    "t": InputType.recurrent(2, None)})
                .add_layer("ls", GravesLSTMLayer(n_out=4,
                                                 activation="tanh"), "s")
                .add_layer("lt", GravesLSTMLayer(n_out=4,
                                                 activation="tanh"), "t")
                .add_layer("o1", RnnOutputLayer(n_out=2,
                                                activation="softmax",
                                                loss="mcxent"), "ls")
                .add_layer("o2", RnnOutputLayer(n_out=2,
                                                activation="softmax",
                                                loss="mcxent"), "lt")
                .set_outputs("o1", "o2")
                .build())
        s = rng.normal(size=(64, 6, 2)).astype(np.float32)
        y = np.zeros((64, 6, 2), np.float32)
        y[..., 0] = 1.0
        m = np.ones((64, 6), np.float32)

        class _Stream:
            def __iter__(self):
                return iter(MultiDataSet([s, s], [y, y],
                                         features_mask=m).batches(32))

            def reset(self):
                pass

        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(4).averaging_frequency(4).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                    ComputationGraph(conf).init(), tm)
        net = spark.network
        mds_all = MultiDataSet([s, s], [y, y], features_mask=m)
        l0 = float(net.score(mds_all))
        spark.fit(_Stream(), epochs=8)   # r5: shared-mask multi TRAINS
        l1 = float(net.score(mds_all))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)

        # per-output labels-mask lists stay rejected with guidance
        class _BadStream:
            def __iter__(self):
                return iter(MultiDataSet(
                    [s, s], [y, y],
                    labels_mask=[m, m]).batches(32))

            def reset(self):
                pass

        spark2 = SparkDl4jMultiLayer(DeviceMesh(data=8),
                                     ComputationGraph(conf).init(), tm)
        with pytest.raises(ValueError, match="per-output labels masks"):
            spark2.fit(_BadStream(), epochs=1)

    def test_unsupported_configs_rejected_loudly(self, rng):
        """What the round plumbing genuinely cannot express (center loss)
        is still refused loudly."""
        from deeplearning4j_tpu.nn.layers import CenterLossOutputLayer
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        conf = (NeuralNetConfiguration.builder().seed(4).updater(Sgd(lr=0.1))
                .list()
                .layer(DenseLayer(n_out=8, activation="relu"))
                .layer(CenterLossOutputLayer(n_out=4, activation="softmax",
                                             loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        x, y, it = self._data(rng, n=256)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), conf, tm)
        with pytest.raises(NotImplementedError, match="center loss"):
            spark.fit(it, epochs=1)

    def test_uneven_tail_dropped_with_warning(self, rng):
        import warnings as _w

        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        x = rng.normal(size=(200, 8)).astype(np.float32)   # 64,64,64 + 8 tail
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 200)]
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

        it = ArrayDataSetIterator(x, y, batch_size=64)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        net = _model(seed=11)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), net, tm)
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            spark.fit(it, epochs=4)   # 12 full batches -> 3 rounds
        assert any("dropped" in str(r.message) for r in rec)

    def test_graph_model_k_gt_1_trains(self, rng):
        """ComputationGraph models route through CG.as_loss_fn on the
        K>1 local-SGD path too."""
        from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkComputationGraph)

        gb = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(lr=0.2))
              .graph_builder().add_inputs("in")
              .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
              .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                            loss="mcxent"), "d")
              .set_input_types(**{"in": InputType.feed_forward(8)})
              .set_outputs("out"))
        conf = gb.build()
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        x = rng.normal(size=(256, 8)).astype(np.float32)
        w = rng.normal(size=(8, 4)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, 1)]
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

        it = ArrayDataSetIterator(x, y, batch_size=64)
        spark = SparkComputationGraph(DeviceMesh(data=8), conf, tm)
        net = spark.fit(it, epochs=12)
        out = np.asarray(net.output(x))
        acc = (out.argmax(1) == y.argmax(1)).mean()
        assert acc > 0.8, acc


class TestMaskedLocalSGD:
    """r5 (VERDICT r4 #3): masked DataSets on the averaging_frequency>1
    path — as_loss_fn takes (mask, label_mask), each local step normalizes
    by its shard's valid count, and the spark rebatcher's mask
    concatenation feeds the rounds."""

    def _seq_model(self, seed=3, lr=0.05):
        from deeplearning4j_tpu.nn.layers import LSTMLayer, RnnOutputLayer

        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Sgd(lr=lr)).list()
                .layer(LSTMLayer(n_out=8))
                .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(4, 6)).build())
        return MultiLayerNetwork(conf).init()

    def _masked_data(self, rng, n=256, T=6, F=4, C=3):
        from deeplearning4j_tpu.datasets import DataSet

        x = rng.normal(size=(n, T, F)).astype(np.float32)
        # learnable per-step signal (argmax of the first C features)
        cls = np.argmax(x[..., :C], axis=-1)
        y = np.eye(C, dtype=np.float32)[cls]
        mask = np.ones((n, T), np.float32)
        lens = rng.integers(2, T + 1, n)     # UNEVEN padding across rows
        for i, L in enumerate(lens):
            mask[i, L:] = 0.0
        return x, y, mask, [DataSet(x[i:i + 32], y[i:i + 32],
                                    features_mask=mask[i:i + 32])
                            for i in range(0, n, 32)]

    def test_padded_lstm_trains_at_k4_via_spark(self, rng):
        """The exact r4 rejection case: a padded-sequence LSTM config at
        averaging_frequency=4 — must TRAIN now, through the rebatcher's
        mask concatenation."""
        from deeplearning4j_tpu.datasets import DataSet
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer)

        x, y, mask, batches = self._masked_data(rng)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        net = self._seq_model(lr=0.3)
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), net, tm)
        l0 = net.score(DataSet(x, y, features_mask=mask))
        spark.fit(batches, epochs=15)
        l1 = net.score(DataSet(x, y, features_mask=mask))
        assert np.isfinite(l1) and l1 < l0 * 0.8, (l0, l1)

    def test_k1_round_equals_single_device_fit_with_masks(self, rng):
        """K=1 IS sync DP, masks included: one masked round must equal one
        single-device fit_batch on the same global batch EXACTLY, even
        with padding distributed unevenly across the 8 shards (the
        global-valid/dp denominator)."""
        from deeplearning4j_tpu.datasets import DataSet
        from deeplearning4j_tpu.parallel import ParameterAveragingTrainer

        x, y, mask, _ = self._masked_data(rng, n=64)
        net_a = self._seq_model(seed=21)
        net_b = self._seq_model(seed=21)
        loss_fn, (p0, s0) = net_a.as_loss_fn(train=True)
        tr = ParameterAveragingTrainer(loss_fn, Sgd(lr=0.05),
                                       DeviceMesh(data=8).mesh,
                                       averaging_frequency=1, stateful=True)
        carry = tr.init(p0, state=s0, rng=jax.random.key(0))
        losses_tr, losses_fit = [], []
        for _ in range(3):
            carry, l = tr.fit_round(carry, x, y, mask=mask)
            losses_tr.append(float(l))
            losses_fit.append(net_b.fit_batch(DataSet(x, y,
                                                      features_mask=mask)))
        for pa, pb in zip(tr.params(carry), net_b.params):
            for ka in pa:
                np.testing.assert_allclose(np.asarray(pa[ka]),
                                           np.asarray(pb[ka]),
                                           rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(losses_tr, losses_fit, rtol=2e-5)

    def test_k4_masked_rounds_use_local_valid_counts(self, rng):
        """K>1 keeps the honest local-SGD semantics: replicas normalize by
        their OWN shard's valid count (no global denominator), so the
        trajectory differs from K=1 on the same data."""
        from deeplearning4j_tpu.parallel import ParameterAveragingTrainer

        x, y, mask, _ = self._masked_data(rng, n=256)
        mesh = DeviceMesh(data=8).mesh

        def make(k):
            net = self._seq_model(seed=5)
            loss_fn, (p0, s0) = net.as_loss_fn(train=True)
            tr = ParameterAveragingTrainer(loss_fn, Sgd(lr=0.05), mesh,
                                           averaging_frequency=k,
                                           stateful=True)
            return tr, tr.init(p0, state=s0, rng=jax.random.key(1))

        t4, c4 = make(4)
        t1, c1 = make(1)
        c4, _ = t4.fit_round(c4, x, y, mask=mask)
        for k in range(4):
            c1, _ = t1.fit_round(c1, x[k * 64:(k + 1) * 64],
                                 y[k * 64:(k + 1) * 64],
                                 mask=mask[k * 64:(k + 1) * 64])
        diff = False
        for pa, pb in zip(t4.params(c4), t1.params(c1)):
            for ka in pa:
                if not np.allclose(np.asarray(pa[ka]), np.asarray(pb[ka]),
                                   atol=1e-6):
                    diff = True
        assert diff, "K=4 local steps were not genuinely local"

    def test_mlm_dual_masks_on_k4_path(self, rng):
        """Distinct features/labels masks ride the functional surface too:
        a masked-LM-shaped batch trains at K=4 and routes the masks
        separately (garbage labels at loss-masked-out positions leave the
        round loss unchanged)."""
        from deeplearning4j_tpu.parallel import ParameterAveragingTrainer

        net = self._seq_model(seed=7)
        loss_fn, (p0, s0) = net.as_loss_fn(train=True)
        mesh = DeviceMesh(data=8).mesh
        x, y, mask, _ = self._masked_data(rng, n=64)
        lmask = np.zeros_like(mask)
        lmask[:, 1] = 1.0                   # loss covers ONE position
        y_g = y.copy()
        y_g[:, 2:] = 5.0                    # garbage at loss-masked steps

        def round_loss(yy):
            tr = ParameterAveragingTrainer(loss_fn, Sgd(lr=0.05), mesh,
                                           averaging_frequency=4,
                                           stateful=True)
            carry = tr.init(p0, state=s0, rng=jax.random.key(2))
            _, l = tr.fit_round(carry, x, yy, mask=mask, label_mask=lmask)
            return float(l)

        la, lb = round_loss(y), round_loss(y_g)
        assert la == pytest.approx(lb, rel=1e-5), (la, lb)


class TestConvShardingAndHeteroPipe:
    """r5 (VERDICT r4 #4): the conv flagship sharded — structure-based TP
    roles for Conv/BN on the ComputationGraph tier, and the heterogeneous
    GPipe (HeteroPipe) that carries ResNet-50-style stages whose
    activation shapes and param structures differ."""

    def _conv_graph(self, seed=11):
        from deeplearning4j_tpu.nn import ComputationGraph
        from deeplearning4j_tpu.nn.layers import (ActivationLayer,
                                                  BatchNormalizationLayer,
                                                  ConvolutionLayer,
                                                  GlobalPoolingLayer)

        g = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr=0.05))
             .graph_builder().add_inputs("in")
             .set_input_types(**{"in": InputType.convolutional(8, 8, 3)})
             .add_layer("c1", ConvolutionLayer(n_out=16, kernel=(3, 3),
                                               padding="same",
                                               has_bias=False), "in")
             .add_layer("bn1", BatchNormalizationLayer(), "c1")
             .add_layer("r1", ActivationLayer(activation="relu"), "bn1")
             .add_layer("c2", ConvolutionLayer(n_out=32, kernel=(3, 3),
                                               padding="same"), "r1")
             .add_layer("gp", GlobalPoolingLayer(pooling_type="avg"), "c2")
             .add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                           loss="mcxent"), "gp")
             .set_outputs("out").build())
        return ComputationGraph(g).init()

    def test_tp_conv_graph_matches_single_device(self, rng):
        """Conv kernels column-split over "model", BN replicated: the TP
        train step must reproduce the single-device step exactly (GSPMD
        layout hints never change the math)."""
        from deeplearning4j_tpu.parallel import TensorParallel

        x = rng.normal(size=(8, 8, 8, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]
        tp = TensorParallel(self._conv_graph(),
                            DeviceMesh(data=2, model=4))
        ref = self._conv_graph()
        l_tp = [tp.fit_batch((x, y)) for _ in range(3)]
        l_ref = [ref.fit_batch((x, y)) for _ in range(3)]
        np.testing.assert_allclose(l_tp, l_ref, rtol=2e-5)
        for name in ref.params:
            for k in ref.params[name]:
                np.testing.assert_allclose(
                    np.asarray(tp.model.params[name][k]),
                    np.asarray(ref.params[name][k]), rtol=1e-4, atol=1e-6)

    def test_tp_conv_specs_shard_conv_kernels(self):
        """The structure-based role table actually fires for conv layers:
        kernels get a "model"-sharded last axis, BN params replicate."""
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.parallel import TensorParallel

        tp = TensorParallel(self._conv_graph(), DeviceMesh(data=2, model=4))
        specs = tp.param_specs()
        assert specs["c1"]["W"] == P(None, None, None, "model")
        assert specs["c2"]["b"] == P("model")
        assert specs["bn1"]["gamma"] == P()

    def test_heteropipe_matches_sequential(self):
        """4 heterogeneous stages (shapes shrink 16->12->8->4, different
        param structures): pipelined output and grads == unpipelined."""
        from deeplearning4j_tpu.parallel import (HeteroPipe,
                                                 pack_stage_params)

        key = jax.random.key(0)
        dims = [16, 12, 8, 4, 4]
        stage_params, stage_fns = [], []
        for s in range(4):
            W = jax.random.normal(jax.random.fold_in(key, s),
                                  (dims[s], dims[s + 1])) * 0.4
            if s % 2 == 0:     # alternate param STRUCTURES
                stage_params.append({"W": W, "b": jnp.zeros(dims[s + 1])})
                stage_fns.append(
                    lambda p, x: jnp.tanh(x @ p["W"] + p["b"]))
            else:
                stage_params.append({"W": W})
                stage_fns.append(lambda p, x: jnp.tanh(x @ p["W"]))
        packed, metas = pack_stage_params(stage_params)
        mesh = DeviceMesh(data=1, pipe=4, devices=jax.devices()[:4])
        pipe = HeteroPipe(stage_fns, metas,
                          [(d,) for d in dims], mesh, n_microbatches=2)
        x = jax.random.normal(jax.random.fold_in(key, 9), (6, 16))
        with mesh.mesh:
            y = pipe(packed, x)
        y_ref = pipe.sequential_reference(packed, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)
        # pipelined backward == unpipelined backward
        with mesh.mesh:
            g = jax.jit(jax.grad(lambda p: (pipe(p, x) ** 2).sum()))(packed)
        g_ref = jax.grad(
            lambda p: (pipe.sequential_reference(p, x) ** 2).sum())(packed)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_graph_stage_fn_rejects_noncontiguous_cut(self):
        from deeplearning4j_tpu.parallel import graph_stage_fn

        m = self._conv_graph()
        # "r1" depends on bn1 which is neither in the slice nor the entry
        with pytest.raises(ValueError, match="outside the stage"):
            graph_stage_fn(m, ["r1", "c2"], "c1")

    def test_resnet50_pipeline_plan_shapes(self):
        """The four conv stage cuts are contiguous and the eval_shape
        probe reports the shrinking stage-entry activations."""
        from deeplearning4j_tpu.parallel import graph_stage_fn
        from deeplearning4j_tpu.zoo import ResNet50
        from deeplearning4j_tpu.zoo.resnet import resnet50_pipeline_plan

        m = ResNet50(height=16, width=16, num_classes=4,
                     dtype="float32").init()
        stages, head, shapes = resnet50_pipeline_plan(m, (16, 16, 3))
        assert len(stages) == 4 and head[-1] == "output"
        assert shapes[0] == (16, 16, 3) and shapes[-1][-1] == 2048
        # every cut is a closed contiguous slice (graph_stage_fn validates)
        entries = ["input"] + [s[-1] for s in stages[:-1]]
        for s, e in zip(stages, entries):
            graph_stage_fn(m, s, e)


class TestInferencePadBatches:
    def test_padded_partial_batches_return_correct_results(self, rng):
        """r5 serving fix: partially-filled batches are zero-padded to the
        next pow2 bucket before dispatch (bounded compile set); results
        must match the direct forward exactly for the REAL rows."""
        from deeplearning4j_tpu.parallel import ParallelInference

        model = _model(seed=2)
        xs = rng.normal(size=(5, 8)).astype(np.float32)   # -> bucket 8
        pi = ParallelInference(model, batch_limit=8,
                               queue_timeout_s=0.05).start()
        try:
            queues = [pi.submit(x) for x in xs]
            got = np.stack([q.get(timeout=30) for q in queues])
        finally:
            pi.stop()
        want = np.asarray(model.output(xs))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_pad_batches_bounds_the_compile_set(self, rng):
        """Every dispatched batch size is a power of two (or 1): the
        padded worker can only ever trace log2(limit)+1 programs."""
        from deeplearning4j_tpu.parallel import ParallelInference

        model = _model(seed=2)
        seen = []
        orig = model.output

        def spy(x, **kw):
            seen.append(np.shape(x)[0])
            return orig(x, **kw)

        model.output = spy
        pi = ParallelInference(model, batch_limit=16,
                               queue_timeout_s=0.02).start()
        try:
            for n in (3, 5, 7, 11, 13):
                qs = [pi.submit(rng.normal(size=8).astype(np.float32))
                      for _ in range(n)]
                for q in qs:
                    q.get(timeout=30)
        finally:
            pi.stop()
            model.output = orig
        assert seen and all(s == 1 or (s & (s - 1)) == 0 for s in seen), seen
