"""Int8 quantization subsystem tests (ISSUE 9).

Covers the tentpole witness list: per-channel absmax quantization round
trip, the fused quantized ops (dequantize on the ACCUMULATOR — the jaxpr
witness proves no full-size f32 weight copy is ever materialized), the
``quantize_network`` pass (rule whitelist, inference-view semantics, the
original stays trainable), zip serde round trip, the int8 KV-cache ring
(running absmax scales, requant-on-growth, decode parity against the f32
cache on the post-softmax distribution), the retrace-free compile-counter
guards, serving-gateway load-time quantization, and the monitoring tier's
zero-overhead contract.
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.attention import (
    PositionalEmbeddingLayer, TransformerEncoderLayer,
)
from deeplearning4j_tpu.nn.layers import EmbeddingSequenceLayer, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.registry import op
from deeplearning4j_tpu.quantize import (
    QUANT_RULES, QuantizedTensor, assert_no_dequantized_weights,
    dequantize_tensor, find_dequantized_weights, quantize_cache,
    quantize_params, quantize_tensor, ring_write_quantized,
)

V = 13  # tiny vocab for the decode fixtures


def _dense_net(seed=0, n_in=16, hidden=32, n_out=5):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _tf_net(seed=3, D=16, n_layers=2, max_len=32):
    b = NeuralNetConfiguration.builder().seed(seed).list()
    b = b.layer(EmbeddingSequenceLayer(n_out=D, n_in=V))
    b = b.layer(PositionalEmbeddingLayer(max_len=max_len))
    for _ in range(n_layers):
        b = b.layer(TransformerEncoderLayer(d_model=D, n_heads=2,
                                            causal=True))
    b = b.layer(RnnOutputLayer(n_out=V, activation="softmax", loss="mcxent"))
    conf = b.set_input_type(InputType.recurrent(V, 12)).build()
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def dense_net():
    return _dense_net()


@pytest.fixture(scope="module")
def qdense(dense_net):
    return dense_net.quantize()


# ------------------------------------------------------------ tensor core
class TestQuantizedTensor:
    def test_round_trip_error_bounded(self):
        rng = np.random.default_rng(0)
        w = rng.normal(size=(64, 32)).astype(np.float32)
        qt = quantize_tensor(w)
        assert qt.q.dtype == jnp.int8
        assert qt.scale.shape == (32,)          # per-output-channel
        deq = np.asarray(dequantize_tensor(qt))
        # absmax symmetric: per-element error <= half a quantization step
        step = np.asarray(qt.scale)[None, :]
        assert np.all(np.abs(w - deq) <= 0.51 * step)
        # the channel max hits the int8 rails
        assert int(np.abs(np.asarray(qt.q)).max()) == 127

    def test_conv_axis(self):
        w = np.random.default_rng(1).normal(size=(3, 3, 4, 8)).astype(
            np.float32)
        qt = quantize_tensor(w, axis=3)
        assert qt.scale.shape == (8,)
        deq = np.asarray(dequantize_tensor(qt))
        assert np.all(np.abs(w - deq)
                      <= 0.51 * np.asarray(qt.scale)[None, None, None, :])

    def test_matmul_operator_routes_through_op(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
        w = rng.normal(size=(64, 32)).astype(np.float32)
        qt = quantize_tensor(w)
        got = x @ qt
        want = x @ dequantize_tensor(qt)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_getitem_dequantizes_rows(self):
        w = np.random.default_rng(3).normal(size=(10, 6)).astype(np.float32)
        qt = quantize_tensor(w)
        row = np.asarray(qt[4])
        np.testing.assert_allclose(
            row, np.asarray(dequantize_tensor(qt))[4], rtol=1e-6)

    def test_astype_moves_only_scale(self):
        qt = quantize_tensor(np.ones((4, 4), np.float32))
        cast = qt.astype(jnp.bfloat16)
        assert cast.q.dtype == jnp.int8
        assert cast.scale.dtype == jnp.bfloat16
        assert qt.scale.dtype == jnp.float32    # original untouched

    def test_pytree_round_trip_through_jit(self):
        qt = quantize_tensor(np.random.default_rng(4).normal(
            size=(8, 8)).astype(np.float32))
        out = jax.jit(lambda t: t)(qt)
        assert isinstance(out, QuantizedTensor)
        assert out.axis == qt.axis
        np.testing.assert_array_equal(np.asarray(out.q), np.asarray(qt.q))


# ------------------------------------------------------------- fused ops
class TestQuantizedOps:
    def test_quantized_matmul_math(self):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
        qt = quantize_tensor(rng.normal(size=(16, 8)).astype(np.float32))
        got = op("quantized_matmul")(x, qt.q, qt.scale)
        want = x @ dequantize_tensor(qt)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_quantized_einsum_math(self):
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
        qt = quantize_tensor(rng.normal(size=(16, 8)).astype(np.float32))
        got = op("quantized_einsum")("btd,df->btf", x, qt.q, qt.scale)
        want = jnp.einsum("btd,df->btf", x, dequantize_tensor(qt))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_quantized_einsum_rejects_contracted_scale_axis(self):
        x = jnp.zeros((2, 16), jnp.float32)
        qt = quantize_tensor(np.ones((8, 16), np.float32))
        # weight's last axis is contracted away -> the per-output-channel
        # scale cannot be applied on the accumulator
        with pytest.raises(ValueError):
            op("quantized_einsum")("bd,fd->bf", x, qt.q, qt.scale)


# ---------------------------------------------------------- jaxpr witness
class TestDequantWitness:
    def test_fused_path_passes(self):
        qt = quantize_tensor(np.random.default_rng(7).normal(
            size=(32, 16)).astype(np.float32))
        x = jnp.zeros((4, 32), jnp.float32)
        assert_no_dequantized_weights(
            lambda a, q, s: op("quantized_matmul")(a, q, s),
            x, qt.q, qt.scale)

    def test_materialized_dequant_is_flagged(self):
        qt = quantize_tensor(np.random.default_rng(8).normal(
            size=(32, 16)).astype(np.float32))
        x = jnp.zeros((4, 32), jnp.float32)

        def bad(a, q, s):
            return a @ (q.astype(jnp.float32) * s)   # full f32 weight copy

        assert find_dequantized_weights(bad, x, qt.q, qt.scale)
        with pytest.raises(AssertionError):
            assert_no_dequantized_weights(bad, x, qt.q, qt.scale)


# -------------------------------------------------------- network pass
class TestQuantizeNetwork:
    def test_rules_whitelist(self, dense_net, qdense):
        p0 = qdense.params[0]
        assert isinstance(p0["W"], QuantizedTensor)
        assert not isinstance(p0["b"], QuantizedTensor)
        assert isinstance(qdense.params[1]["W"], QuantizedTensor)
        # the original is untouched — still plain arrays
        assert not isinstance(dense_net.params[0]["W"], QuantizedTensor)
        assert "DenseLayer" in QUANT_RULES
        assert "CenterLossOutputLayer" not in QUANT_RULES  # exact-match only

    def test_unknown_layer_passes_through(self):
        class FakeLayer:
            pass

        params = {"W": jnp.ones((4, 4))}
        out, n = quantize_params(params, FakeLayer())
        assert out is params and n == 0

    def test_top1_agreement(self, dense_net, qdense):
        x = jnp.asarray(np.random.default_rng(9).normal(size=(64, 16)),
                        jnp.float32)
        a = np.asarray(dense_net.output(x))
        b = np.asarray(qdense.output(x))
        assert (a.argmax(-1) == b.argmax(-1)).mean() >= 0.97
        assert float(np.abs(a - b).max()) < 0.05

    def test_inference_view_semantics(self, qdense):
        assert qdense._quantized
        assert qdense.opt_state == [{} for _ in qdense.params]
        with pytest.raises(RuntimeError, match="inference view"):
            qdense.fit_batch((jnp.zeros((4, 16)), jnp.zeros((4, 5))))

    def test_original_still_trains(self, dense_net, qdense):
        x = jnp.asarray(np.random.default_rng(10).normal(size=(8, 16)),
                        jnp.float32)
        y = jnp.eye(5)[np.random.default_rng(11).integers(0, 5, 8)]
        score = dense_net.fit_batch((x, y))
        assert np.isfinite(float(score))

    def test_predict_is_retrace_free(self, qdense):
        """Tier-1 guard: repeated quantized predict at one shape compiles
        exactly ONE program — the QuantizedTensor pytree hashes stably."""
        x = jnp.zeros((4, 16), jnp.float32)
        qdense.output(x)
        n0 = qdense._jit_cache["output"]._cache_size()
        for _ in range(3):
            qdense.output(x)
        assert qdense._jit_cache["output"]._cache_size() == n0

    def test_predict_never_materializes_f32_weights(self, qdense):
        """Tier-1 guard: the whole quantized forward contains no float
        array of any quantized weight's shape — dequantization happens on
        the matmul accumulator, not the weight."""
        x = jnp.zeros((4, 16), jnp.float32)
        qdense.output(x)
        fn = qdense._jit_cache["output"]
        assert_no_dequantized_weights(fn, qdense.params, qdense.state, x,
                                      None)

    def test_regularization_skips_quantized(self, qdense):
        # l1/l2 walks params; QuantizedTensor leaves must be skipped, not
        # crashed on — exercise via a direct layer regularization call
        layer = qdense.conf.layers[0]
        if hasattr(layer, "regularization"):
            val = layer.regularization(qdense.params[0])
            assert np.isfinite(float(val))

    def test_conv_net_quantize(self):
        from deeplearning4j_tpu.nn.layers import ConvolutionLayer
        conf = (NeuralNetConfiguration.builder().seed(5).list()
                .layer(ConvolutionLayer(n_out=4, kernel=(3, 3),
                                        activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.convolutional(8, 8, 2)).build())
        net = MultiLayerNetwork(conf).init()
        qnet = net.quantize()
        w = qnet.params[0]["W"]
        assert isinstance(w, QuantizedTensor)
        assert w.axis == 3 and w.scale.shape == (4,)   # per-output-channel
        x = jnp.asarray(np.random.default_rng(18).normal(size=(4, 8, 8, 2)),
                        jnp.float32)
        a = np.asarray(net.output(x))
        b = np.asarray(qnet.output(x))
        assert (a.argmax(-1) == b.argmax(-1)).mean() >= 0.9
        assert float(np.abs(a - b).max()) < 0.05

    def test_graph_quantize(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (NeuralNetConfiguration.builder().seed(0).graph_builder()
                .add_inputs("in")
                .set_input_types(**{"in": InputType.feed_forward(4)})
                .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss="mcxent"), "d")
                .set_outputs("out").build())
        g = ComputationGraph(conf).init()
        qg = g.quantize()
        assert qg._quantized
        assert isinstance(qg.params["d"]["W"], QuantizedTensor)
        x = jnp.asarray(np.random.default_rng(12).normal(size=(16, 4)),
                        jnp.float32)
        a = np.asarray(g.output(x))
        b = np.asarray(qg.output(x))
        assert (a.argmax(-1) == b.argmax(-1)).mean() >= 0.9
        with pytest.raises(RuntimeError):
            qg.fit_batch((x, jnp.eye(3)[np.zeros(16, int)]))


# ----------------------------------------------------------------- serde
class TestSerde:
    def test_zip_round_trip_exact(self, qdense, tmp_path):
        from deeplearning4j_tpu.util.serialization import (restore_model,
                                                           write_model)
        path = str(tmp_path / "q.zip")
        write_model(qdense, path)
        back = restore_model(path)
        assert back._quantized
        w = back.params[0]["W"]
        assert isinstance(w, QuantizedTensor) and w.q.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(w.q),
                                      np.asarray(qdense.params[0]["W"].q))
        x = jnp.asarray(np.random.default_rng(13).normal(size=(8, 16)),
                        jnp.float32)
        np.testing.assert_array_equal(np.asarray(qdense.output(x)),
                                      np.asarray(back.output(x)))
        with pytest.raises(RuntimeError):
            back.fit_batch((x, jnp.zeros((8, 5))))


# ----------------------------------------------------------- int8 KV ring
class TestKvRing:
    def test_ring_write_scale_monotonic(self):
        B, N, L, Dh = 2, 2, 4, 8
        cache = jnp.zeros((B, N, L, Dh), jnp.int8)
        scale = jnp.zeros((B, N), jnp.float32)
        rows = jnp.arange(B)
        big = jnp.full((B, N, Dh), 2.54, jnp.float32)
        cache, scale = ring_write_quantized(cache, scale, big, rows,
                                            jnp.zeros(B, jnp.int32))
        np.testing.assert_allclose(np.asarray(scale), 2.54 / 127, rtol=1e-6)
        # smaller step: scale must NOT shrink (running max)
        small = jnp.full((B, N, Dh), 0.1, jnp.float32)
        cache, scale2 = ring_write_quantized(cache, scale, small, rows,
                                             jnp.ones(B, jnp.int32))
        np.testing.assert_array_equal(np.asarray(scale2), np.asarray(scale))

    def test_requant_preserves_old_slots(self):
        B, N, L, Dh = 1, 1, 4, 8
        cache = jnp.zeros((B, N, L, Dh), jnp.int8)
        scale = jnp.zeros((B, N), jnp.float32)
        rows = jnp.arange(B)
        v0 = jnp.asarray(np.random.default_rng(14).normal(
            size=(B, N, Dh)), jnp.float32)
        cache, scale = ring_write_quantized(cache, scale, v0, rows,
                                            jnp.zeros(B, jnp.int32))
        # a 4x larger vector forces the running scale up; slot 0 must be
        # requantized into the new range, not left misscaled
        cache, scale = ring_write_quantized(cache, scale, v0 * 4, rows,
                                            jnp.ones(B, jnp.int32))
        deq0 = np.asarray(cache[0, 0, 0].astype(jnp.float32)) * float(scale[0, 0])
        np.testing.assert_allclose(deq0, np.asarray(v0[0, 0]),
                                   atol=1.1 * float(scale[0, 0]))

    def test_quantize_cache_round_trip(self):
        c = jnp.asarray(np.random.default_rng(15).normal(
            size=(2, 3, 8, 4)), jnp.float32)
        q, s = quantize_cache(c)
        deq = np.asarray(q.astype(jnp.float32)) * np.asarray(
            s)[:, :, None, None]
        assert np.abs(deq - np.asarray(c)).max() <= 0.51 * float(s.max())


class TestInt8Decode:
    @pytest.fixture(scope="class")
    def tf(self):
        return _tf_net()

    def test_int8_kv_decode_matches_f32_distribution(self, tf):
        """The accuracy contract: int8-KV decode's post-softmax
        distribution within 1e-2 of the f32-cached path, top-1 tokens in
        near-total agreement, on a greedy rollout."""
        from deeplearning4j_tpu.generation.engine import (
            AttentionDecodeAdapter)
        max_len, B, T0 = 32, 4, 6
        af = AttentionDecodeAdapter(tf, max_len)
        aq = AttentionDecodeAdapter(tf, max_len, kv_dtype="int8")
        rng = np.random.default_rng(16)
        prompt = jnp.asarray(rng.integers(0, V, (B, T0)))
        cf = af.prefill(tf.params, tf.state, prompt, None)
        cq = aq.prefill(tf.params, tf.state, prompt, None)
        for i in cq:   # prefill produced int8 4-tuples
            assert len(cq[i]) == 4 and cq[i][0].dtype == jnp.int8
        decf = jax.jit(af.decode)
        decq = jax.jit(aq.decode)
        tok = prompt[:, -1]
        max_prob_delta, agree, steps = 0.0, 0, 16
        for t in range(T0 - 1, T0 - 1 + steps):
            pos = jnp.full((B,), t, jnp.int32)
            lf, cf = decf(tf.params, tf.state, cf, tok, pos)
            lq, cq = decq(tf.params, tf.state, cq, tok, pos)
            pf = jax.nn.softmax(lf, -1)
            pq = jax.nn.softmax(lq, -1)
            max_prob_delta = max(max_prob_delta,
                                 float(jnp.abs(pf - pq).max()))
            agree += int((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).sum())
            tok = jnp.argmax(lf, -1)    # both follow the f32 greedy path
        assert max_prob_delta <= 1e-2
        assert agree / (B * steps) >= 0.95
        # compile-counter witness: one program each through all steps
        assert decf._cache_size() == 1
        assert decq._cache_size() == 1

    def test_engine_kv_dtype_int8(self, tf):
        """GenerationEngine(kv_dtype="int8") serves streams end to end and
        stays on ONE decode program."""
        from deeplearning4j_tpu.generation import GenerationEngine
        eng = GenerationEngine(tf, slots=4, max_len=24, kv_dtype="int8")
        outs = [eng.generate(list(np.random.default_rng(s).integers(
            0, V, 5)), max_new_tokens=6, temperature=0.0) for s in range(3)]
        for o in outs:
            assert len(o) == 6 and all(0 <= t < V for t in o)
        assert eng.decode_programs == 1

    def test_quantized_weights_plus_int8_kv(self, tf):
        """Full int8 serving: quantized weights AND int8 KV — the decode
        jaxpr never materializes a dequantized f32 weight buffer."""
        from deeplearning4j_tpu.generation.engine import (
            AttentionDecodeAdapter)
        qtf = tf.quantize()
        a = AttentionDecodeAdapter(qtf, 16, kv_dtype="int8")
        B = 2
        prompt = jnp.asarray(np.random.default_rng(17).integers(
            0, V, (B, 4)))
        caches = a.prefill(qtf.params, qtf.state, prompt, None)
        tok = prompt[:, -1]
        pos = jnp.full((B,), 3, jnp.int32)
        logits, caches = a.decode(qtf.params, qtf.state, caches, tok, pos)
        assert logits.shape == (B, V)
        assert bool(jnp.all(jnp.isfinite(logits)))
        # screen only the WEIGHT shapes: the int8 KV cache is also int8 in
        # the args, but its requant-on-scale-growth pass legitimately
        # multiplies at cache shape
        wshapes = {tuple(t.q.shape) for p in qtf.params
                   for t in p.values() if isinstance(t, QuantizedTensor)}
        assert_no_dequantized_weights(a.decode, qtf.params, qtf.state,
                                      caches, tok, pos,
                                      weight_shapes=wshapes)


# --------------------------------------------------------------- serving
class TestServingQuantize:
    def _post(self, base, path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            r = urllib.request.urlopen(req, timeout=30)
            return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    def test_load_time_quantization(self, tmp_path):
        from deeplearning4j_tpu.serving import ServingGateway
        from deeplearning4j_tpu.util.serialization import write_model
        net = _dense_net(seed=21, n_in=4, hidden=8, n_out=3)
        path = str(tmp_path / "m.zip")
        write_model(net, path)
        gw = ServingGateway(port=0, batch_limit=4, seed=0).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            code, body = self._post(base, "/models/load",
                                    {"name": "m", "version": "v1",
                                     "path": path, "warmup": False,
                                     "quantize": "int8"})
            assert code == 200, body
            models = json.loads(urllib.request.urlopen(
                base + "/models", timeout=10).read())
            ver = models["models"]["m"]["versions"]["v1"]
            assert ver["quantized"] is True
            code, body = self._post(base, "/v1/m/predict",
                                    {"inputs": [[1.0, 2.0, 3.0, 4.0]]})
            assert code == 200
            want = np.asarray(net.quantize().output(
                jnp.asarray([[1.0, 2.0, 3.0, 4.0]])))
            np.testing.assert_allclose(np.asarray(body["outputs"][0]),
                                       want[0], rtol=1e-4, atol=1e-5)
            # unsupported dtype -> 400, not a crash
            code, _ = self._post(base, "/models/load",
                                 {"name": "m", "version": "v2",
                                  "path": path, "warmup": False,
                                  "quantize": "int4"})
            assert code == 400
        finally:
            gw.stop()


# ------------------------------------------------------------ monitoring
class TestQuantizeMonitoring:
    def test_disabled_is_free(self, monitoring_off):
        assert monitoring.quantize_monitor() is None
        net = _dense_net(seed=31, n_in=4, hidden=8, n_out=3)
        net.quantize()
        assert not monitoring.enabled()

    def test_enabled_records_pass(self):
        monitoring.reset()
        monitoring.enable()
        try:
            net = _dense_net(seed=32, n_in=4, hidden=8, n_out=3)
            net.quantize()
            text = monitoring.registry().exposition()
            assert 'dl4j_quantize_passes_total{dtype="int8"} 1' in text
            assert "dl4j_quantize_bytes_before" in text
            assert "dl4j_quantize_bytes_after" in text
        finally:
            monitoring.reset()
