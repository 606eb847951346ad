"""Continuous-batching generation engine tests.

Covers the ISSUE-8 witness list: seeded sampler determinism (greedy ==
argmax, top-k/top-p support bounds), per-row carry surgery next to the
plain API's kept batch-change rejection, slot admit/evict state-leak
witness, KV-cached decode == full-recompute logits at 1e-5, the
compile-counter witness (steady-state decode stays ONE program under >= 8
concurrent mixed-length streams), the streaming HTTP round-trip, the
monitoring zero-overhead guard, and the tier-1 import-graph guard.
Compile-heavy end-to-end cases are marked slow.
"""

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.generation import (
    CharCodec, GenerationEngine, SlotPool, sample_keys, sample_logits,
)
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    EmbeddingSequenceLayer, LSTMLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.attention import (
    PositionalEmbeddingLayer, TransformerEncoderLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

V = 13  # tiny char vocab shared by the LSTM fixtures


def _lstm_net(units=12, seed=7):
    conf = (
        NeuralNetConfiguration.builder().seed(seed).list()
        .layer(LSTMLayer(n_out=units))
        .layer(RnnOutputLayer(n_out=V, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(V, 8))
        .build()
    )
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def lstm_net():
    return _lstm_net()


@pytest.fixture(scope="module")
def tf_net():
    D = 16
    conf = (
        NeuralNetConfiguration.builder().seed(3).list()
        .layer(EmbeddingSequenceLayer(n_out=D, n_in=V))
        .layer(PositionalEmbeddingLayer(max_len=32))
        .layer(TransformerEncoderLayer(d_model=D, n_heads=2, causal=True))
        .layer(TransformerEncoderLayer(d_model=D, n_heads=2, causal=True))
        .layer(RnnOutputLayer(n_out=V, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(V, 12))
        .build()
    )
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------- sampler
class TestSampler:
    def _keys(self, seeds, pos):
        return sample_keys(np.asarray(seeds), np.asarray(pos))

    def test_greedy_is_argmax(self):
        logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, V)),
                             jnp.float32)
        out = sample_logits(self._keys([1, 2, 3, 4], [0, 1, 2, 3]), logits,
                            temperature=np.zeros(4, np.float32),
                            top_k=np.zeros(4, np.int32),
                            top_p=np.ones(4, np.float32))
        assert out.tolist() == jnp.argmax(logits, -1).tolist()

    def test_seeded_determinism_and_slot_independence(self):
        logits = jnp.asarray(np.random.default_rng(1).normal(size=(3, V)),
                             jnp.float32)
        kw = dict(temperature=np.full(3, 1.0, np.float32),
                  top_k=np.zeros(3, np.int32),
                  top_p=np.ones(3, np.float32))
        a = sample_logits(self._keys([5, 5, 9], [2, 2, 2]), logits, **kw)
        b = sample_logits(self._keys([5, 5, 9], [2, 2, 2]), logits, **kw)
        # same (seed, pos) -> same token, no matter which row/slot it's in
        assert a.tolist() == b.tolist()
        assert int(a[0]) == int(a[1])

    def test_top_k_support_bound(self):
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(1, V)), jnp.float32)
        topk = set(np.argsort(np.asarray(logits[0]))[-3:].tolist())
        for i in range(40):
            out = sample_logits(
                self._keys([i], [i]), logits,
                temperature=np.full(1, 1.5, np.float32),
                top_k=np.full(1, 3, np.int32),
                top_p=np.ones(1, np.float32))
            assert int(out[0]) in topk

    def test_top_p_nucleus_mass_bound(self):
        """Every sampled token lies in the smallest prefix of the sorted
        distribution whose cumulative mass reaches p."""
        rng = np.random.default_rng(3)
        logits = np.asarray(rng.normal(size=(1, V)) * 2.0, np.float32)
        probs = np.exp(logits[0] - logits[0].max())
        probs /= probs.sum()
        order = np.argsort(-probs)
        csum = np.cumsum(probs[order])
        n_keep = int(np.searchsorted(csum, 0.7) + 1)
        nucleus = set(order[:n_keep].tolist())
        assert n_keep < V  # the bound must actually bind for this witness
        for i in range(40):
            out = sample_logits(
                self._keys([i], [0]), jnp.asarray(logits),
                temperature=np.ones(1, np.float32),
                top_k=np.zeros(1, np.int32),
                top_p=np.full(1, 0.7, np.float32))
            assert int(out[0]) in nucleus


# ------------------------------------------------------- carry row surgery
class TestCarryRows:
    def _x(self, seed, batch=1):
        rng = np.random.default_rng(seed)
        return jnp.asarray(
            np.eye(V, dtype=np.float32)[rng.integers(0, V, batch)])

    def test_plain_api_still_rejects_batch_change(self, lstm_net):
        lstm_net.rnn_clear_previous_state()
        lstm_net.rnn_time_step(self._x(0, batch=2))
        with pytest.raises(ValueError, match="batch size changed"):
            lstm_net.rnn_time_step(self._x(1, batch=3))
        lstm_net.rnn_clear_previous_state()

    def test_get_rows_without_state_raises(self, lstm_net):
        lstm_net.rnn_clear_previous_state()
        with pytest.raises(ValueError, match="no stored rnn state"):
            lstm_net.rnn_get_carry_rows(0)
        with pytest.raises(ValueError, match="pass batch="):
            lstm_net.rnn_set_carry_rows([0], {}, batch=None)

    def test_row_extract_merge_roundtrip(self, lstm_net):
        net = lstm_net
        xa, xb = self._x(10), self._x(11)
        xb2 = self._x(12)
        # batch-2 run: [a; b], snapshot b's carry, then continue
        net.rnn_clear_previous_state()
        net.rnn_time_step(jnp.concatenate([xa, xb]))
        sub = net.rnn_get_carry_rows(1)
        ref = net.rnn_time_step(jnp.concatenate([xa, xb2]))[1]
        # replay b alone from the snapshot in a fresh batch-1 state
        net.rnn_clear_previous_state()
        net.rnn_set_carry_rows([0], sub, batch=1)
        out = net.rnn_time_step(xb2)[0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)
        net.rnn_clear_previous_state()

    def test_merge_into_existing_batch(self, lstm_net):
        net = lstm_net
        net.rnn_clear_previous_state()
        net.rnn_time_step(jnp.concatenate([self._x(20), self._x(21)]))
        # overwrite row 0 with row 1's carry -> identical continuations
        net.rnn_set_carry_rows([0], net.rnn_get_carry_rows(1))
        x = self._x(22)
        out = net.rnn_time_step(jnp.concatenate([x, x]))
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[1]),
                                   atol=1e-6)
        net.rnn_clear_previous_state()


# ---------------------------------------------------------------- slot pool
class TestSlotPool:
    def _pool(self, n=3):
        return SlotPool(n, lambda s: {"h": jnp.zeros((s, 4))})

    def test_bookkeeping(self):
        pool = self._pool()
        assert pool.free_slots() == [0, 1, 2] and pool.occupancy() == 0
        pool.admit(1, {"h": jnp.ones((1, 4))}, token=5, pos=2, seed=0,
                   temperature=0.0, top_k=0, top_p=1.0, meta="r1")
        assert pool.occupancy() == 1 and pool.free_slots() == [0, 2]
        assert pool.tokens[1] == 5 and pool.pos[1] == 2
        assert float(np.asarray(pool.state["h"])[1].sum()) == 4.0
        with pytest.raises(ValueError, match="occupied"):
            pool.admit(1, {"h": jnp.zeros((1, 4))}, token=0, pos=0, seed=0,
                       temperature=0.0, top_k=0, top_p=1.0)
        assert pool.retire(1) == "r1"
        assert pool.occupancy() == 0

    def test_admit_overwrites_entire_row(self):
        pool = self._pool()
        pool.admit(0, {"h": jnp.full((1, 4), 9.0)}, token=1, pos=0, seed=0,
                   temperature=0.0, top_k=0, top_p=1.0)
        pool.retire(0)
        pool.admit(0, {"h": jnp.full((1, 4), 2.0)}, token=1, pos=0, seed=0,
                   temperature=0.0, top_k=0, top_p=1.0)
        assert np.asarray(pool.state["h"])[0].tolist() == [2.0] * 4


# ------------------------------------------------------------------ engine
class TestEngine:
    def test_greedy_matches_rnn_time_step(self, lstm_net):
        """Engine decode == the stored-state streaming API, token for
        token (greedy), i.e. the slot pool changes scheduling, not math."""
        eng = GenerationEngine(lstm_net, slots=2, max_len=32)
        got = eng.generate([1, 2, 3], max_new_tokens=5)
        net = lstm_net
        net.rnn_clear_previous_state()
        out = net.rnn_time_step(jnp.asarray(np.eye(V, dtype=np.float32)[
            [1, 2, 3]])[None])
        ref = [int(jnp.argmax(out[0, -1]))]
        for _ in range(4):
            o = net.rnn_time_step(jnp.asarray(
                np.eye(V, dtype=np.float32)[[ref[-1]]]))
            ref.append(int(jnp.argmax(o[0])))
        net.rnn_clear_previous_state()
        assert got == ref

    def test_slot_reuse_no_state_leak(self, lstm_net):
        """The admit/evict witness: a retired sequence's state must never
        color a newcomer decoding in the same slot."""
        eng = GenerationEngine(lstm_net, slots=1, max_len=32)
        eng.generate([4, 5, 6, 7], max_new_tokens=6, seed=1)  # pollute slot 0
        reused = eng.generate([2, 3], max_new_tokens=6, seed=2)
        fresh = GenerationEngine(lstm_net, slots=1, max_len=32).generate(
            [2, 3], max_new_tokens=6, seed=2)
        assert reused == fresh

    def test_eos_retires_immediately(self, lstm_net):
        eng = GenerationEngine(lstm_net, slots=2, max_len=32)
        first = eng.generate([1, 2], max_new_tokens=4)[0]
        s = eng.submit([1, 2], max_new_tokens=4, eos_id=first)
        eng.drain()
        assert s.finish_reason == "eos"
        assert s.tokens == []  # EOS itself is not emitted

    def test_prompt_validation(self, lstm_net):
        eng = GenerationEngine(lstm_net, slots=1, max_len=8)
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.submit(list(range(9)))

    def test_cancel_frees_slot(self, lstm_net):
        eng = GenerationEngine(lstm_net, slots=1, max_len=32)
        s = eng.submit([1], max_new_tokens=500)
        eng.step()
        s.cancel()
        eng.drain()
        assert s.finish_reason == "cancelled"
        assert eng.pool.occupancy() == 0

    def test_shutdown_cancels_stragglers(self, lstm_net):
        eng = GenerationEngine(lstm_net, slots=1, max_len=32)
        running = eng.submit([1], max_new_tokens=10 ** 6)
        queued = eng.submit([2], max_new_tokens=4)
        eng.step()
        eng.shutdown(timeout=0.0)
        assert running.finish_reason == "cancelled"
        assert queued.finish_reason == "cancelled"
        with pytest.raises(RuntimeError, match="shut down"):
            eng.submit([1])


@pytest.mark.slow
class TestCompileWitness:
    def test_eight_streams_one_decode_program(self, lstm_net):
        """>= 8 concurrent mixed-length streams, churning admits/retires,
        through ONE steady-state compiled decode program (the PyGraph
        replay witness), with prefill bounded by the pow2 buckets."""
        eng = GenerationEngine(lstm_net, slots=8, max_len=64)
        rng = np.random.default_rng(0)
        streams = [eng.submit(rng.integers(0, V, int(l)).tolist(),
                              max_new_tokens=int(n), temperature=0.9,
                              top_k=5, seed=i)
                   for i, (l, n) in enumerate(zip(
                       rng.integers(1, 30, 24), rng.integers(3, 40, 24)))]
        peak = 0
        while eng.has_work():
            eng.step()
            peak = max(peak, eng.pool.occupancy())
        assert peak == 8  # the pool really ran full
        assert all(s.finish_reason == "length" for s in streams)
        assert eng.decode_programs == 1
        assert eng.prefill_programs <= len(eng.buckets)


# ----------------------------------------------------------- KV-cache parity
@pytest.mark.slow
class TestKVCacheParity:
    def test_cached_decode_matches_full_recompute(self, tf_net):
        """Cached single-query decode logits == full causal forward over
        the growing prefix, at 1e-5, across prefill + 6 decode steps."""
        net = tf_net
        eng = GenerationEngine(net, slots=2, max_len=32)
        ad = eng.adapter

        def full_logits(ids):
            h = jnp.asarray(ids)[None]
            for i, layer in enumerate(net.layers):
                if i == len(net.layers) - 1:
                    return layer.preout(net.params[i], h)[0, -1]
                h, _ = layer.apply(net.params[i], net.state[i], h)

        seq = [1, 2, 3, 4]
        state = eng._prefill_state(tuple(seq))
        cur, pos = seq[-1], len(seq) - 1
        for _ in range(6):
            logits, state = ad.decode(net.params, net.state, state,
                                      jnp.asarray([cur]), jnp.asarray([pos]))
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       np.asarray(full_logits(seq)),
                                       atol=1e-5)
            cur = int(jnp.argmax(logits[0]))
            seq.append(cur)
            pos += 1

    def test_transformer_engine_greedy_matches_full(self, tf_net):
        eng = GenerationEngine(tf_net, slots=2, max_len=32)
        got = eng.generate([1, 2, 3, 4], max_new_tokens=6)

        def step(ids):
            h = jnp.asarray(ids)[None]
            for i, layer in enumerate(tf_net.layers):
                if i == len(tf_net.layers) - 1:
                    return int(jnp.argmax(layer.preout(
                        tf_net.params[i], h)[0, -1]))
                h, _ = layer.apply(tf_net.params[i], tf_net.state[i], h)

        seq, ref = [1, 2, 3, 4], []
        for _ in range(6):
            t = step(seq)
            ref.append(t)
            seq.append(t)
        assert got == ref


# ------------------------------------------------------------- HTTP serving
def _post_json(base, path, payload, timeout=30):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


@pytest.fixture()
def metrics_on():
    monitoring.reset()
    monitoring.enable()
    yield
    monitoring.reset()


@pytest.mark.slow
class TestStreamingHTTP:
    @pytest.fixture()
    def gateway(self, lstm_net):
        from deeplearning4j_tpu.serving import ServingGateway

        codec = CharCodec("abcdefghijklm")
        assert codec.vocab_size == V
        eng = GenerationEngine(lstm_net, slots=4, max_len=64, codec=codec)
        gw = ServingGateway(port=0).start()
        gw.register_generator("charlm", eng)
        yield gw, eng, codec
        gw.stop(timeout=5)

    def _stream(self, port, payload, timeout=30):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        conn.request("POST", "/v1/charlm/generate",
                     json.dumps(payload).encode(),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        lines = [json.loads(l) for l in r if l.strip()]
        conn.close()
        return r, lines

    def test_streaming_round_trip(self, gateway, metrics_on):
        gw, eng, codec = gateway
        r, lines = self._stream(gw.port, {"prompt": "abc",
                                          "max_new_tokens": 5, "seed": 3})
        assert r.status == 200
        assert r.getheader("Content-Type") == "application/x-ndjson"
        assert lines[-1]["done"] and lines[-1]["finish_reason"] == "length"
        toks = [l["token"] for l in lines[:-1]]
        assert len(toks) == 5 == lines[-1]["n_tokens"]
        # the stream is the same computation the engine runs directly
        assert toks == eng.generate("abc", max_new_tokens=5, seed=3)
        # and every emitted token round-trips through the codec
        assert "".join(l["text"] for l in lines[:-1]) == codec.decode(toks)
        assert "dl4j_generate_requests_total" in monitoring.metrics_text()

    def test_one_shot_mode_and_errors(self, gateway):
        gw, _, _ = gateway
        base = f"http://127.0.0.1:{gw.port}"
        code, body, _ = _post_json(base, "/v1/charlm/generate",
                                   {"prompt": "ab", "stream": False,
                                    "max_new_tokens": 4})
        assert code == 200 and len(body["tokens"]) == 4
        assert body["finish_reason"] == "length" and len(body["text"]) == 4
        code, _, _ = _post_json(base, "/v1/nope/generate",
                                {"prompt_ids": [1]})
        assert code == 404
        code, body, _ = _post_json(base, "/v1/charlm/generate", {})
        assert code == 400 and "prompt" in body["error"]

    def test_backlog_sheds_429_with_retry_after(self, lstm_net, metrics_on):
        from deeplearning4j_tpu.serving import ServingGateway

        eng = GenerationEngine(lstm_net, slots=1, max_len=64)
        # no step loop driving the engine -> pending only grows
        gw = ServingGateway(port=0, generate_max_queue=1).start()
        gw._generators["g"] = eng  # not started: backlog stays queued
        try:
            base = f"http://127.0.0.1:{gw.port}"
            eng.submit([1], max_new_tokens=4)
            code, _, headers = _post_json(base, "/v1/g/generate",
                                          {"prompt_ids": [1]})
            assert code == 429 and "Retry-After" in headers
            assert "outcome=\"shed\"" in monitoring.metrics_text()
        finally:
            del gw._generators["g"]
            gw.stop(timeout=2)
            eng.shutdown(timeout=0)

    def test_drain_finishes_streams_and_rejects_new(self, gateway):
        """Streaming-aware graceful stop: an open stream finishes (or is
        cancelled with a terminal line) within the deadline; new requests
        see 503 the moment draining starts."""
        import http.client

        gw, eng, _ = gateway
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
        conn.request("POST", "/v1/charlm/generate",
                     json.dumps({"prompt": "a",
                                 "max_new_tokens": 3000}).encode())
        r = conn.getresponse()
        json.loads(r.readline())  # stream is live
        codes = {}

        def late():
            code, _, _ = _post_json(f"http://127.0.0.1:{gw.port}",
                                    "/v1/charlm/generate",
                                    {"prompt": "b", "max_new_tokens": 1})
            codes["late"] = code

        stopper = threading.Thread(target=lambda: gw.stop(timeout=10))
        stopper.start()
        time.sleep(0.05)
        late()
        lines = [json.loads(l) for l in r if l.strip()]
        stopper.join()
        conn.close()
        assert lines and lines[-1].get("done")
        # either the stream outran the drain or the deadline cancelled it —
        # both are clean terminations with a terminal line
        assert lines[-1]["finish_reason"] in ("length", "cancelled")
        assert codes["late"] == 503


# ----------------------------------------------------------- zero overhead
class TestZeroOverhead:
    def test_monitor_none_and_no_metrics_by_default(self, lstm_net,
                                                    monitoring_off):
        assert monitoring.generate_monitor() is None
        eng = GenerationEngine(lstm_net, slots=1, max_len=16)
        eng.generate([1], max_new_tokens=2)
        assert "dl4j_generate" not in monitoring.metrics_text()

    def test_metrics_flow_when_enabled(self, lstm_net, metrics_on):
        eng = GenerationEngine(lstm_net, slots=2, max_len=16)
        eng.generate([1, 2], max_new_tokens=3)
        text = monitoring.metrics_text()
        assert 'dl4j_generate_requests_total{outcome="length"} 1' in text
        assert "dl4j_generate_tokens_total 3" in text
        assert "dl4j_generate_ttft_seconds" in text
        assert "dl4j_generate_decode_steps_total 3" in text


# ------------------------------------------------------------- import graph
class TestImportGraph:
    def test_base_import_does_not_pull_generation(self):
        """`import deeplearning4j_tpu` must stay lean: the generation
        subsystem (and the serving HTTP stack it feeds) load on demand."""
        code = (
            "import sys; import deeplearning4j_tpu; "
            "bad = [m for m in sys.modules if m.startswith("
            "('deeplearning4j_tpu.generation', 'deeplearning4j_tpu.serving'"
            "))]; "
            "assert not bad, bad"
        )
        subprocess.run([sys.executable, "-c", code], check=True)

    def test_generation_import_pulls_no_heavyweight_deps(self):
        """The generation import graph must not drag in frameworks the
        engine doesn't use (TF/torch/flax/pandas) nor the HTTP server
        stack (serving.http) — only warmup's bucket helpers."""
        code = (
            "import sys; import deeplearning4j_tpu.generation; "
            "bad = [m for m in ('tensorflow', 'torch', 'flax', 'pandas', "
            "'deeplearning4j_tpu.serving.http', "
            "'deeplearning4j_tpu.serving.gateway') if m in sys.modules]; "
            "assert not bad, bad"
        )
        subprocess.run([sys.executable, "-c", code], check=True)


# ----------------------------------------------------- ring wrap-around
class TestRingWraparound:
    """Decode past ``pos >= max_len``: the KV ring wraps (slot = pos % L)
    and attention becomes a sliding window over the last L tokens. The
    reference recomputes each step's logits from scratch over exactly that
    window, with ABSOLUTE positional embeddings (``P[abs_pos]``, matching
    what the ring rows were written with) — for a single transformer layer
    the two are algebraically identical. Checked for the f32 cache at 1e-5
    and the int8 cache on the post-softmax distribution, with the
    compile-counter witness holding decode to ONE program through the
    wrap."""

    L = 8  # ring length; decode runs to pos ~20, wrapping 2.5 times

    @pytest.fixture(scope="class")
    def wrap_net(self):
        D = 16
        conf = (
            NeuralNetConfiguration.builder().seed(11).list()
            .layer(EmbeddingSequenceLayer(n_out=D, n_in=V))
            .layer(PositionalEmbeddingLayer(max_len=64))
            .layer(TransformerEncoderLayer(d_model=D, n_heads=2,
                                           causal=True))
            .layer(RnnOutputLayer(n_out=V, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(V, 12))
            .build()
        )
        return MultiLayerNetwork(conf).init()

    def _window_logits(self, net, tokens, t):
        """Reference: full recompute over the last-L window ending at
        absolute position ``t``, positions kept absolute."""
        start = max(0, t - self.L + 1)
        win = tokens[:, start:t + 1]
        emb, pos_l, tf_l, out_l = net.layers
        x = net.params[0]["W"][win]
        if emb.has_bias:
            x = x + net.params[0]["b"]
        x = x + net.params[1]["P"][jnp.arange(start, t + 1)]
        y, _ = tf_l.apply(net.params[2], net.state[2], x, train=False)
        return out_l.preout(net.params[3], y[:, -1:, :])[:, 0]

    def _run(self, net, kv_dtype, tokens, steps):
        from deeplearning4j_tpu.generation.engine import (
            AttentionDecodeAdapter)
        ad = AttentionDecodeAdapter(net, self.L, kv_dtype=kv_dtype)
        B, T0 = tokens.shape[0], 4
        caches = ad.prefill(net.params, net.state, tokens[:, :T0], None)
        dec = jax.jit(ad.decode)
        out = []
        for t in range(T0 - 1, T0 - 1 + steps):
            pos = jnp.full((B,), t, jnp.int32)
            logits, caches = dec(net.params, net.state, caches,
                                 tokens[:, t], pos)
            out.append(logits)
        assert dec._cache_size() == 1   # one program through the wrap
        return out

    def test_f32_ring_matches_sliding_window(self, wrap_net):
        rng = np.random.default_rng(20)
        B, steps = 2, 18                       # pos runs 3..20 (wraps at 8)
        tokens = jnp.asarray(rng.integers(0, V, (B, 4 + steps)))
        got = self._run(wrap_net, None, tokens, steps)
        for k, logits in enumerate(got):
            t = 3 + k
            ref = self._window_logits(wrap_net, tokens, t)
            np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                       atol=2e-5,
                                       err_msg=f"abs pos {t} (wrapped: "
                                               f"{t >= self.L})")

    def test_int8_ring_tracks_f32_through_wrap(self, wrap_net):
        rng = np.random.default_rng(21)
        B, steps = 2, 18
        tokens = jnp.asarray(rng.integers(0, V, (B, 4 + steps)))
        f32 = self._run(wrap_net, None, tokens, steps)
        int8 = self._run(wrap_net, "int8", tokens, steps)
        worst = 0.0
        for lf, lq in zip(f32, int8):
            pf, pq = jax.nn.softmax(lf, -1), jax.nn.softmax(lq, -1)
            worst = max(worst, float(jnp.abs(pf - pq).max()))
        assert worst <= 1e-2
        # the wrapped steps specifically (pos >= L) stay in agreement
        tail_agree = np.mean([
            np.asarray(jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean()
            for lf, lq in zip(f32[self.L:], int8[self.L:])])
        assert tail_agree >= 0.9


# ------------------------------------------------------- priority classes
class TestPriorityLanes:
    def test_interactive_claims_freed_slot_first(self, lstm_net):
        """With one slot busy, a later interactive submission must be
        admitted before earlier-queued batch work (the multi-tenant
        gateway threads tenant class down to here)."""
        eng = GenerationEngine(lstm_net, slots=1, max_len=32)
        a = eng.submit([1], max_new_tokens=2)
        b = eng.submit([2], max_new_tokens=2, klass="batch")
        c = eng.submit([3], max_new_tokens=2)
        eng.drain()
        assert [s.finish_reason for s in (a, b, c)] == ["length"] * 3
        assert a.finished_at < c.finished_at < b.finished_at
        assert eng.pending_count() == 0
        assert eng.pool.occupancy() == 0

    def test_shutdown_cancels_both_lanes(self, lstm_net):
        eng = GenerationEngine(lstm_net, slots=1, max_len=32)
        running = eng.submit([1], max_new_tokens=10 ** 6)
        queued_batch = eng.submit([2], max_new_tokens=4, klass="batch")
        assert eng.pending_count() == 2   # spans both lanes
        eng.step()                        # admits the interactive stream
        assert eng.pending_count() == 1   # the batch job still queued
        eng.shutdown(timeout=0.0)
        assert running.finish_reason == "cancelled"
        assert queued_batch.finish_reason == "cancelled"
        assert eng.pool.occupancy() == 0


class TestMixedPriorityDrain:
    def test_drain_streams_finish_batch_rejected(self, lstm_net):
        """Gateway stop() under mixed priorities: the open interactive
        stream terminates cleanly (terminal ndjson line), queued batch
        work never leaks a slot, and batch arrivals during the drain get
        terminal 503s."""
        import http.client

        from deeplearning4j_tpu.serving import ServingGateway

        eng = GenerationEngine(lstm_net, slots=1, max_len=64)
        gw = ServingGateway(
            port=0,
            tenants=[{"key": "ki", "name": "int", "klass": "interactive"},
                     {"key": "kb", "name": "bat", "klass": "batch"}]).start()
        gw.register_generator("g", eng)
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
        conn.request("POST", "/v1/g/generate",
                     json.dumps({"prompt_ids": [1], "max_new_tokens": 2000,
                                 "api_key": "ki"}).encode(),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 200
        json.loads(r.readline())            # interactive stream is live
        # batch work queued behind it in the engine's low-priority lane
        qb = eng.submit([2], max_new_tokens=4, klass="batch")
        codes = {}

        def late_batch():
            code, _, _ = _post_json(f"http://127.0.0.1:{gw.port}",
                                    "/v1/g/generate",
                                    {"prompt_ids": [3], "max_new_tokens": 1,
                                     "api_key": "kb"})
            codes["late"] = code

        stopper = threading.Thread(target=lambda: gw.stop(timeout=10))
        stopper.start()
        time.sleep(0.05)
        late_batch()
        lines = [json.loads(l) for l in r if l.strip()]
        stopper.join()
        conn.close()
        assert lines and lines[-1].get("done")
        assert lines[-1]["finish_reason"] in ("length", "cancelled")
        assert codes["late"] == 503
        # the queued batch job was terminated by the engine shutdown or ran
        # to completion after the stream — either way nothing leaks
        assert qb.finish_reason is not None
        assert eng.pool.occupancy() == 0
        assert eng.pending_count() == 0
