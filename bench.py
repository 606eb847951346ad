"""Benchmark: ResNet-50 ImageNet-shape training throughput (samples/sec/chip).

The BASELINE.json north-star metric, measured from the framework's own
model-zoo entrypoint, with an in-process JAX/Flax-style reference ResNet-50
train step measured the same way to compute ``vs_baseline`` (target >= 0.70
of the reference's samples/sec/chip).

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N}
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time


def _require_tpu():
    """Every mode measures the chip: not on a TPU is an error, never a
    number taken on the CPU."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found platform '{d.platform}' "
                         f"({d.device_kind}); there is no CPU fallback")


def _cost(compiled):
    """flops / HBM bytes of a compiled program (jax cost_analysis)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception:
        return {}


def _lane_cursor() -> int:
    """Rotation cursor for the full-mode lane list, persisted IN the
    artifact: each run prints ``lane_rotation.next_cursor`` and the next
    run reads it back from the newest ``BENCH_r*.json`` the driver saved
    next to this script. Rotating the starting lane across runs means a
    tight deadline starves a DIFFERENT tail each time instead of the same
    lanes every run (BENCH_r05 skipped 6 lanes perpetually)."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    arts = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not arts:
        return 0
    try:
        with open(arts[-1], errors="replace") as f:
            found = re.findall(r'"next_cursor":\s*(\d+)', f.read())
        return int(found[-1]) if found else 0
    except Exception:
        return 0


def _measure(step_fn, args, loss_index, warmup=2, iters=50):
    """Time ``iters`` data-dependent steps, forcing completion with a host
    fetch of the final loss. Because every step consumes the previous
    step's outputs, one final fetch transitively forces all ``iters``
    executions.
    """
    for _ in range(warmup):
        args = step_fn(*args)
    float(args[loss_index].astype("float32").reshape(()))
    t0 = time.perf_counter()
    for _ in range(iters):
        args = step_fn(*args)
    float(args[loss_index].astype("float32").reshape(()))
    return (time.perf_counter() - t0) / iters


def _measurer(model, batch, make_one):
    """Shared measurement scaffolding: wraps a model's jitted train step into
    measure() -> samples/sec. Fresh state copies each round (the step donates
    its buffers); completion forced by _measure's host-fetch barrier."""
    import jax
    import jax.numpy as jnp

    step = model._jit_cache.get("train") or model._make_train_step()
    one = make_one(step)
    state0 = (model.params, model.state, model.opt_state)

    def measure():
        args = tuple(jax.tree_util.tree_map(lambda a: a + 0, t) for t in state0) + (
            jnp.asarray(0, jnp.int32), jnp.asarray(0.0))
        return batch / _measure(one, args, loss_index=4)

    measure.step = step
    measure.state0 = state0
    return measure


def _batch_pool(batch, n_pool=4, seed=0):
    """Pre-staged pool of DISTINCT device-resident batches, cycled per step.

    The input pipeline is in the measurement loop in the sense that matters
    for the compiler: every step consumes a different batch passed as a jit
    ARGUMENT, so XLA cannot specialize on values or hoist a baked-in
    constant. The host->device leg is pre-staged; the native threaded
    decode/augment pipeline has its own tests (tests/test_native.py) and
    feeds real iterators.
    """
    import itertools

    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    xs, ys = [], []
    for _ in range(n_pool):
        xs.append(jnp.asarray(
            rng.normal(size=(batch, 224, 224, 3)).astype(np.float32),
            dtype=jnp.bfloat16))
        ys.append(jnp.asarray(
            np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]))
    counter = itertools.count()
    return xs, ys, counter, n_pool


def make_ours(batch):
    """Build once; returns measure() -> samples/sec using fresh state."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo import ResNet50

    model = ResNet50(height=224, width=224, num_classes=1000, dtype="bf16").init()
    xs, ys, counter, n_pool = _batch_pool(batch)
    x, y = xs[0], ys[0]
    key = jax.random.key(0)

    def make_one(step):
        def one(params, state, opt_state, i, _prev_loss):
            k = next(counter) % n_pool
            p, s, o, loss = step(params, state, opt_state, i, {"input": xs[k]},
                                 {"output": ys[k]}, key, None)
            return p, s, o, i + 1, loss
        return one

    measure = _measurer(model, batch, make_one)
    step, state0 = measure.step, measure.state0

    flops_cache = []

    def flops_per_step():
        if not flops_cache:
            try:
                comp = step.lower(*state0, jnp.asarray(0, jnp.int32),
                                  {"input": x}, {"output": y}, key,
                                  None).compile()
                flops_cache.append(_cost(comp).get("flops", 0.0))
            except Exception:
                flops_cache.append(0.0)
        return flops_cache[0]

    measure.flops_per_step = flops_per_step
    return measure


def bench_ours(batch):
    return make_ours(batch)()


def make_flax_reference(batch):
    """Minimal Flax ResNet-50 train step, same shapes/dtype policy."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    class Bottleneck(nn.Module):
        width: int
        stride: int = 1
        project: bool = False

        @nn.compact
        def __call__(self, x, train=True):
            conv = lambda f, k, s: nn.Conv(f, (k, k), (s, s), padding="SAME",
                                           use_bias=False, dtype=jnp.bfloat16)
            bn = lambda: nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                      dtype=jnp.bfloat16)
            h = nn.relu(bn()(conv(self.width, 1, self.stride)(x)))
            h = nn.relu(bn()(conv(self.width, 3, 1)(h)))
            h = bn()(conv(self.width * 4, 1, 1)(h))
            if self.project:
                x = bn()(conv(self.width * 4, 1, self.stride)(x))
            return nn.relu(h + x)

    class ResNet50F(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(64, (7, 7), (2, 2), padding="SAME", use_bias=False,
                        dtype=jnp.bfloat16)(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             dtype=jnp.bfloat16)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), (2, 2), padding="SAME")
            for si, (w, n, s) in enumerate([(64, 3, 1), (128, 4, 2), (256, 6, 2),
                                            (512, 3, 2)]):
                for bi in range(n):
                    x = Bottleneck(w, s if bi == 0 else 1, project=(bi == 0))(x, train)
            x = x.mean(axis=(1, 2))
            return nn.Dense(1000, dtype=jnp.bfloat16)(x)

    xs, ys_onehot, counter, n_pool = _batch_pool(batch)
    labels_pool = [jnp.argmax(yy, axis=-1) for yy in ys_onehot]
    x = xs[0]
    m = ResNet50F()
    variables = m.init(jax.random.key(0), x[:1], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt = tx.init(params)

    @jax.jit
    def one_step(params, batch_stats, opt, i, _prev_loss, x, labels):
        def loss_fn(p):
            logits, upd = m.apply({"params": p, "batch_stats": batch_stats}, x,
                                  train=True, mutable=["batch_stats"])
            ll = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels).mean()
            return ll, upd["batch_stats"]

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), bs, opt, i + 1, loss

    def one(params, batch_stats, opt, i, _prev_loss):
        k = next(counter) % n_pool
        return one_step(params, batch_stats, opt, i, _prev_loss,
                        xs[k], labels_pool[k])

    state0 = (params, batch_stats, opt)

    def measure():
        args = tuple(jax.tree_util.tree_map(lambda a: a + 0, t) for t in state0) + (
            jnp.asarray(0), jnp.asarray(0.0))
        return batch / _measure(one, args, loss_index=4)

    return measure


def bench_flax_reference(batch):
    return make_flax_reference(batch)()


def make_mln(model, x, y):
    """Generic measurer over a MultiLayerNetwork zoo model's jitted train step
    (the other BASELINE configs: LeNet-MNIST, char-RNN LSTM, BERT fine-tune).
    Same scaffolding as make_ours; only x/y passing differs (bare arrays vs
    the ComputationGraph's input/label dicts)."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    y = jnp.asarray(y)
    key = jax.random.key(0)

    def make_one(step):
        def one(params, state, opt_state, i, _prev_loss):
            p, s, o, loss = step(params, state, opt_state, i, x, y, key, None)
            return p, s, o, i + 1, loss
        return one

    return _measurer(model, x.shape[0], make_one)


def _two_point(many, state0, batch, iters):
    """The shared two-point device-loop protocol: ``many(*state, n)`` runs
    n chained steps in one jit with a DYNAMIC trip count; (t(2n) - t(n))/n
    cancels the fixed RPC cost exactly. Fresh state copies per call (the
    wrapped steps may donate)."""
    import jax

    def measure():
        args = tuple(jax.tree_util.tree_map(lambda a: a + 0, t)
                     for t in state0)
        float(many(*args, 2))                   # compile + warm
        t0 = time.perf_counter()
        float(many(*args, iters))
        t1 = time.perf_counter()
        float(many(*args, 2 * iters))
        t2 = time.perf_counter()
        return batch * iters / ((t2 - t1) - (t1 - t0))

    return measure


def make_mln_two_point(model, x, y, iters=400):
    """Two-point device-loop rate for an MLN zoo model (VERDICT r3 #10).

    The LeNet step is ~2 ms — per-dispatch timing measures the host, not
    the device. Here the whole train step runs inside ONE jit as a
    data-dependent fori_loop chain, timed by _two_point."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    y = jnp.asarray(y)
    key = jax.random.key(0)
    batch = x.shape[0]
    step = model._jit_cache.get("train") or model._make_train_step()
    state0 = (model.params, model.state, model.opt_state)

    @jax.jit
    def many(params, state, opt_state, n):
        def body(i, carry):
            p, s, o, _ = carry
            p, s, o, loss = step(p, s, o, i, x, y, key, None)
            return p, s, o, loss
        return jax.lax.fori_loop(
            0, n, body, (params, state, opt_state, jnp.asarray(0.0)))[3]

    return _two_point(many, state0, batch, iters)


def make_mode(mode, batch):
    """BASELINE configs 1/3/4 (ResNet-50 is the separate A/B path)."""
    import numpy as np

    rng = np.random.default_rng(0)
    if mode == "lenet":
        from deeplearning4j_tpu.zoo import LeNet

        model = LeNet().init()
        x = rng.normal(size=(batch, 28, 28, 1)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
        # two-point device-loop protocol: the ~2 ms step is dispatch-bound
        # under per-dispatch timing
        return (make_mln_two_point(model, x, y),
                "LeNet-MNIST train throughput (two-point device loop)")
    elif mode == "lstm":
        from deeplearning4j_tpu.zoo import BidirectionalGravesLSTMCharRnn

        model = BidirectionalGravesLSTMCharRnn().init()
        T, V = 64, 77
        ids = rng.integers(0, V, (batch, T))
        x = np.eye(V, dtype=np.float32)[ids]
        y = np.eye(V, dtype=np.float32)[np.roll(ids, -1, axis=1)]
        label = "Bidirectional GravesLSTM char-RNN train throughput"
    elif mode in ("bert", "bert_long"):
        from deeplearning4j_tpu.zoo import BertBase

        T = 128 if mode == "bert" else 512
        model = BertBase(max_len=T).init()
        x = rng.integers(0, 30522, (batch, T)).astype(np.int32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)]
        label = f"BERT-base fine-tune train throughput (seq {T})"
    else:
        raise ValueError(f"make_mode: unknown mode {mode!r}")
    fn = make_mln(model, x, y)
    if mode.startswith("bert"):
        # record which attention impl the registry selects for this model's
        # geometry (BERT-base: 12 heads, head_dim 64) — the VERDICT r3 #1
        # evidence that BERT-class shapes ride (or don't ride) the kernel
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops import get_op

        T = x.shape[1]
        qshape = jnp.zeros((batch, 12, T, 64), jnp.bfloat16)
        fn.attention_path = get_op("dot_product_attention").select(
            qshape, qshape, qshape).platform
    return fn, label


def bench_longcontext(T=8192, rounds=3):
    """Causal transformer block train step (fwd+bwd) at long T.

    Compares the Pallas flash backward-kernel path against the recompute
    path (flash fwd, backward = autodiff through the XLA attention, which
    materializes the [T, T] score matrix) — the r1 behavior. Metric:
    tokens/sec; vs_baseline: flash over recompute (>= 1 means the kernel
    path wins). Also reports device peak memory per path when the PJRT
    backend exposes memory_stats.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.attention import dot_product_attention
    from deeplearning4j_tpu.ops.pallas.flash_attention import (
        _flash_forward, flash_attention)
    from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode

    B, H, Dh = 1, 4, 128
    Dm = H * Dh
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, T, Dm)).astype(np.float32) * 0.1,
                    dtype=jnp.bfloat16)
    params = {w: jnp.asarray(
        rng.normal(size=(Dm, Dm)).astype(np.float32) / np.sqrt(Dm))
        for w in ("Wq", "Wk", "Wv", "Wo")}

    # the r1 recompute path, reconstructed: memory-optimal fwd, O(T^2) bwd
    @jax.custom_vjp
    def attn_recompute(q, k, v):
        # same fwd tiles as the flash path so the comparison isolates the bwd
        return _flash_forward(q, k, v, causal=True, scale=Dh ** -0.5,
                              block_q=512, block_k=1024,
                              interpret=interpret_mode())[0]

    def _rc_fwd(q, k, v):
        return attn_recompute(q, k, v), (q, k, v)

    def _rc_bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(lambda q, k, v: dot_product_attention(
            q, k, v, scale=Dh ** -0.5, causal=True), q, k, v)
        return vjp(g)

    attn_recompute.defvjp(_rc_fwd, _rc_bwd)

    def make_step(attn):
        def loss_fn(p, x):
            def heads(w):
                return (x @ p[w].astype(x.dtype)).reshape(
                    B, T, H, Dh).transpose(0, 2, 1, 3)

            o = attn(heads("Wq"), heads("Wk"), heads("Wv"))
            o = o.transpose(0, 2, 1, 3).reshape(B, T, Dm)
            return (o @ p["Wo"].astype(x.dtype)).astype(jnp.float32).var()

        @jax.jit
        def step(p, x):
            l, g = jax.value_and_grad(loss_fn)(p, x)
            return jax.tree.map(lambda a, b: a - 1e-3 * b, p, g), l

        return step

    def measure(attn):
        step = make_step(attn)
        p = dict(params)
        p, l = step(p, x)
        float(l)  # compile + warm; host fetch is the reliable barrier here
        best = 0.0
        iters = 10
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(iters):
                p, l = step(p, x)
            float(l)  # host fetch: the completion barrier
            best = max(best, iters * B * T / (time.perf_counter() - t0))
        return best

    # peak-memory per path is NOT reported: PJRT memory_stats is a
    # process-lifetime high-water mark, so a per-path comparison from one
    # process would be meaningless
    rc_tps = None
    try:
        rc_tps = measure(attn_recompute)
    except Exception:
        pass  # the recompute path may simply OOM at this T — that's the point
    flash_tps = measure(functools.partial(flash_attention, causal=True))
    print(json.dumps({
        "metric": "long-context causal attention train fwd+bwd "
                  f"(flash bwd kernels, B={B} H={H} T={T} Dh={Dh}, bf16)",
        "value": round(flash_tps, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None if not rc_tps else round(flash_tps / rc_tps, 4),
    }))


def _stats(runs):
    """{median, iqr: [q1, q3], rounds} — the dispersion fields every mode
    reports so backend drift is visible in the artifact itself."""
    s = sorted(runs)
    n = len(s)
    med = s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])
    q1 = s[max(0, (n - 1) // 4)]
    q3 = s[min(n - 1, (3 * (n - 1)) // 4)]
    return {"median": round(med, 2), "iqr": [round(q1, 2), round(q3, 2)],
            "rounds": n}


# --------------------------------------------------------------------------
# per-kernel on-chip A/B (VERDICT r2 #2): each Pallas kernel vs its plain-XLA
# lowering, measured with DEVICE-side loops — per-dispatch latency
# otherwise floors every small-shape measurement.
# --------------------------------------------------------------------------


def _device_loop_ab(build_kernel, build_xla, *, iters=30, rounds=3):
    """Interleaved A/B of two jitted scalar-returning step fns, each executed
    inside ONE jit via fori_loop (dependent chain) with a DYNAMIC trip
    count, timed by the two-point method: step_ms = (t(2n) - t(n)) / n.
    The difference cancels every fixed cost — jit dispatch, the host-fetch
    barrier — exactly; a single long chain merely amortizes it. Returns
    per-path ms/step MEDIANS over ``rounds`` interleaved rounds (see the
    estimator note below)."""
    import jax
    import jax.numpy as jnp

    def looped(step):
        @jax.jit
        def many(seed, n):
            def body(i, acc):
                return step(acc)
            return jax.lax.fori_loop(0, n, body, seed)
        return many

    fk, fx = looped(build_kernel()), looped(build_xla())
    seed = 0.0
    float(fk(seed, 2))   # compile + warm (host fetch = barrier)
    float(fx(seed, 2))

    def one(f):
        t0 = time.perf_counter()
        float(f(seed, iters))
        t1 = time.perf_counter()
        float(f(seed, 2 * iters))
        t2 = time.perf_counter()
        return ((t2 - t1) - (t1 - t0)) / iters * 1e3

    tk, tx = [], []
    for _ in range(rounds):
        tk.append(one(fk))
        tx.append(one(fx))
    # median over >= 3 interleaved rounds: two-point noise is SIGNED — a
    # hiccup inside the first segment understates the round (and min would
    # then deterministically pick the flattering outlier), one inside the
    # second overstates it — so the median, which discards one outlier in
    # either direction, is the right estimator. (An r4 rounds=2 cap was
    # reverted for exactly this reason; per-row iters are trimmed instead
    # to keep the full table inside the bench deadline.)
    mk = sorted(tk)[len(tk) // 2]
    mx = sorted(tx)[len(tx) // 2]
    return {"kernel_ms": round(mk, 3), "xla_ms": round(mx, 3),
            "speedup": round(mx / mk, 3)}


def bench_kernels(rounds=3, budget_deadline=None):
    """Per-kernel speedup table: flash attention (fwd + train, incl. the r4
    D=64/masked rows and the measured-demoted short-T rows), fused LSTM and
    GRU (all selected regimes incl. the r4 batch-blocked B=256/H=1024),
    LRN (AlexNet shape, fwd + the r4 backward-kernel train row). Each entry
    records kernel-vs-XLA on this chip. Rounds are floored at 3 — the
    median needs an outlier-rejecting sample (see _device_loop_ab) — and
    the full table fits the bench deadline via trimmed per-row iters plus
    the 0.5 s persistent-cache threshold (the r3 table was truncated)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.common.env import env

    rounds = max(rounds, 3)
    table = {}

    def over_deadline():
        return budget_deadline is not None and time.perf_counter() > budget_deadline

    rng = np.random.default_rng(0)

    # ---- flash attention: fwd and train. D=128 long-T rows plus the r4
    # D=64 rows (the BERT-class geometry, BASELINE config #4) and a masked
    # row — the kernel now serves key-padding masks natively.
    def _flash_rowfn():
        from deeplearning4j_tpu.ops.attention import dot_product_attention
        from deeplearning4j_tpu.ops.pallas.flash_attention import flash_attention

        def rows(tag, B, H, T, D, fwd_iters, train_iters, *, causal=True,
                 masked=False):
            q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
            mask = None
            if masked:
                m = np.ones((B, T), np.float32)
                m[:, int(T * 0.75):] = 0  # 25% padded batch
                mask = jnp.asarray(m)[:, None, None, :]

            # the carry REALLY feeds the input (x + acc*1e-12): acc*0 would
            # be constant-folded and the whole loop body hoisted out of the
            # while-loop, timing nothing
            def fwd(attn):
                def step(acc):
                    o = attn(q + (acc * 1e-12).astype(jnp.bfloat16), q, q,
                             mask=mask, causal=causal)
                    return o.astype(jnp.float32).mean()
                return step

            def train(attn):
                def step(acc):
                    def loss(qq):
                        return attn(qq, qq, qq, mask=mask,
                                    causal=causal).astype(jnp.float32).var()
                    return jax.grad(loss)(
                        q + (acc * 1e-12).astype(jnp.bfloat16)
                    ).astype(jnp.float32).mean()
                return step

            table[f"flash_attention_fwd_{tag}"] = _device_loop_ab(
                lambda: fwd(flash_attention),
                lambda: fwd(dot_product_attention),
                iters=fwd_iters, rounds=rounds)
            table[f"flash_attention_train_{tag}"] = _device_loop_ab(
                lambda: train(flash_attention),
                lambda: train(dot_product_attention),
                iters=train_iters, rounds=rounds)

        return rows

    def flash_rows():
        rows = _flash_rowfn()
        rows("T4096", 1, 4, 4096, 128, 250, 150)

    def flash_d64_rows():
        # BERT-base geometry (H=12, Dh=64): non-causal encoder attention
        rows = _flash_rowfn()
        rows("D64_T512", 8, 12, 512, 64, 600, 350, causal=False)
        if not over_deadline():
            rows("D64_T2048", 2, 12, 2048, 64, 200, 120, causal=False)
        if not over_deadline():
            rows("D64_T2048_masked", 2, 12, 2048, 64, 200, 120,
                 causal=False, masked=True)

    # ---- fused LSTM: selected regime (nj==1) and demoted multi-tile regime
    def _lstm_rowfn():
        from deeplearning4j_tpu.ops.pallas.fused_lstm import fused_lstm_layer
        from deeplearning4j_tpu.ops.recurrent import lstm_layer

        def rows(tag, B, T, F, H, iters):
            # iters scaled so iters*step_time >> dispatch jitter
            x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
            h0 = jnp.zeros((B, H))
            W = jnp.asarray(rng.normal(size=(F, 4 * H)).astype(np.float32) * .05)
            R = jnp.asarray(rng.normal(size=(H, 4 * H)).astype(np.float32) * .05)
            b = jnp.zeros((4 * H,))
            p = jnp.asarray(rng.normal(size=(3 * H,)).astype(np.float32) * .05)

            def fwd(fn):
                def step(acc):
                    out, _ = fn(x + acc * 1e-12, h0, h0, W, R, b, peephole=p)
                    return out.mean()
                return step

            def train(fn):
                def step(acc):
                    def loss(WW):
                        return fn(x, h0, h0, WW, R, b, peephole=p)[0].sum()
                    return jax.grad(loss)(W + acc * 1e-16).mean()
                return step

            table[f"fused_lstm_fwd_{tag}"] = _device_loop_ab(
                lambda: fwd(fused_lstm_layer), lambda: fwd(lstm_layer),
                iters=iters, rounds=rounds)
            table[f"fused_lstm_train_{tag}"] = _device_loop_ab(
                lambda: train(fused_lstm_layer), lambda: train(lstm_layer),
                iters=iters, rounds=rounds)

        return rows

    def lstm_rows():
        rows = _lstm_rowfn()
        rows("B64_H256", 64, 64, 128, 256, 1500)        # selected (nj==1)
        if not over_deadline():
            rows("B32_H1024", 32, 64, 256, 1024, 150)   # selected (R resident)
        if not over_deadline():
            # selected since r4: batch-blocked plan (fwd Bc=64/32, bwd
            # (64,512)) — was the demoted nj>1 regime in r3. iters=60
            # keeps the n..2n span >= ~55 ms even on the fastest path
            # (GRU fwd ~0.9 ms/step), above the +-20 ms RPC jitter, with
            # median-of-3 rejecting any single hiccup round
            rows("B256_H1024", 256, 64, 512, 1024, 60)

    # ---- fused GRU: same regimes as the LSTM (3-gate cell, same policy)
    def _gru_rowfn():
        from deeplearning4j_tpu.ops.pallas.fused_gru import fused_gru_layer
        from deeplearning4j_tpu.ops.recurrent import gru_layer

        def rows(tag, B, T, F, H, iters):
            x = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
            h0 = jnp.zeros((B, H))
            W = jnp.asarray(rng.normal(size=(F, 3 * H)).astype(np.float32) * .05)
            R = jnp.asarray(rng.normal(size=(H, 3 * H)).astype(np.float32) * .05)
            b = jnp.zeros((3 * H,))

            def fwd(fn):
                def step(acc):
                    out, _ = fn(x + acc * 1e-12, h0, W, R, b)
                    return out.mean()
                return step

            def train(fn):
                def step(acc):
                    def loss(WW):
                        return fn(x, h0, WW, R, b)[0].sum()
                    return jax.grad(loss)(W + acc * 1e-16).mean()
                return step

            table[f"fused_gru_fwd_{tag}"] = _device_loop_ab(
                lambda: fwd(fused_gru_layer), lambda: fwd(gru_layer),
                iters=iters, rounds=rounds)
            table[f"fused_gru_train_{tag}"] = _device_loop_ab(
                lambda: train(fused_gru_layer), lambda: train(gru_layer),
                iters=iters, rounds=rounds)

        return rows

    def gru_rows():
        rows = _gru_rowfn()
        rows("B64_H256", 64, 64, 128, 256, 1500)        # selected (nj==1)
        if not over_deadline():
            rows("B64_H1024", 64, 64, 256, 1024, 150)   # selected (R resident)
        if not over_deadline():
            rows("B256_H1024", 256, 64, 512, 1024, 60)  # selected since r4

    # ---- LRN, AlexNet conv2 shape. The impl fns are captured at BUILD
    # time (pallas_lrn directly vs the registered xla lowering) — selecting
    # through the registry inside the jitted step would read the env flags
    # at TRACE time, after both builders ran, and silently A/B the xla
    # path against itself
    def lrn_rows():
        from deeplearning4j_tpu.ops.convolution import lrn as xla_lrn
        from deeplearning4j_tpu.ops.pallas.lrn import pallas_lrn

        x = jnp.asarray(rng.normal(size=(64, 27, 27, 256)).astype(np.float32))

        def build(fn):
            def mk():
                def step(acc):
                    return fn(x + acc * 1e-12, depth=5).mean()
                return step
            return mk

        def build_train(fn):
            def mk():
                def step(acc):
                    return jax.grad(
                        lambda xx: (fn(xx, depth=5) ** 2).sum())(
                            x + acc * 1e-12).mean()
                return step
            return mk

        table["lrn_fwd_alexnet"] = _device_loop_ab(
            build(pallas_lrn), build(xla_lrn), iters=1200, rounds=rounds)
        table["lrn_train_alexnet"] = _device_loop_ab(
            build_train(pallas_lrn), build_train(xla_lrn), iters=400,
            rounds=rounds)

    for block in (flash_rows, flash_d64_rows, lstm_rows, gru_rows,
                  lrn_rows):
        if over_deadline():
            table["truncated"] = "deadline reached; remaining kernels skipped"
            break
        try:
            block()
        except Exception as e:          # record, never kill the bench line
            table[f"error_{block.__name__}"] = f"{type(e).__name__}: {e}"
    return table


def _bert_import_step(imp, y, feeds, B, head_dim):
    """Build (measure, cost_fn) for one imported-BERT fine-tune lane: the
    bf16-compute / f32-master CE step over ``imp.as_trainable`` under Adam,
    two-point device-loop timed. Shared by the optimizer on/off A-B."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.optimize.updaters import Adam, get_updater

    fn, bert_params = imp.as_trainable(outputs=["pooler_output"],
                                       compute_dtype=jnp.bfloat16)
    key = jax.random.key(0)
    params0 = {"bert": bert_params,
               "head": {"W": jax.random.normal(key, (head_dim, 2)) * 0.05,
                        "b": jnp.zeros((2,))}}
    updater = get_updater(Adam(lr=2e-5))

    def imported_loss(p):
        cp = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
        pooled = jax.vmap(lambda f: fn(cp["bert"], f))(feeds)
        pooled = pooled.reshape(B, head_dim)
        logits = (pooled @ cp["head"]["W"] + cp["head"]["b"]).astype(
            jnp.float32)
        return -(y * jax.nn.log_softmax(logits)).sum(-1).mean()

    def step(p, o, i):
        loss, g = jax.value_and_grad(imported_loss)(p)
        upd, o = updater.update(g, o, p, i)
        return jax.tree_util.tree_map(lambda a, d: a - d, p, upd), o, loss

    @jax.jit
    def many(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            return step(p, o, i)
        return jax.lax.fori_loop(0, n, body,
                                 (p, o, jnp.asarray(0.0, jnp.float32)))[2]

    opt0 = updater.init_state(params0)

    def cost_fn():
        return _cost(jax.jit(lambda p, o: step(p, o, 0)).lower(
            params0, opt0).compile())

    return (params0, opt0), many, cost_fn


def _fused_attention_count(imp):
    from deeplearning4j_tpu.modelimport.optimizer import FUSED_ATTENTION_OP

    return sum(1 for n in imp.nodes
               if getattr(n, "op", None) == FUSED_ATTENTION_OP)


def bench_bert_import(iters=300, rounds=3):
    """BASELINE config #4 AS WRITTEN (r5, VERDICT r4 #2): import a BERT
    graph, call as_trainable(), fine-tune — measured against the
    zoo-native twin of the same architecture at the same shapes.

    The imported graph is the committed ONNX golden (a REAL transformers
    BertModel — 2 layers, hidden 64, heads 2, ffn 128, vocab 500 —
    exported by torch.onnx; tests/test_golden_import.py pins its outputs
    against recorded torch activations). The zoo twin is zoo.Bert at
    identical dims. Both run a bf16-compute / f32-master CE fine-tune
    train step under Adam, timed with the same two-point device-loop
    protocol, so the ratio is direct evidence for "the import path
    compiles to the XLA program the native path gets".

    Known architecture deltas (documented, not hidden): the HF graph has
    token-type embeddings and a tanh-pooler head; the zoo twin uses
    learned positions + avg-pool. Both are O(2·L·T·D·(4D+2F)) — the
    deltas are sub-percent FLOPs at these dims."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.modelimport.onnx import OnnxModelImport
    from deeplearning4j_tpu.ops import get_op
    from deeplearning4j_tpu.optimize.updaters import Adam, get_updater
    from deeplearning4j_tpu.zoo import Bert

    # the committed golden was exported by torch.onnx with STATIC shapes
    # (2, 16) baked into its expanded position/token-type constants; the
    # import runs at that inner shape and jax.vmap supplies the outer
    # batch axis (128 x 2 = 256 samples/step) — the zoo twin runs the
    # same [256, 16] batch directly, so per-step FLOPs match.
    BO, BI, T, V, C = 128, 2, 16, 500, 2
    B = BO * BI
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, T)).astype(np.int32)
    am = np.ones((BO, BI, T), np.int32)
    y = jnp.asarray(np.eye(C, dtype=np.float32)[rng.integers(0, C, B)])

    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tests", "fixtures", "bert_tiny.onnx")
    # optimizer A-B: the same fixture imported with the import-graph
    # optimizer on (the default) and force-off
    imp = OnnxModelImport.import_model(fixture)
    imp_off = OnnxModelImport.import_model(fixture, optimize=False)
    feeds = {"input_ids": jnp.asarray(ids).reshape(BO, BI, T),
             "attention_mask": jnp.asarray(am)}
    state_on, many_on, cost_on = _bert_import_step(imp, y, feeds, B, 64)
    state_off, many_off, cost_off = _bert_import_step(imp_off, y, feeds,
                                                      B, 64)
    measure_imported = _two_point(many_on, state_on, B, iters)
    measure_imported_off = _two_point(many_off, state_off, B, iters)

    # the zoo twin at identical dims, same protocol, same per-step work:
    # pin plain Adam (Bert defaults to AdamW+schedule) and drop Bert's
    # gradient clipping — the imported step has neither, and an
    # asymmetric optimizer would pollute the ratio
    twin = Bert(vocab_size=V, max_len=T, d_model=64, n_layers=2, n_heads=2,
                d_ff=128, num_classes=C, dropout=0.0, lr=2e-5,
                dtype="bf16", seed=1).init()
    twin.conf.max_grad_norm = 0.0
    twin._updaters = [get_updater(Adam(lr=2e-5)) for _ in twin.layers]
    twin.opt_state = [u.init_state(p)
                      for u, p in zip(twin._updaters, twin.params)]
    measure_twin = make_mln_two_point(twin, ids, np.asarray(y), iters=iters)

    # INTERLEAVED rounds (the _device_loop_ab discipline): the ratio must
    # come from adjacent measurements, not two sequential blocks. Three
    # lanes per round: optimized import, raw import, zoo-native twin.
    triples = [(measure_imported(), measure_imported_off(), measure_twin())
               for _ in range(rounds)]
    med_i = sorted(t[0] for t in triples)[rounds // 2]
    med_off = sorted(t[1] for t in triples)[rounds // 2]
    med_n = sorted(t[2] for t in triples)[rounds // 2]
    med_ratio = sorted(t[0] / t[2] for t in triples)[rounds // 2]
    med_ratio_off = sorted(t[1] / t[2] for t in triples)[rounds // 2]

    # the compiled-program evidence behind the ratio: per-step flops and
    # HBM bytes of the three programs (jax cost_analysis). Matching flops
    # with excess bytes = exporter-materialized layout/expand ops — the
    # bandwidth gap the import-graph optimizer exists to close.
    ci, ci_off = cost_on(), cost_off()
    tstep = twin._jit_cache.get("train") or twin._make_train_step()
    ct = _cost(tstep.lower(twin.params, twin.state, twin.opt_state,
                           jnp.asarray(0, jnp.int32), jnp.asarray(ids),
                           y, jax.random.key(1), None).compile())

    def _ratio(a, b, key="bytes_accessed"):
        return (round(a.get(key, 0) / b[key], 4)
                if b.get(key) else None)

    # the ACTUAL post-optimizer attention path: fused nodes in the graph
    # + the registry impl selected at the imported geometry (heads=4,
    # head_dim=16 per vmap slice)
    n_fused = _fused_attention_count(imp)
    qi = jnp.zeros((BI, 4, T, 16), jnp.bfloat16)
    imported_platform = get_op("dot_product_attention").select(
        qi, qi, qi).platform
    qshape = jnp.zeros((B, 2, T, 32), jnp.bfloat16)
    return {
        "imported_samples_per_sec": round(med_i, 1),
        "zoo_native_samples_per_sec": round(med_n, 1),
        "ratio_imported_over_native": round(med_ratio, 4),
        "imported_step_cost": ci,
        "native_step_cost": ct,
        "hbm_bytes_imported_over_native": _ratio(ci, ct),
        "attention_path_native": get_op("dot_product_attention").select(
            qshape, qshape, qshape).platform,
        "attention_path_imported": (
            "dot_product_attention[%s] x%d (import-optimizer fused)"
            % (imported_platform, n_fused) if n_fused
            else "composed (imported graph ops)"),
        "optimizer_ab": {
            "on": {"samples_per_sec": round(med_i, 1), "cost": ci,
                   "nodes": len(imp.nodes)},
            "off": {"samples_per_sec": round(med_off, 1), "cost": ci_off,
                    "nodes": len(imp_off.nodes)},
            "ratio_on_over_native": round(med_ratio, 4),
            "ratio_off_over_native": round(med_ratio_off, 4),
            "speedup_on_over_off": round(med_i / med_off, 4),
            "bytes_accessed_off_over_on": _ratio(ci_off, ci),
            "rewrites": imp.import_opt_stats,
        },
        "shapes": {"batch": B, "seq": T, "d_model": 64, "layers": 2,
                   "note": "golden exported with static (2, 16) shapes; "
                           "vmap supplies the outer batch axis"},
        "protocol": "two-point device loop, median of %d rounds, "
                    "bf16 compute / f32 master, Adam; three interleaved "
                    "lanes (optimizer on / off / native)" % rounds,
        "gap_explanation":
            "per-step FLOPs ratio %.3f vs native; HBM bytes %.2fx "
            "(raw import: %.2fx) — the import-graph optimizer removes "
            "the exporter-materialized layout/mask ops and fuses the "
            "attention pattern, closing the r05 bandwidth gap" % (
                (ci.get("flops", 0) / ct["flops"]) if ct.get("flops")
                else float("nan"),
                (ci.get("bytes_accessed", 0) / ct["bytes_accessed"])
                if ct.get("bytes_accessed") else float("nan"),
                (ci_off.get("bytes_accessed", 0) / ct["bytes_accessed"])
                if ct.get("bytes_accessed") else float("nan")),
    }


def bench_bert_import_at_scale(iters=80, rounds=3):
    """The tiny-fixture block above explains its 0.58 ratio as
    bandwidth-boundness at d_model=64 and PREDICTS the byte overhead
    amortizes at real dims — this lane proves it (r5). A BERT-like graph
    at compute-bound dims (d=256, T=64, L=4, H=4, ffn=1024) is exported
    AT BENCH TIME by torch.onnx from a transformers BertModel (random
    init; both baked into the image, no network), imported through the
    same OnnxModelImport.as_trainable path, and fine-tuned against the
    zoo twin under the identical protocol. Skips cleanly when
    torch/transformers are unavailable."""
    import importlib.machinery
    import sys
    import tempfile
    import types

    try:
        # torch 2.13's legacy exporter scans for onnxscript functions via
        # the `onnx` package, which this image lacks; the scan is a no-op
        # for plain graphs, so a stub satisfies it (the committed-golden
        # import tests use the same shim)
        if "onnx" not in sys.modules:
            stub = types.ModuleType("onnx")
            stub.__spec__ = importlib.machinery.ModuleSpec("onnx",
                                                           loader=None)
            stub.__version__ = "1.16.0"

            class _G:
                node = []

            class _M:
                graph = _G()
                functions = []

                def SerializeToString(self):
                    return b""

            stub.load_model_from_string = lambda b: _M()
            sys.modules["onnx"] = stub
        import torch
        from transformers import BertConfig, BertModel
    except Exception as e:
        return {"skipped": f"torch/transformers unavailable: {e}"[:200]}

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.modelimport.onnx import OnnxModelImport
    from deeplearning4j_tpu.optimize.updaters import Adam, get_updater
    from deeplearning4j_tpu.zoo import Bert

    BO, BI, T, V, D, L, H, F, C = 8, 8, 64, 1000, 256, 4, 4, 1024, 2
    B = BO * BI
    cfg = BertConfig(vocab_size=V, hidden_size=D, num_hidden_layers=L,
                     num_attention_heads=H, intermediate_size=F,
                     max_position_embeddings=T, type_vocab_size=1)
    torch.manual_seed(0)
    tm = BertModel(cfg).eval()
    tids = torch.zeros((BI, T), dtype=torch.long)
    tam = torch.ones((BI, T), dtype=torch.long)
    with tempfile.TemporaryDirectory() as td:
        fx = os.path.join(td, "bert_scale.onnx")
        torch.onnx.export(tm, (tids, tam), fx,
                          input_names=["input_ids", "attention_mask"],
                          output_names=["last_hidden_state",
                                        "pooler_output"],
                          opset_version=14, do_constant_folding=True,
                          dynamo=False)
        imp = OnnxModelImport.import_model(fx)
    fn, bert_params = imp.as_trainable(outputs=["pooler_output"],
                                       compute_dtype=jnp.bfloat16)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, T)).astype(np.int32)
    y = jnp.asarray(np.eye(C, dtype=np.float32)[rng.integers(0, C, B)])
    key = jax.random.key(0)
    params0 = {"bert": bert_params,
               "head": {"W": jax.random.normal(key, (D, C)) * 0.05,
                        "b": jnp.zeros((C,))}}
    updater = get_updater(Adam(lr=2e-5))
    feeds = {"input_ids": jnp.asarray(ids).reshape(BO, BI, T),
             "attention_mask": jnp.ones((BO, BI, T), jnp.int32)}

    def imported_loss(p):
        cp = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
        pooled = jax.vmap(lambda f: fn(cp["bert"], f))(feeds)
        logits = (pooled.reshape(B, D) @ cp["head"]["W"]
                  + cp["head"]["b"]).astype(jnp.float32)
        return -(y * jax.nn.log_softmax(logits)).sum(-1).mean()

    def step(p, o, i):
        loss, g = jax.value_and_grad(imported_loss)(p)
        upd, o = updater.update(g, o, p, i)
        return jax.tree_util.tree_map(lambda a, d: a - d, p, upd), o, loss

    @jax.jit
    def many(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            return step(p, o, i)
        return jax.lax.fori_loop(0, n, body,
                                 (p, o, jnp.asarray(0.0, jnp.float32)))[2]

    opt0 = updater.init_state(params0)
    measure_imported = _two_point(many, (params0, opt0), B, iters)

    twin = Bert(vocab_size=V, max_len=T, d_model=D, n_layers=L, n_heads=H,
                d_ff=F, num_classes=C, dropout=0.0, lr=2e-5,
                dtype="bf16", seed=1).init()
    twin.conf.max_grad_norm = 0.0
    twin._updaters = [get_updater(Adam(lr=2e-5)) for _ in twin.layers]
    twin.opt_state = [u.init_state(p)
                      for u, p in zip(twin._updaters, twin.params)]
    measure_twin = make_mln_two_point(twin, ids, np.asarray(y), iters=iters)

    pairs = [(measure_imported(), measure_twin()) for _ in range(rounds)]
    ratios = sorted(p[0] / p[1] for p in pairs)
    ci = _cost(jax.jit(lambda p, o: step(p, o, 0)).lower(
        params0, opt0).compile())
    tstep = twin._jit_cache.get("train") or twin._make_train_step()
    ct = _cost(tstep.lower(twin.params, twin.state, twin.opt_state,
                           jnp.asarray(0, jnp.int32), jnp.asarray(ids),
                           y, jax.random.key(1), None).compile())
    return {
        "imported_samples_per_sec":
            round(sorted(p[0] for p in pairs)[rounds // 2], 1),
        "zoo_native_samples_per_sec":
            round(sorted(p[1] for p in pairs)[rounds // 2], 1),
        "ratio_imported_over_native": round(ratios[rounds // 2], 4),
        "imported_step_cost": ci,
        "native_step_cost": ct,
        "shapes": {"batch": B, "seq": T, "d_model": D, "layers": L,
                   "heads": H, "ffn": F,
                   "note": "exported at bench time (torch.onnx, random "
                           "init); static (8, 64) shapes, vmap outer 8"},
        "protocol": "two-point device loop, median of %d rounds, "
                    "bf16 compute / f32 master, Adam" % rounds,
    }


def bench_nlp(n_sentences=50000, sent_len=19, vocab=10000, rounds=3):
    """NLP throughput (r5, VERDICT r4 #6): words/sec for streaming
    Word2Vec (skip-gram + negative sampling, the reference's headline
    configuration) over the file corpus front, with the host/device
    split measured honestly.

    Three numbers, each the median of ``rounds``:
    - end_to_end: Word2Vec.fit over a LineSentenceIterator on a real
      file — vocab pass + windowing + sampling + device steps, i.e. what
      a user gets (words/sec over the epoch's corpus words).
    - host_only: the same loop with the device step replaced by a no-op —
      pair generation, shuffling, negative sampling (the part the
      reference parallelizes with Hogwild threads; here it is one numpy
      stream feeding a device that is much faster than it).
    - device_only: the jitted _sg_neg_step chained over pre-staged
      batches, two-point timed (pairs/sec converted to words/sec via the
      measured pairs-per-word ratio).
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nlp.corpus import LineSentenceIterator
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec, _sg_neg_step

    rng = np.random.default_rng(0)
    # Zipf-ish corpus file: freq rank ~ 1/(r+1)
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    words = np.array([f"w{i}" for i in range(vocab)])
    ids_all = rng.choice(vocab, size=(n_sentences, sent_len), p=probs)
    with tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False) as f:
        for ids in ids_all:
            f.write(" ".join(words[ids]) + "\n")
        path = f.name
    n_words = n_sentences * sent_len

    try:
        import contextlib

        import deeplearning4j_tpu.nlp.word2vec as _w2v_mod

        @contextlib.contextmanager
        def _noop_device_step():
            # host_only: the compiled update becomes a no-op — measures
            # the numpy windowing/shuffle/sampling stream. NOTE: the
            # per-batch jnp.asarray host->device transfers still run (the
            # transfer sits inside train_chunk, upstream of the step), so
            # host_only is "everything except the compute", not "pure
            # numpy"
            orig = _w2v_mod._sg_neg_step
            _w2v_mod._sg_neg_step = lambda W, C, a, b, n, lr: (W, C, 0.0)
            try:
                yield
            finally:
                _w2v_mod._sg_neg_step = orig

        def fit_once(train=True, native=False):
            w2v = Word2Vec(vector_size=100, window=5, negative=5,
                           min_count=1, epochs=1, batch_size=2048, seed=1)
            ctx = (contextlib.nullcontext() if train
                   else _noop_device_step())
            with ctx:
                t0 = time.perf_counter()
                w2v.fit(LineSentenceIterator(path), native_front=native)
                return n_words / (time.perf_counter() - t0)

        from deeplearning4j_tpu.native.lib import native_available

        # the DEFAULT path (r5): native concurrent host front — C++
        # threads tokenize/encode/window in parallel, pairs ship as
        # uint16, negatives are sampled on-device, S=32 batches ride each
        # dispatch via the scanned step
        e2e_native = (sorted(fit_once(native=True) for _ in range(rounds))
                      [rounds // 2] if native_available() else None)
        e2e = sorted(fit_once(native=False)
                     for _ in range(rounds))[rounds // 2]
        host = sorted(fit_once(train=False)
                      for _ in range(rounds))[rounds // 2]

        # native host stream drain (no device work): the concurrent
        # front's own ceiling on this host's core count
        native_drain = None
        if native_available():
            from deeplearning4j_tpu.nlp.native_text import (
                NativeSkipGramStream, native_word_counts)

            wv = Word2Vec(vector_size=100, window=5, negative=5,
                          min_count=1, batch_size=2048, seed=1)
            wv.vocab.fit_from_counts(native_word_counts(path, wv.workers))
            drain_s = NativeSkipGramStream(
                path, wv.vocab.words, None, None, 5, 0, 2048, seed=1,
                n_threads=wv.workers)
            t0 = time.perf_counter()
            for _ in drain_s:
                pass
            native_drain = n_words / (time.perf_counter() - t0)
            drain_s.close()

        # device-only: the compiled step over pre-staged batches.
        # pairs-per-word: ~2*mean(min(b, dist-to-edge)) with the window
        # shrink; measure it from one chunk instead of guessing.
        w2v = Word2Vec(vector_size=100, window=5, negative=5, min_count=1)
        w2v.vocab.fit(w2v._iter_token_sents(LineSentenceIterator(path)))
        sents = []
        for i, toks in enumerate(
                w2v._iter_token_sents(LineSentenceIterator(path))):
            if i >= 2000:
                break
            sents.append(w2v.vocab.encode(toks))
        pairs = w2v._pairs(sents, rng)
        ppw = len(pairs) / (len(sents) * sent_len)
        B, K, D = 2048, 5, 100
        V = len(w2v.vocab)
        W0 = jnp.asarray(((rng.random((V, D)) - 0.5) / D).astype(np.float32))
        C0 = jnp.zeros((V, D), jnp.float32)
        centers = jnp.asarray(rng.integers(0, V, (8, B), dtype=np.int32))
        ctxs = jnp.asarray(rng.integers(0, V, (8, B), dtype=np.int32))
        negs = jnp.asarray(rng.integers(0, V, (8, B, K), dtype=np.int32))

        @jax.jit
        def many(W, C, n):
            def body(i, carry):
                W, C, _ = carry
                j = i % 8
                return _sg_neg_step(W, C, centers[j], ctxs[j], negs[j],
                                    lr=0.025)
            return jax.lax.fori_loop(0, n, body,
                                     (W, C, jnp.asarray(0.0)))[2]

        dev_round = _two_point(many, (W0, C0), B, iters=400)
        dev_pairs = sorted(dev_round() for _ in range(rounds))[rounds // 2]
        dev_words = dev_pairs / ppw
        return {
            "end_to_end_words_per_sec": round(e2e_native or e2e, 1),
            "native_front_words_per_sec": (round(e2e_native, 1)
                                           if e2e_native else None),
            "python_front_words_per_sec": round(e2e, 1),
            "native_host_drain_words_per_sec": (round(native_drain, 1)
                                                if native_drain else None),
            "host_only_words_per_sec": round(host, 1),
            "device_step_words_per_sec": round(dev_words, 1),
            "device_step_pairs_per_sec": round(dev_pairs, 1),
            "pairs_per_word": round(ppw, 3),
            "corpus": {"sentences": n_sentences, "words": n_words,
                       "vocab": vocab, "file": "LineSentenceIterator"},
            "config": "skip-gram, negative=5, window=5 (shrunk), D=100, "
                      "batch 2048",
            "bottleneck": ("host->device transfer + dispatch (host drain "
                           "and device step both exceed end-to-end)"
                           if (native_drain
                               and native_drain > 1.5 * (e2e_native or e2e)
                               and dev_words > 1.5 * (e2e_native or e2e))
                           else ("host pair generation"
                                 if (e2e_native or e2e) < dev_words
                                 else "device step")),
            "note": "end_to_end is the DEFAULT path (r5): the native "
                    "concurrent host front (the reference's Hogwild-class "
                    "concurrency, N C++ worker threads) with uint16 pair "
                    "transfer + on-device alias negative sampling + S=32 "
                    "scanned batches per dispatch; python_front is the "
                    "deterministic single-threaded stream (the r4 path); "
                    "host_only is the python front minus the device step; "
                    "native_host_drain is the C++ pipeline alone on this "
                    "host's cores",
        }
    finally:
        os.unlink(path)


def bench_serving(n_requests=384, clients=16, batch_limit=32):
    """Serving performance lane (r5, VERDICT r4 #5): p50/p99 request
    latency and sustained throughput through ParallelInference, batching
    ON vs OFF, plus the direct output() floor.

    Protocol: `clients` threads each fire n_requests/clients single
    requests back-to-back (closed loop); per-request latency is
    submit -> result. The direct lane is one thread calling
    model.output(x[None]) sequentially — the no-server floor. The
    comparison between lanes (one dispatch per request vs one per
    coalesced batch) is exactly the batching win the reference's
    ParallelInference exists for."""
    import threading

    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel import ParallelInference
    from deeplearning4j_tpu.zoo import LeNet

    model = LeNet().init()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(n_requests, 28, 28, 1)).astype(np.float32)

    def pctl(lat, q):
        return float(np.percentile(np.asarray(lat) * 1000.0, q))

    def lane_direct(n=64):
        jax.block_until_ready(model.output(xs[:1]))     # compile
        lats = []
        t00 = time.perf_counter()
        for i in range(n):
            t0 = time.perf_counter()
            np.asarray(model.output(xs[i:i + 1]))
            lats.append(time.perf_counter() - t0)
        dt = time.perf_counter() - t00
        return {"p50_ms": round(pctl(lats, 50), 2),
                "p99_ms": round(pctl(lats, 99), 2),
                "throughput_rps": round(n / dt, 1),
                "requests": n}

    def lane_pi(batching):
        pi = ParallelInference(
            model, batch_limit=batch_limit if batching else 1,
            queue_timeout_s=0.01).start()
        try:
            # warm every dispatchable bucket (pow2s clamped to the limit,
            # plus the limit itself for non-pow2 limits) so compiles
            # don't ride the timing
            warm = (sorted({min(1 << i, batch_limit)
                            for i in range(batch_limit.bit_length() + 1)})
                    if batching else [1])
            for warm_n in warm:
                np.asarray(model.output(xs[:warm_n]))
            lats, lock = [], threading.Lock()
            per_client = n_requests // clients

            def client(ci):
                mine = []
                for i in range(per_client):
                    t0 = time.perf_counter()
                    pi.submit(xs[(ci * per_client + i) % len(xs)]).get(
                        timeout=60)
                    mine.append(time.perf_counter() - t0)
                with lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            return {"p50_ms": round(pctl(lats, 50), 2),
                    "p99_ms": round(pctl(lats, 99), 2),
                    "throughput_rps": round(len(lats) / dt, 1),
                    "requests": len(lats), "clients": clients}
        finally:
            pi.stop()

    direct = lane_direct()
    off = lane_pi(batching=False)
    on = lane_pi(batching=True)
    return {
        "model": "LeNet (28x28x1 -> 10)",
        "direct_output": direct,
        "parallel_inference_batching_off": off,
        "parallel_inference_batching_on": on,
        "batching_speedup_vs_off": round(
            on["throughput_rps"] / max(off["throughput_rps"], 1e-9), 2),
    }


def bench_serving_gateway(n_requests=384, clients=16, batch_limit=32,
                          overload_clients=48, overload_queue=8):
    """Serving-gateway lane (PR 2): the FULL HTTP path through
    ServingGateway — two model versions on a 90/10 canary split, warmed at
    every pad-to-bucket batch shape at load time.

    Two phases: (1) steady state — `clients` closed-loop threads, p50/p99
    request latency + sustained throughput, shed rate must be 0; (2)
    synthetic overload — `overload_clients` threads against a gateway
    whose per-model queue is only `overload_queue` deep, measuring the
    shed (429) rate and confirming the burst resolves promptly instead of
    piling up. Warmup timings per bucket + the first post-warmup request
    latency quantify the no-compile-on-request-path property."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.serving import ServingGateway
    from deeplearning4j_tpu.zoo import LeNet

    monitoring.enable()
    v1, v2 = LeNet().init(), LeNet().init()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 28, 28, 1)).astype(np.float32)

    def pctl(lat, q):
        return float(np.percentile(np.asarray(lat) * 1000.0, q))

    def fire(base, payload):
        req = urllib.request.Request(
            base + "/v1/lenet/predict", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        for attempt in range(3):
            try:
                urllib.request.urlopen(req, timeout=120).read()
                return 200, time.perf_counter() - t0
            except urllib.error.HTTPError as e:
                e.read()
                return e.code, time.perf_counter() - t0
            except (ConnectionResetError, urllib.error.URLError):
                # transient TCP-level reset under burst; retry briefly
                if attempt == 2:
                    return 599, time.perf_counter() - t0
                time.sleep(0.01 * (attempt + 1))

    def fleet(base, n_clients, per_client):
        stats, lock = {"lat_ok": [], "codes": {}}, threading.Lock()

        def client(ci):
            mine_lat, mine_codes = [], {}
            for i in range(per_client):
                payload = {"inputs": [xs[(ci + i) % len(xs)].tolist()],
                           "timeout_ms": 120000}
                code, dt = fire(base, payload)
                mine_codes[code] = mine_codes.get(code, 0) + 1
                if code == 200:
                    mine_lat.append(dt)
            with lock:
                stats["lat_ok"].extend(mine_lat)
                for c, n in mine_codes.items():
                    stats["codes"][c] = stats["codes"].get(c, 0) + n

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        total = sum(stats["codes"].values())
        served = stats["codes"].get(200, 0)
        return {"p50_ms": round(pctl(stats["lat_ok"], 50), 2),
                "p99_ms": round(pctl(stats["lat_ok"], 99), 2),
                "throughput_rps": round(served / dt, 1),
                "offered_rps": round(total / dt, 1),
                "requests": total, "served": served,
                "shed_429": stats["codes"].get(429, 0),
                "shed_rate": round(
                    stats["codes"].get(429, 0) / max(total, 1), 3),
                "codes": {str(k): v for k, v in stats["codes"].items()},
                "clients": n_clients}

    def run_phase(max_queue, n_clients, total_requests, limit=None):
        gw = ServingGateway(port=0, batch_limit=limit or batch_limit,
                            max_queue=max_queue, seed=0).start()
        try:
            mv1 = gw.register_model("lenet", "v1", v1,
                                    warmup_shape=(28, 28, 1))
            gw.register_model("lenet", "v2", v2, warmup_shape=(28, 28, 1),
                              weight=0.0)
            gw.set_split("lenet", {"v1": 0.9, "v2": 0.1})
            base = f"http://127.0.0.1:{gw.port}"
            code, first_lat = fire(
                base, {"inputs": [xs[0].tolist()], "timeout_ms": 120000})
            out = fleet(base, n_clients, total_requests // n_clients)
            out["first_request_ms"] = round(first_lat * 1000.0, 2)
            out["warmup_buckets_ms"] = {
                str(b): round(t * 1000.0, 1)
                for b, t in sorted(mv1.warmup_timings.items())}
            return out
        finally:
            gw.stop()

    steady = run_phase(max_queue=max(clients * 4, 128), n_clients=clients,
                       total_requests=n_requests)
    # overload: small queue AND small coalescing limit so the offered load
    # genuinely exceeds drain capacity — quantifies the 429 backpressure
    overload = run_phase(max_queue=overload_queue,
                         n_clients=overload_clients,
                         total_requests=n_requests, limit=4)
    return {
        "model": "LeNet x2 versions (90/10 canary split)",
        "batch_limit": batch_limit,
        "steady": steady,
        "overload": overload,
        "note": "steady shed_rate should be 0; overload quantifies "
                "never-hangs backpressure (429 + Retry-After). "
                "first_request_ms excludes compile (warmed buckets).",
    }


def bench_chaos(interactive_clients=6, batch_clients=10,
                interactive_per=20, batch_per=12, objective_ms=2000.0,
                spike_factor=3):
    """Chaos lane (PR 11): the multi-tenant gateway under injected faults.

    A small dense MLP behind a ServingGateway configured with two tenants
    (``interactive`` > ``batch``), a per-class latency SLO, replica
    autoscaling, and deliberately tight per-lane queues. Two phases over
    the SAME gateway:

      - steady: both classes run closed-loop, nothing armed;
      - chaos: the faults grammar arms ``worker_crash`` (self-healed
        restarts), ``slow_worker`` (random dispatch stalls), and
        ``traffic_spike`` — batch clients poll the spike trigger and
        multiply their offered load while it fires, so the grammar drives
        the OFFERED load, not just the serving side.

    Acceptance (reported in the artifact): interactive p99 stays within
    its objective through the chaos phase while the batch class sheds
    (429s) > 0, and the per-class ``dl4j_serving_shed_total`` deltas
    witness shed-lowest-class-first.

    Observability hook (PR 12): the gateway runs traced and the flight
    recorder is armed for the whole lane, so every admit / shed / crash /
    autoscale / fault-injection incident of the chaos phase lands in the
    ring; the bundle is force-dumped to ``FLIGHT_chaos.json`` next to the
    BENCH artifact and its path is reported in the lane result."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from deeplearning4j_tpu import faults, monitoring
    from deeplearning4j_tpu.monitoring import flight
    from deeplearning4j_tpu.nn import (
        InputType, MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import ServingGateway

    monitoring.enable()
    # ring only (no dump_dir): trigger kinds accumulate instead of writing
    # one bundle per crash; the single postmortem is force-dumped below
    flight.configure(enabled=True, capacity=2048)
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=8, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(32)).build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 32)).astype(np.float32)

    def pctl(lat, q):
        if not lat:
            return None
        return float(np.percentile(np.asarray(lat) * 1000.0, q))

    def shed_by_class():
        fam = monitoring.registry().get("dl4j_serving_shed_total")
        out = {}
        if fam is not None:
            for key, child in fam.children():   # key = (model, reason, class)
                out[key[2]] = out.get(key[2], 0.0) + child.value
        return out

    gw = ServingGateway(
        port=0, batch_limit=4, max_queue=6, seed=0,
        tenants=[{"key": "key-int", "name": "interactive-tenant",
                  "klass": "interactive"},
                 {"key": "key-bat", "name": "batch-tenant",
                  "klass": "batch"}],
        slo={"interactive": {"objective_ms": objective_ms, "target": 0.99}},
        autoscale={"max_replicas": 2, "high_backlog": 4.0,
                   "scale_up_after": 2, "interval_s": 0.1},
        trace=True).start()
    base = f"http://127.0.0.1:{gw.port}"
    mv = gw.register_model("mlp", "v1", model, warmup_shape=(32,),
                           batch_limit=4)

    def fire(key, i):
        req = urllib.request.Request(
            base + "/v1/mlp/predict",
            data=_json.dumps({"inputs": [xs[i % len(xs)].tolist()],
                              "timeout_ms": 60000,
                              "api_key": key}).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            urllib.request.urlopen(req, timeout=90).read()
            return 200, time.perf_counter() - t0
        except urllib.error.HTTPError as e:
            e.read()
            return e.code, time.perf_counter() - t0
        except (ConnectionResetError, urllib.error.URLError):
            return 599, time.perf_counter() - t0

    def run_phase(tag, plan):
        stats = {"interactive": {"lat": [], "codes": {}},
                 "batch": {"lat": [], "codes": {}}}
        lock = threading.Lock()
        shed_before = shed_by_class()

        def client(klass, key, per, ci):
            mine_lat, mine_codes = [], {}
            for i in range(per):
                # the spike trigger multiplies the BATCH offered load
                burst = (spike_factor
                         if (plan is not None and klass == "batch"
                             and plan.fires("traffic_spike")) else 1)
                for b in range(burst):
                    code, dt = fire(key, ci * per + i + b)
                    mine_codes[code] = mine_codes.get(code, 0) + 1
                    if code == 200:
                        mine_lat.append(dt)
            with lock:
                stats[klass]["lat"].extend(mine_lat)
                for c, n in mine_codes.items():
                    stats[klass]["codes"][c] = (
                        stats[klass]["codes"].get(c, 0) + n)

        threads = (
            [threading.Thread(target=client,
                              args=("interactive", "key-int",
                                    interactive_per, ci))
             for ci in range(interactive_clients)] +
            [threading.Thread(target=client,
                              args=("batch", "key-bat", batch_per, ci))
             for ci in range(batch_clients)])
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        shed_after = shed_by_class()
        out = {"wall_s": round(dt, 2),
               "shed_delta_by_class": {
                   k: shed_after.get(k, 0.0) - shed_before.get(k, 0.0)
                   for k in set(shed_before) | set(shed_after)}}
        for klass, s in stats.items():
            total = sum(s["codes"].values())
            out[klass] = {
                "p50_ms": pctl(s["lat"], 50), "p99_ms": pctl(s["lat"], 99),
                "requests": total, "served": s["codes"].get(200, 0),
                "shed_429": s["codes"].get(429, 0),
                "shed_rate": round(s["codes"].get(429, 0) / max(total, 1),
                                   3),
                "codes": {str(k): v for k, v in s["codes"].items()}}
        code = urllib.request.urlopen(base + "/slo", timeout=10)
        out["slo"] = _json.loads(code.read())
        return out

    def run_recovery_phase():
        """ISSUE-13: kill-and-resume drill for durable generation sessions.

        N sessions stream from a journal-armed char-LSTM engine; the
        faults grammar arms ``preempt`` (the in-process SIGTERM
        equivalent) + ``worker_crash``, and the preemption fires
        mid-decode with no lifecycle manager — the engine loop dies hard,
        exactly like an unhandled SIGTERM. A fresh engine on the same
        journal then resumes every interrupted session; reported: the
        sessions-resumed rate, whether every resumed stream is
        BIT-IDENTICAL to its uninterrupted reference, and the p99 added
        latency of recovery (restart -> first resumed token)."""
        import tempfile

        from deeplearning4j_tpu.nn.layers import LSTMLayer, RnnOutputLayer
        from deeplearning4j_tpu.generation import (
            GenerationEngine, SessionJournal,
        )

        vocab, n_sessions = 13, 12
        lconf = (NeuralNetConfiguration.builder().seed(7).list()
                 .layer(LSTMLayer(n_out=24))
                 .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                       loss="mcxent"))
                 .set_input_type(InputType.recurrent(vocab, 8)).build())
        lnet = MultiLayerNetwork(lconf).init()
        reqs = [{"prompt": [1 + (i % 5), 2, 3], "max_new_tokens": 40,
                 "temperature": 0.9, "seed": 100 + i}
                for i in range(n_sessions)]

        # uninterrupted references (same engine config -> same keys)
        ref_eng = GenerationEngine(lnet, slots=4, max_len=64)
        refs = {}
        streams = {f"sess-{i}": ref_eng.submit(**reqs[i])
                   for i in range(n_sessions)}
        ref_eng.drain()
        for rid, s in streams.items():
            refs[rid] = list(s.tokens)

        path = os.path.join(tempfile.mkdtemp(prefix="dl4j-recovery-"),
                            "sessions.ndjson")
        eng = GenerationEngine(lnet, slots=4, max_len=64,
                               journal=SessionJournal(path)).start()
        with faults.injected("preempt:1@step==12;worker_crash:2", seed=0):
            live = [eng.submit(request_id=f"sess-{i}", **reqs[i])
                    for i in range(n_sessions)]
            for s in live:
                s.wait(timeout=60)
        preempted = sum(1 for s in live if s.finish_reason == "preempted")
        eng.journal.close()

        # the restart: fresh engine, same journal, resume before traffic
        t0 = time.perf_counter()
        t0_mono = time.monotonic()
        j2 = SessionJournal(path)
        eng2 = GenerationEngine(lnet, slots=4, max_len=64,
                                journal=j2).start()
        out = j2.resume_into(eng2)
        resumed_streams = [j2.get(f"sess-{i}").stream
                           for i in range(n_sessions)
                           if j2.get(f"sess-{i}").stream is not None]
        for s in resumed_streams:
            s.wait(timeout=60)
        recovery_wall = time.perf_counter() - t0
        # added latency of recovery: restart begin -> first resumed token
        resume_ttft = [s.first_token_at - t0_mono for s in resumed_streams
                       if s.first_token_at is not None]
        exact = all(j2.get(f"sess-{i}").tokens == refs[f"sess-{i}"]
                    for i in range(n_sessions)
                    if not j2.get(f"sess-{i}").lost)
        finished = sum(1 for i in range(n_sessions)
                       if j2.get(f"sess-{i}").finish_reason == "length")
        eng2.shutdown(timeout=10)
        j2.close()
        rate = (out["resumed"] + out["completed"]) / float(n_sessions)
        return {
            "sessions": n_sessions,
            "preempted_mid_decode": preempted,
            "resumed": out["resumed"], "lost": out["lost"],
            "completed_at_crash": out["completed"],
            "finished_after_resume": finished,
            "sessions_resumed_rate": round(rate, 3),
            "resume_bit_identical": bool(exact),
            "recovery_wall_s": round(recovery_wall, 2),
            "recovery_added_p99_ms": pctl(resume_ttft, 99),
            "recovery_added_p50_ms": pctl(resume_ttft, 50),
            "journal": path,
        }

    try:
        steady = run_phase("steady", plan=None)
        with faults.injected(
                "worker_crash:2;slow_worker:0.4;traffic_spike:0.5",
                seed=0, delay_s=0.08) as plan:
            chaos = run_phase("chaos", plan=plan)
            injected = dict(plan.injected)
        recovery = run_recovery_phase()
        # the recovery drill must be VISIBLE: the resume outcome counter
        # and the flight recorder's preempt incident are the witnesses an
        # operator would actually page on
        recovery["recovery_metric_visible"] = (
            'dl4j_recovery_total{component="generation",'
            'outcome="session_resumed"}') in monitoring.metrics_text()
        _rec = flight.recorder()
        recovery["flight_preempt_incident"] = bool(
            _rec is not None
            and any(e.get("kind") == "preempt" for e in _rec.tail()))
        replicas_final = mv.pi.replicas()
        # PR 12: the chaos lane's black box, next to the BENCH artifact —
        # every admit/shed/crash/autoscale/fault event of the run, plus a
        # metrics snapshot, in one Perfetto-adjacent postmortem bundle
        flight_bundle, flight_events = None, 0
        rec = flight.recorder()
        if rec is not None:
            here = os.path.dirname(os.path.abspath(__file__))
            flight_bundle = rec.dump(
                "chaos_lane", force=True,
                path=os.path.join(here, "FLIGHT_chaos.json"))
            flight_events = rec.describe(tail=1)["recorded_total"]
    finally:
        gw.stop()
        flight.reset()
    chaos_shed = chaos["shed_delta_by_class"]
    return {
        "model": "dense MLP 32->64->8 (multi-tenant gateway)",
        "objective_ms": objective_ms,
        "steady": steady,
        "chaos": chaos,
        "recovery": recovery,
        "faults_injected": injected,
        "flight_bundle": flight_bundle,
        "flight_events_recorded": flight_events,
        "replicas_final": replicas_final,
        "acceptance": {
            "interactive_p99_within_objective":
                chaos["interactive"]["p99_ms"] is not None
                and chaos["interactive"]["p99_ms"] <= objective_ms,
            "batch_shed_gt_zero": chaos["batch"]["shed_429"] > 0,
            "shed_order_lowest_first":
                chaos_shed.get("batch", 0.0)
                >= chaos_shed.get("interactive", 0.0),
            "sessions_resumed_rate_ge_95":
                recovery["sessions_resumed_rate"] >= 0.95,
            "resume_bit_identical": recovery["resume_bit_identical"],
            "recovery_observable":
                recovery["recovery_metric_visible"]
                and recovery["flight_preempt_incident"],
        },
        "note": "chaos arms worker_crash (self-healed), slow_worker "
                "(dispatch stalls), traffic_spike (batch clients poll the "
                "trigger and burst). Interactive rides the priority lane, "
                "so its p99 holds while the batch lane absorbs the shed. "
                "The recovery phase (PR 13) preempts a journal-armed "
                "generation engine mid-decode and witnesses the resumed "
                "sessions bit-identical to their uninterrupted references.",
    }


def bench_generate(n_requests=48, slots=8, units=256, vocab=77,
                   budget_deadline=None):
    """Generation-engine lane (continuous-batching PR): autoregressive
    decode throughput + streaming SLOs over a mixed-length workload.

    One char-LSTM net (zoo TextGenerationLSTM topology), one slot pool,
    TWO scheduling policies over the identical seeded workload:
      - ``continuous``: admit into free slots every step, retire on finish
        (the engine's production mode);
      - ``static``: run-to-completion batching — a batch must fully finish
        before the next is admitted (what a naive fixed-batch sampler
        does, and the A/B baseline the ISSUE acceptance names).
    Reported per policy: tokens/sec, TTFT p50/p99, inter-token p99 (all
    measured at STREAM ARRIVAL by per-request consumer threads, i.e. what
    a client would see), plus the compile-counter witness — decode must
    stay ONE program for the whole run. Prompts/max-new are seeded, so the
    A/B compares schedulers, not workloads; both run after an untimed
    warmup pass that compiles every prefill bucket."""
    import threading

    import numpy as np

    from deeplearning4j_tpu.generation import GenerationEngine
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import LSTMLayer, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    rng = np.random.default_rng(0)
    lens = rng.integers(4, 48, n_requests)
    # long-tailed completion mix (the serving reality that motivates
    # continuous batching): mostly short answers, a minority of long ones
    # that run-to-completion batching lets block a whole batch's slots
    news = np.where(rng.random(n_requests) < 0.75,
                    rng.integers(8, 32, n_requests),
                    rng.integers(96, 192, n_requests))
    prompts = [rng.integers(0, vocab, int(l)).tolist() for l in lens]

    conf = (
        NeuralNetConfiguration.builder().seed(0).list()
        .layer(LSTMLayer(n_out=units))
        .layer(LSTMLayer(n_out=units))
        .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                              loss="mcxent"))
        .set_input_type(InputType.recurrent(vocab, 64))
        .build()
    )
    net = MultiLayerNetwork(conf).init()

    def pctl(xs, q):
        return (None if not xs
                else round(float(np.percentile(np.asarray(xs), q)), 2))

    def run(continuous):
        eng = GenerationEngine(net, slots=slots, max_len=256,
                               continuous=continuous)
        # untimed warmup: compiles the decode step + every prefill bucket
        # this workload touches, so the timed run measures scheduling
        for p in prompts:
            eng.submit(p, max_new_tokens=2)
        eng.drain()

        arrivals = [[] for _ in range(n_requests)]
        submit_t = [0.0] * n_requests
        streams, consumers = [], []

        def consume(s, acc):
            for _ in s:
                acc.append(time.perf_counter())

        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            submit_t[i] = time.perf_counter()
            s = eng.submit(p, max_new_tokens=int(news[i]), temperature=0.8,
                           top_k=40, seed=i)
            th = threading.Thread(target=consume, args=(s, arrivals[i]),
                                  daemon=True)
            th.start()
            streams.append(s)
            consumers.append(th)
        eng.drain()
        for th in consumers:
            th.join()
        dt = time.perf_counter() - t0
        total = sum(len(s.tokens) for s in streams)
        ttft_ms = [(a[0] - submit_t[i]) * 1000.0
                   for i, a in enumerate(arrivals) if a]
        inter_ms = np.concatenate(
            [np.diff(a) * 1000.0 for a in arrivals if len(a) > 1])
        return {
            "tokens_per_sec": round(total / dt, 1),
            "wall_secs": round(dt, 2),
            "tokens": total,
            "ttft_p50_ms": pctl(ttft_ms, 50),
            "ttft_p99_ms": pctl(ttft_ms, 99),
            "inter_token_p99_ms": pctl(inter_ms.tolist(), 99),
            "decode_steps": eng.steps_run,
            "decode_programs": eng.decode_programs,
            "prefill_programs": eng.prefill_programs,
        }

    cont = run(continuous=True)
    out = {
        "model": f"char-LSTM {units}x2 vocab {vocab}",
        "workload": {"requests": n_requests, "slots": slots,
                     "prompt_len": [int(lens.min()), int(lens.max())],
                     "max_new_tokens": [int(news.min()), int(news.max())]},
        "continuous": cont,
    }
    if budget_deadline is not None and time.perf_counter() > budget_deadline:
        out["static"] = {"skipped": "deadline margin exhausted"}
        return out
    stat = run(continuous=False)
    out["static"] = stat
    out["continuous_speedup"] = round(
        cont["tokens_per_sec"] / stat["tokens_per_sec"], 2)
    return out


def bench_quantize(iters=30, budget_deadline=None):
    """Int8 quantization lane (quantize PR): is weight-only int8 + int8 KV
    actually buying the bandwidth it claims, and at what accuracy cost?

    Two A/Bs, both against the SAME trained weights:
      - ``predict``: a zoo.Bert-shaped encoder under the bf16 compute
        policy, full-precision weights vs ``net.quantize()``. Reports
        samples/sec both ways, the compiled programs' cost_analysis
        bytes_accessed ratio (the lever being claimed: >= 1.5x fewer
        bytes), and top-1 agreement of the output distributions.
      - ``decode``: a char-transformer GenerationEngine, f32 KV ring vs
        ``kv_dtype="int8"`` over the identical seeded workload. Reports
        tokens/sec both ways, the decode step's bytes ratio, the
        compile-counter witness (decode stays ONE program), and the
        accuracy contract: top-1 agreement + max softmax-distribution
        delta of int8-KV cached decode vs the f32 cached path (<= 1e-2).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.generation import GenerationEngine
    from deeplearning4j_tpu.generation.engine import AttentionDecodeAdapter
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer,
    )
    from deeplearning4j_tpu.nn.layers.attention import (
        PositionalEmbeddingLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import Bert

    out = {}

    # ---------------------------------------------- predict A/B (weights)
    # serving-style small batch: per-sample weight traffic dominates, the
    # bandwidth-bound regime the int8 pass targets (large-batch training
    # amortizes the weight read and is NOT the claim)
    B, T, V, C = 4, 32, 1000, 4
    net = Bert(vocab_size=V, max_len=T, d_model=512, n_layers=4, n_heads=8,
               d_ff=2048, num_classes=C, dropout=0.0, dtype="bf16",
               seed=0).init()
    qnet = net.quantize()
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, V, (B, T)).astype(np.int32))

    def timed(model):
        y = model.output(ids)                      # compile + warmup
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(iters):
            y = model.output(ids)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        fn = model._jit_cache["output"]
        cost = _cost(fn.lower(model.params, model.state, ids,
                              None).compile())
        return iters * B / dt, cost, np.asarray(y)

    base_sps, base_cost, yb = timed(net)
    q_sps, q_cost, yq = timed(qnet)
    bytes_ratio = None
    if base_cost.get("bytes_accessed") and q_cost.get("bytes_accessed"):
        bytes_ratio = round(base_cost["bytes_accessed"]
                            / q_cost["bytes_accessed"], 3)
    out["predict"] = {
        "model": "zoo.Bert d512 L4 T32 B4 (bf16 compute)",
        "baseline_samples_per_sec": round(base_sps, 1),
        "int8_samples_per_sec": round(q_sps, 1),
        "int8_speedup": round(q_sps / base_sps, 3),
        "baseline_bytes_accessed": base_cost.get("bytes_accessed"),
        "int8_bytes_accessed": q_cost.get("bytes_accessed"),
        "bytes_reduction": bytes_ratio,
        # exact storage-side reduction (cost_analysis also counts backend
        # emulation copies — XLA:CPU materializes every convert — so the
        # param-tree ratio is the floor-truth of what int8 removed)
        "param_bytes_reduction": round(
            sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(net.params))
            / sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                  for l in jax.tree_util.tree_leaves(qnet.params)), 3),
        "top1_agreement": round(
            float((yb.argmax(-1) == yq.argmax(-1)).mean()), 4),
        "max_prob_delta": round(float(np.abs(yb - yq).max()), 5),
    }

    if budget_deadline is not None and time.perf_counter() > budget_deadline:
        out["decode"] = {"skipped": "deadline margin exhausted"}
        return out

    # ------------------------------------------------ decode A/B (KV ring)
    # the model must be big enough that per-step weight + cache streaming
    # dominates launch overhead, or the int8 lever has nothing to shrink
    D, H, n_layers, vocab, max_len = 256, 8, 4, 512, 96
    b = (NeuralNetConfiguration.builder().seed(1).list()
         .layer(EmbeddingSequenceLayer(n_out=D, n_in=vocab))
         .layer(PositionalEmbeddingLayer(max_len=max_len)))
    for _ in range(n_layers):
        b = b.layer(TransformerEncoderLayer(d_model=D, n_heads=H,
                                            causal=True))
    conf = (b.layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab, 16))
            .build())
    tnet = MultiLayerNetwork(conf).init()
    n_req = 16
    lens = rng.integers(4, 16, n_req)
    news = rng.integers(12, 40, n_req)
    prompts = [rng.integers(0, vocab, int(l)).tolist() for l in lens]

    qtnet = tnet.quantize()    # int8 serving = int8 weights + int8 KV

    def run_engine(model, kv_dtype):
        eng = GenerationEngine(model, slots=8, max_len=max_len,
                               kv_dtype=kv_dtype)
        for p in prompts:                          # untimed compile pass
            eng.submit(p, max_new_tokens=2)
        eng.drain()
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=int(news[i]),
                              temperature=0.8, top_k=40, seed=i)
                   for i, p in enumerate(prompts)]
        eng.drain()
        dt = time.perf_counter() - t0
        total = sum(len(s.tokens) for s in streams)
        return {"tokens_per_sec": round(total / dt, 1),
                "decode_programs": eng.decode_programs}

    f32_run = run_engine(tnet, None)
    int8_run = run_engine(qtnet, "int8")

    # accuracy contract + decode-step bytes, via the adapters directly
    af = AttentionDecodeAdapter(tnet, max_len)
    aq = AttentionDecodeAdapter(tnet, max_len, kv_dtype="int8")
    Bd = 8
    pr = jnp.asarray(rng.integers(0, vocab, (Bd, 12)))
    length = jnp.full((Bd,), 12)
    cf = af.prefill(tnet.params, tnet.state, pr, length)
    cq = aq.prefill(tnet.params, tnet.state, pr, length)
    df = jax.jit(af.decode)
    dq = jax.jit(aq.decode)
    toks = pr[:, -1]
    agree, max_prob_delta, max_logit_delta = [], 0.0, 0.0
    for t in range(11, 43):
        pos = jnp.full((Bd,), t, jnp.int32)
        lf, cf = df(tnet.params, tnet.state, cf, toks, pos)
        lq, cq = dq(tnet.params, tnet.state, cq, toks, pos)
        pf, pq = jax.nn.softmax(lf, -1), jax.nn.softmax(lq, -1)
        max_prob_delta = max(max_prob_delta,
                             float(jnp.abs(pf - pq).max()))
        max_logit_delta = max(max_logit_delta,
                              float(jnp.abs(lf - lq).max()))
        agree.append(float((lf.argmax(-1) == lq.argmax(-1)).mean()))
        toks = lf.argmax(-1)                       # same token feed to both
    cost_f = _cost(df.lower(tnet.params, tnet.state, cf, toks,
                            pos).compile())
    # bytes of the FULL int8 path (int8 weights + int8 KV), matching the
    # engine A/B above
    afull = AttentionDecodeAdapter(qtnet, max_len, kv_dtype="int8")
    cfull = afull.prefill(qtnet.params, qtnet.state, pr, length)
    cost_q = _cost(jax.jit(afull.decode).lower(
        qtnet.params, qtnet.state, cfull, toks, pos).compile())
    kv_bytes_ratio = None
    if cost_f.get("bytes_accessed") and cost_q.get("bytes_accessed"):
        kv_bytes_ratio = round(cost_f["bytes_accessed"]
                               / cost_q["bytes_accessed"], 3)
    out["decode"] = {
        "model": f"char-transformer d{D} L{n_layers} vocab {vocab}",
        "f32_kv": f32_run,
        "int8_kv": int8_run,
        "int8_speedup": round(int8_run["tokens_per_sec"]
                              / f32_run["tokens_per_sec"], 3),
        "decode_step_bytes_reduction": kv_bytes_ratio,
        "top1_agreement": round(float(np.mean(agree)), 4),
        "max_prob_delta": round(max_prob_delta, 5),
        "max_logit_delta": round(max_logit_delta, 5),
    }
    return out


def bench_faults(steps=150, rounds=3):
    """Recovery-cost lane (fault-injection PR): what resilience costs.

    Lanes, all on one small MLN fit loop (host-side machinery is what's
    being measured, not the device step):
      - ``steady_off``: fit throughput with no fault plan installed (the
        production default — hooks compile to a None check);
      - ``steady_armed``: a plan installed whose rules can never fire
        (upper bound on the *armed* bookkeeping cost);
      - ``steady_faulted``: a fixed seeded schedule (ckpt_io + data_io
        retries riding the checkpoint cadence) — the price of absorbing
        real faults;
    plus per-class MTTR (wall-clock from injection to completed recovery,
    measured on the recovery operation itself minus its clean-run cost)
    and steps lost per crash (kill-and-resume against the checkpoint
    cadence with a corrupted-latest fallback)."""
    import shutil
    import tempfile

    import numpy as np

    from deeplearning4j_tpu import faults
    from deeplearning4j_tpu.datasets import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (
        InputType, MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd
    from deeplearning4j_tpu.parallel.distributed import FaultTolerantTrainer
    from deeplearning4j_tpu.util.checkpoints import TrainingCheckpointer

    def model():
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Sgd(lr=0.05)).list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(16)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]

    def fit_lane():
        m = model()
        it = ArrayDataSetIterator(x, y, batch_size=16)
        m.fit(it, epochs=1)                     # compile + warm
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            for ds in it:
                m.fit_batch(ds)
                done += 1
                if done >= steps:
                    break
        return steps / (time.perf_counter() - t0)

    faults.configure("")
    steady_off = [fit_lane() for _ in range(rounds)]
    faults.configure("data_io:1@call<0", seed=0)   # armed, never fires
    steady_armed = [fit_lane() for _ in range(rounds)]
    faults.configure("")

    # ---- per-class MTTR: recovery-op wall time minus its clean cost ----
    retry = faults.RetryPolicy(max_attempts=4, base_delay_s=0.02,
                               max_delay_s=0.2, seed=0)
    mttr = {}

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    work = tempfile.mkdtemp(prefix="bench_faults_")
    try:
        m = model()
        ck = TrainingCheckpointer(os.path.join(work, "mttr"), keep_last=4,
                                  async_save=False, retry=retry)
        clean_save = timed(lambda: ck.save(1, m))
        with faults.injected("ckpt_io:1", seed=0):
            mttr["ckpt_io"] = round(
                max(0.0, timed(lambda: ck.save(2, m)) - clean_save), 4)
        ck.save(3, m)
        clean_restore = timed(lambda: ck.restore_latest(model()))
        ck._corrupt_step(3)
        mttr["ckpt_corrupt"] = round(
            max(0.0, timed(lambda: ck.restore_latest(model()))
                - clean_restore), 4)
        ck.close()

        def flaky_connect():
            calls = {"n": 0}

            def connect():
                plan = faults.active()
                if plan is not None and plan.fires("coord_connect"):
                    raise faults.CoordinatorConnectFault("refused")
                calls["n"] += 1

            retry.call(connect, component="distributed")

        with faults.injected("coord_connect:1", seed=0):
            mttr["coord_connect"] = round(timed(flaky_connect), 4)

        it = ArrayDataSetIterator(x, y, batch_size=16)
        clean_epoch = timed(lambda: list(it))
        with faults.injected("data_io:1", seed=0):
            mttr["data_io"] = round(
                max(0.0, timed(lambda: list(it)) - clean_epoch), 4)

        from deeplearning4j_tpu.parallel.inference import ParallelInference

        class _Echo:
            def output(self, z):
                return np.asarray(z)

        pi = ParallelInference(_Echo(), queue_timeout_s=0.001).start()
        try:
            pi.submit(np.ones(4)).get(timeout=30)      # warm
            with faults.injected("infer_crash:1", seed=0):
                def crash_and_recover():
                    pi.submit(np.ones(4)).get(timeout=30)   # errored
                    pi.submit(np.ones(4)).get(timeout=30)   # served again
                mttr["infer_crash"] = round(timed(crash_and_recover), 4)
        finally:
            pi.stop()

        # ---- steps lost per crash: cadence vs corrupted-latest resume ----
        crash_at, save_every = 17, 5
        ft_dir = os.path.join(work, "ft")
        tr = FaultTolerantTrainer(model(), ft_dir, save_every=save_every)
        it = ArrayDataSetIterator(x, y, batch_size=16)
        while tr._target.step_count < crash_at:
            for ds in it:
                tr.fit_batch(ds)
                if tr._target.step_count >= crash_at:
                    break
        tr.checkpointer.wait()                  # "crash": abandon trainer
        relaunch = FaultTolerantTrainer(model(), ft_dir,
                                        save_every=save_every)
        steps_lost = crash_at - (relaunch.restored_step or 0)
        relaunch.checkpointer._corrupt_step(relaunch.restored_step)
        fallback = FaultTolerantTrainer(model(), ft_dir,
                                        save_every=save_every)
        steps_lost_corrupt = crash_at - (fallback.restored_step or 0)
        relaunch.close()
        fallback.close()

        # ---- checkpointing steady state, with and without the fault
        # schedule: the SAME FaultTolerantTrainer cadence both times, so
        # the delta isolates fault-absorption cost from checkpoint cost
        def ft_lane(spec, tag):
            ctx = (faults.injected(spec, seed=1) if spec
                   else contextlib.nullcontext())
            with ctx:
                m = model()
                ftr = FaultTolerantTrainer(
                    m, os.path.join(work, "steady", tag), save_every=10)
                it2 = ArrayDataSetIterator(x, y, batch_size=16)
                m.fit(it2, epochs=1)            # compile + warm
                done = 0
                t0 = time.perf_counter()
                while done < steps:
                    for ds in it2:
                        ftr.fit_batch(ds)
                        done += 1
                        if done >= steps:
                            break
                rate = steps / (time.perf_counter() - t0)
                ftr.checkpointer.wait()
                ftr.close()
                return rate

        steady_ckpt = [ft_lane(None, f"clean{r}") for r in range(rounds)]
        steady_faulted = [ft_lane("data_io:3;ckpt_io:2", f"faulted{r}")
                          for r in range(rounds)]
    finally:
        shutil.rmtree(work, ignore_errors=True)
        faults.configure("")

    off = _stats(steady_off)
    armed = _stats(steady_armed)
    ckpt_stats = _stats(steady_ckpt)
    faulted = _stats(steady_faulted)
    return {
        "steps_per_lane": steps,
        "steady_off_steps_per_sec": off,
        "steady_armed_steps_per_sec": armed,
        "steady_ckpt_steps_per_sec": ckpt_stats,
        "steady_faulted_steps_per_sec": faulted,
        "armed_over_off": round(armed["median"] / max(off["median"], 1e-9),
                                4),
        "faulted_over_ckpt": round(
            faulted["median"] / max(ckpt_stats["median"], 1e-9), 4),
        "mttr_seconds": mttr,
        "steps_lost_per_crash": {
            "save_every": save_every,
            "crash_at_step": crash_at,
            "clean_resume": steps_lost,
            "corrupted_latest_resume": steps_lost_corrupt,
        },
        "note": "armed_over_off ~1.0 is the zero-overhead contract "
                "(spy-based tier-1 guard in tests/test_faults.py); the "
                "faulted lane absorbs 3 data_io + 2 ckpt_io retries on "
                "top of the identical checkpoint cadence",
    }


def bench_guardrails(steps=120, rounds=3):
    """Training-guardrails lane: what the numeric sentinel costs and what
    a trip costs to recover from.

    Lanes, one small MLN fit loop each (the sentinel is in-step device
    work plus host screening, so the small-model fit loop is the
    worst case for relative overhead):
      - ``off`` vs ``armed``: fit throughput unarmed vs armed-untripped
        (guarded train step + drain screening, checkpoint cadence pushed
        past the run). Acceptance: ``armed_over_off >= 0.97``;
      - NaN recovery: a seeded ``nan_grad`` trip driven down the full
        ladder (skip_budget=0, straight to rollback) — MTTR is the
        wall-clock of the recovering step minus the median clean step,
        steps_lost from the guardrail's own ledger;
      - bisection probes vs async window size: how blame attribution
        scales with the in-flight window the rollback has to replay.
    """
    import shutil
    import tempfile

    import numpy as np

    from deeplearning4j_tpu import faults, guardrails
    from deeplearning4j_tpu.common.env import env as _env
    from deeplearning4j_tpu.datasets import ArrayDataSetIterator
    from deeplearning4j_tpu.guardrails import GuardrailPolicy
    from deeplearning4j_tpu.nn import (
        InputType, MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd

    def model():
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Sgd(lr=0.05)).list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(16)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 64)]

    def fit_lane(armed, work=None):
        m = model()
        if armed:
            guardrails.arm(m, GuardrailPolicy(checkpoint_every=10_000),
                           checkpoint_dir=work)
        it = ArrayDataSetIterator(x, y, batch_size=16)
        m.fit(it, epochs=1)                     # compile + warm
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            for ds in it:
                m.fit_batch(ds)
                done += 1
                if done >= steps:
                    break
        rate = steps / (time.perf_counter() - t0)
        if armed:
            guardrails.disarm(m)
        return rate

    work = tempfile.mkdtemp(prefix="bench_guardrails_")
    try:
        faults.configure("")
        off = [fit_lane(False) for _ in range(rounds)]
        armed = [fit_lane(True, os.path.join(work, "armed"))
                 for _ in range(rounds)]

        # ---- NaN trip: MTTR + steps lost through the rollback rung ----
        trip_at, ckpt_every = 11, 5
        m = model()
        guard = guardrails.arm(
            m, GuardrailPolicy(skip_budget=0, clip_retry=False,
                               checkpoint_every=ckpt_every, warmup_steps=4),
            checkpoint_dir=os.path.join(work, "mttr"))
        it = ArrayDataSetIterator(x, y, batch_size=16)
        m.fit(it, epochs=1)                     # compile + warm
        faults.configure(f"nan_grad:1@step=={trip_at}", seed=0)
        clean_times, trip_time = [], None
        done = 0
        while trip_time is None:
            for ds in it:
                t0 = time.perf_counter()
                m.fit_batch(ds)
                dt = time.perf_counter() - t0
                if guard.rollbacks:
                    trip_time = dt
                    break
                clean_times.append(dt)
                done += 1
                if done > 200:                  # safety: should never hit
                    trip_time = float("nan")
                    break
        faults.configure("")
        clean_step = sorted(clean_times)[len(clean_times) // 2]
        mttr = max(0.0, trip_time - clean_step)
        nan_steps_lost = guard.steps_lost
        guardrails.disarm(m)

        # ---- bisection probe count vs async window size ----
        probes = {}
        for win in (1, 4, 8):
            os.environ["DL4J_TPU_ASYNC_STEPS"] = str(win)
            _env.reload()
            try:
                mw = model()
                gw = guardrails.arm(
                    mw, GuardrailPolicy(skip_budget=0, clip_retry=False,
                                        checkpoint_every=4, warmup_steps=4),
                    checkpoint_dir=os.path.join(work, f"bisect{win}"))
                itw = ArrayDataSetIterator(x, y, batch_size=16)
                faults.configure("nan_grad:1@step==9", seed=0)
                mw.fit(itw, epochs=5)
                probes[str(win)] = {
                    "bisect_probes": gw.last_bisect_probes,
                    "culprit": (gw.quarantined or [None])[0],
                }
                guardrails.disarm(mw)
            finally:
                faults.configure("")
                os.environ.pop("DL4J_TPU_ASYNC_STEPS", None)
                _env.reload()
    finally:
        shutil.rmtree(work, ignore_errors=True)
        faults.configure("")

    off_s, armed_s = _stats(off), _stats(armed)
    return {
        "steps_per_lane": steps,
        "off_steps_per_sec": off_s,
        "armed_steps_per_sec": armed_s,
        "armed_over_off": round(armed_s["median"] / max(off_s["median"],
                                                        1e-9), 4),
        "nan_recovery": {
            "checkpoint_every": ckpt_every,
            "trip_at_step": trip_at,
            "mttr_seconds": round(mttr, 4),
            "clean_step_seconds": round(clean_step, 5),
            "steps_lost": nan_steps_lost,
        },
        "bisect_probes_by_window": probes,
        "note": "armed_over_off >= 0.97 is the acceptance line: the "
                "sentinel rides the existing loss fetch, so armed-"
                "untripped overhead is one f32[4] word per step",
    }


def bench_pipeline(batch=256, n=2048, hw=256, crop=224, epochs=3):
    """Standalone sustained throughput of the native image input path
    (VERDICT r2 #3): staged uint8 [n, hw, hw, 3] -> threaded random-crop /
    flip / normalize -> float32 [batch, crop, crop, 3] batches. Measured on
    the bench HOST; the number to compare against the model's samples/sec
    (the pipeline must sustain at least the model rate to not be the
    bottleneck)."""
    import tempfile

    import numpy as np

    from deeplearning4j_tpu.native import NativeImageDataSetIterator
    from deeplearning4j_tpu.native.pipeline import write_image_dataset

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (n, hw, hw, 3), dtype=np.uint8)
    labels = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, n)]
    threads = max(4, (os.cpu_count() or 4) - 1)
    out = {"batch": batch, "shape": f"{hw}x{hw}x3->crop{crop}",
           "threads": threads}
    with tempfile.TemporaryDirectory() as d:
        img_path, label_path = write_image_dataset(d, imgs, labels)
        # f32: host-side normalize (DataVec behavior); u8: crop/flip only,
        # normalize on DEVICE (the shipping imagenet path — 4x less host
        # traffic, XLA fuses the affine into the first conv)
        for output in ("f32", "u8"):
            it = NativeImageDataSetIterator(
                img_path, label_path, n, (hw, hw, 3), 1000, batch,
                crop=(crop, crop), shuffle=True, augment=True,
                n_threads=threads, queue_cap=8, output=output)
            out["native"] = it.native
            rates = []
            for e in range(epochs):
                t0 = time.perf_counter()
                seen = 0
                for ds in it:
                    seen += ds.features.shape[0]
                dt = time.perf_counter() - t0
                if e > 0:                # epoch 0 warms the worker threads
                    rates.append(seen / dt)
                it.reset()
            it.close()
            out[f"samples_per_sec_{output}"] = _stats(rates)
    out["samples_per_sec"] = out["samples_per_sec_u8"]
    return out


def bench_dispatch(batch=256, epochs=4, budget_deadline=None):
    """A/B the fit loop's dispatch modes: {sync, async window} x {prefetch
    off, device prefetch on}. Reports samples/sec per cell, the async
    speedup over the fully-synchronous baseline (the ISSUE north-star
    claim), and the host-blocked fraction from the fit monitor's phase
    histograms — sync mode blocks the host for the whole device_step
    (the scalar fetch inside waits out the compute); async mode blocks
    only in drain."""
    import numpy as np

    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.common.env import env as _env
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        ArrayDataSetIterator, AsyncPrefetchIterator,
    )
    from deeplearning4j_tpu.nn import (
        InputType, MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd
    from deeplearning4j_tpu.optimize.async_dispatch import drain_scores

    hw = 32
    n_in = hw * hw * 3
    io_ms = 25.0

    class _EtlIterator(ArrayDataSetIterator):
        """DataVec-style host input path per batch: a storage/decode stall
        (GIL-released, like a real file read — simulated with a fixed
        latency so the A/B is deterministic) followed by uint8 -> float32
        normalize. This is the per-step host time the async window and the
        prefetch thread exist to overlap with device compute."""

        def __iter__(self):
            for ds in super().__iter__():
                time.sleep(io_ms / 1e3)
                f = np.asarray(ds.features, np.float32) * (1 / 127.5) - 1.0
                yield DataSet(f.reshape(len(f), n_in), ds.labels)

    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Sgd(lr=0.01)).list()
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(DenseLayer(n_out=1024, activation="relu"))
            .layer(OutputLayer(n_out=64, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(3)
    n = batch * 6
    x = rng.integers(0, 256, (n, hw, hw, 3), dtype=np.uint8)
    y = np.eye(64, dtype=np.float32)[rng.integers(0, 64, n)]
    warm = next(iter(_EtlIterator(x, y, batch_size=batch)))
    net.fit_batch(warm)                          # compile outside the timing
    drain_scores(net)

    saved = os.environ.get("DL4J_TPU_ASYNC_STEPS")
    out = {"batch": batch, "epochs": epochs, "steps_per_epoch": n // batch,
           "simulated_io_ms_per_batch": io_ms}
    try:
        for async_steps, prefetch in ((0, False), (0, True),
                                      (2, False), (2, True)):
            if budget_deadline and time.perf_counter() >= budget_deadline:
                break
            os.environ["DL4J_TPU_ASYNC_STEPS"] = str(async_steps)
            _env.reload()
            it = _EtlIterator(x, y, batch_size=batch)
            if prefetch:
                it = AsyncPrefetchIterator(it, queue_size=2)
            monitoring.reset()
            monitoring.enable()
            t0 = time.perf_counter()
            net.fit(it, epochs=epochs)
            wall = time.perf_counter() - t0
            reg = monitoring.registry()

            def _sum(name):
                try:
                    return reg.get(name).sum
                except Exception:
                    return 0.0

            blocked = (_sum("dl4j_train_device_step_seconds")
                       if async_steps == 0
                       else _sum("dl4j_train_drain_seconds"))
            key = (("async" if async_steps else "sync")
                   + ("+prefetch" if prefetch else ""))
            out[key] = {
                "samples_per_sec": round(epochs * n / wall, 1),
                "host_blocked_fraction": round(blocked / max(wall, 1e-9), 4),
            }
    finally:
        if saved is None:
            os.environ.pop("DL4J_TPU_ASYNC_STEPS", None)
        else:
            os.environ["DL4J_TPU_ASYNC_STEPS"] = saved
        _env.reload()
        monitoring.reset()
    if "sync" in out and "async+prefetch" in out:
        out["async_speedup"] = round(
            out["async+prefetch"]["samples_per_sec"]
            / max(out["sync"]["samples_per_sec"], 1e-9), 4)
    return out


def main():
    from deeplearning4j_tpu.monitoring.compile import configure_compile_cache

    _require_tpu()
    configure_compile_cache()
    # argv: [mode] [batch] — a bare number is a resnet50 batch (back-compat)
    mode, batch = "resnet50", None
    for a in sys.argv[1:3]:
        if a.isdigit():
            batch = int(a)
        else:
            mode = a
    rounds = int(os.environ.get("BENCH_ROUNDS", "3"))
    deadline = time.perf_counter() + float(
        os.environ.get("BENCH_DEADLINE_SECS", "520"))

    if mode == "longcontext":
        bench_longcontext(T=batch or 8192, rounds=rounds)
        return
    if mode == "pipeline":
        out = bench_pipeline(batch=batch or 256)
        print(json.dumps({
            "metric": "native image input pipeline sustained throughput "
                      "(host, %s, batch %d)" % (out["shape"], out["batch"]),
            "value": out["samples_per_sec"]["median"],
            "unit": "samples/sec",
            "vs_baseline": None,
            "dispersion": out["samples_per_sec"],
            "native": out["native"],
            "threads": out["threads"],
        }))
        return
    if mode == "dispatch":
        out = bench_dispatch(batch=batch or 256)
        print(json.dumps({
            "metric": "fit-loop dispatch A/B (sync vs async window x "
                      "prefetch off/on, batch %d)" % out["batch"],
            "value": out.get("async_speedup"),
            "unit": "x vs sync",
            "vs_baseline": None,
            "dispatch": out,
        }))
        return
    if mode == "nlp":
        t = bench_nlp(rounds=rounds)
        print(json.dumps({
            "metric": "streaming Word2Vec skip-gram+negative-sampling "
                      "throughput (file corpus, host/device split)",
            "value": t["end_to_end_words_per_sec"],
            "unit": "words/sec",
            "vs_baseline": None,
            "nlp": t,
        }))
        return
    if mode == "faults":
        t = bench_faults(rounds=rounds)
        print(json.dumps({
            "metric": "fault-injection recovery cost (steady fit "
                      "off/armed/faulted + MTTR per class + steps lost "
                      "per crash)",
            "value": t["faulted_over_ckpt"],
            "unit": "x of fault-free throughput",
            "vs_baseline": t["armed_over_off"],
            "faults": t,
        }))
        return
    if mode == "guardrails":
        t = bench_guardrails(rounds=rounds)
        print(json.dumps({
            "metric": "training-guardrails cost (armed-untripped fit "
                      "throughput vs off + NaN-trip MTTR/steps-lost + "
                      "bisection probes vs window)",
            "value": t["armed_over_off"],
            "unit": "x of unarmed throughput (acceptance >= 0.97)",
            "vs_baseline": t["nan_recovery"]["mttr_seconds"],
            "guardrails": t,
        }))
        return
    if mode == "serve":
        t = bench_serving()
        print(json.dumps({
            "metric": "ParallelInference serving lane (batching on vs "
                      "off vs direct)",
            "value": t["parallel_inference_batching_on"]["throughput_rps"],
            "unit": "requests/sec",
            "vs_baseline": t["batching_speedup_vs_off"],
            "serving": t,
        }))
        return
    if mode == "generate":
        t = bench_generate(budget_deadline=deadline)
        print(json.dumps({
            "metric": "continuous-batching generation engine "
                      "(mixed-length streams, one compiled decode step)",
            "value": t["continuous"]["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": t.get("continuous_speedup"),
            "generate": t,
        }))
        return
    if mode == "quantize":
        t = bench_quantize(budget_deadline=deadline)
        print(json.dumps({
            "metric": "int8 quantization A/B (weight-only predict + "
                      "int8-KV decode vs full precision)",
            "value": t["predict"].get("int8_speedup"),
            "unit": "x samples/sec vs bf16",
            "vs_baseline": t["predict"].get("bytes_reduction"),
            "quantize": t,
        }))
        return
    if mode == "serve_gateway":
        t = bench_serving_gateway()
        print(json.dumps({
            "metric": "ServingGateway lane (two-version 90/10 split, "
                      "warm buckets; steady + overload shed rate)",
            "value": t["steady"]["throughput_rps"],
            "unit": "requests/sec",
            "vs_baseline": None,
            "overload_shed_rate": t["overload"]["shed_rate"],
            "serving_gateway": t,
        }))
        return
    if mode == "chaos":
        t = bench_chaos()
        print(json.dumps({
            "metric": "multi-tenant chaos lane (worker crash + slow "
                      "worker + traffic spike vs per-class SLOs)",
            "value": t["chaos"]["interactive"]["p99_ms"],
            "unit": "ms interactive p99 under chaos",
            "vs_baseline": t["steady"]["interactive"]["p99_ms"],
            "acceptance": t["acceptance"],
            "chaos": t,
        }))
        return
    if mode == "bert_import":
        t = bench_bert_import(rounds=rounds)
        t["at_scale"] = bench_bert_import_at_scale(rounds=rounds)
        print(json.dumps({
            "metric": "BERT fine-tune via ONNX import -> as_trainable "
                      "(BASELINE config #4 as written) vs zoo-native twin",
            "value": t["imported_samples_per_sec"],
            "unit": "samples/sec/chip",
            "vs_baseline": t["ratio_imported_over_native"],
            "bert_import": t,
        }))
        return
    if mode == "kernels":
        table = bench_kernels(rounds=rounds, budget_deadline=deadline)
        speedups = [v["speedup"] for v in table.values()
                    if isinstance(v, dict) and "speedup" in v]
        gm = 1.0
        for s in speedups:
            gm *= s
        gm = gm ** (1.0 / max(1, len(speedups)))
        print(json.dumps({
            "metric": "Pallas kernel vs plain-XLA speedup table "
                      "(geometric mean of %d entries)" % len(speedups),
            "value": round(gm, 4),
            "unit": "x",
            "vs_baseline": None,
            "kernels": table,
        }))
        return
    if mode != "resnet50":
        defaults = {"lenet": 512, "lstm": 64, "bert": 32, "bert_long": 16}
        if mode not in defaults:
            raise SystemExit(
                f"unknown bench mode '{mode}' (expected resnet50|lenet|lstm|"
                f"bert|bert_long|bert_import|serve|serve_gateway|nlp|"
                f"longcontext|pipeline|kernels)")
        batch = batch or defaults[mode]
        fn, label = make_mode(mode, batch)
        runs = [fn() for _ in range(rounds)]
        # a SECOND measurement block in the same artifact: protocol drift
        # (the r1->r2 LSTM 3x mystery) becomes visible per-run, not
        # per-round
        runs2 = [fn() for _ in range(rounds)]
        st1, st2 = _stats(runs), _stats(runs2)
        out = {
            "metric": "%s (zoo entrypoint, batch %d, median of %d rounds)"
                      % (label, batch, rounds),
            "value": st1["median"],
            "unit": "samples/sec/chip",
            "vs_baseline": None,
            "dispersion": st1,
            "remeasure": st2,
        }
        if getattr(fn, "attention_path", None):
            out["attention_path"] = fn.attention_path
        print(json.dumps(out))
        return
    batch = batch or 256

    def run_rounds(b, fns=None):
        # interleave A/B rounds and report the median throughput and median
        # per-round ratio
        if fns is None:
            ours_fn = make_ours(b)
            # AOT-compile once up front; with the persistent cache enabled the
            # timed jit path below reuses this compile instead of repeating it
            ours_fn.flops_per_step()
            try:
                flax_fn = make_flax_reference(b)
            except Exception:
                flax_fn = None
        else:
            ours_fn, flax_fn = fns
        ours_runs, ratios = [], []
        for _ in range(rounds):
            o = ours_fn()
            ours_runs.append(o)
            if flax_fn is not None:
                try:
                    ratios.append(o / flax_fn())
                except Exception:
                    flax_fn = None  # keep reporting ours even if ref dies
        med = sorted(ours_runs)[len(ours_runs) // 2]
        vs = sorted(ratios)[len(ratios) // 2] if ratios else None
        return med, vs, ours_fn, (ours_runs, ratios, flax_fn)

    def peak_flops():
        import jax

        kind = jax.devices()[0].device_kind.lower()
        table = {"v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
                 "v6e": 918e12, "v6 lite": 918e12}
        for name, peak in table.items():
            if name in kind:
                return peak
        return None  # unknown device: report mfu=null, not a guess

    try:
        med, vs, ours_fn, extra = run_rounds(batch)
    except Exception:  # OOM during compile/execute: retry at half batch
        batch = batch // 2
        med, vs, ours_fn, extra = run_rounds(batch)

    # MFU: XLA-counted flops/step x steps/sec over chip peak (the BASELINE
    # metric is samples/sec/chip + MFU)
    mfu = None
    try:
        peak = peak_flops()
        flops = ours_fn.flops_per_step()
        if flops and peak:
            mfu = flops * (med / batch) / peak
    except Exception:
        mfu = None
    result = {
        "metric": "ResNet-50 ImageNet train throughput (zoo entrypoint, bf16, batch %d, median of %d interleaved rounds)" % (batch, rounds),
        "value": round(med, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": None if vs is None else round(vs, 4),
        "mfu": None if mfu is None else round(mfu, 4),
        "dispersion": _stats(extra[0]),
    }
    # Optional blocks, each within the bench deadline so the driver's
    # timeout can never lose the north-star line: bert_import (+ at-scale)
    # -> serving -> nlp -> quick lenet/lstm configs -> kernels table
    # (self-truncating) -> input pipeline -> remeasure.
    #
    # Per-lane deadline BUDGETING (r6): r05 skipped 6 of 11 lanes with
    # "deadline margin exhausted" because early lanes ran unbounded and
    # starved the tail. Each lane declares a minimum slice; a lane only
    # runs when the remaining budget covers its own minimum, and
    # deadline-aware lanes get a sub-deadline of (remaining - the sum of
    # the minimum slices still owed to later lanes), so no lane can eat
    # the reservations of the ones behind it. planned_vs_run records the
    # plan, what actually ran, and what was skipped.
    block_secs = {"north_star": round(time.perf_counter()
                                      - (deadline - float(
                                          os.environ.get(
                                              "BENCH_DEADLINE_SECS",
                                              "520"))), 1)}

    def nlp_quick():
        # one native-front fit (r5): the concurrent C++ host pipeline +
        # scanned device steps — a driver-captured words/sec datapoint
        # (the full host/device split lives in `bench.py nlp`)
        t = bench_nlp(rounds=1)
        return {"end_to_end_words_per_sec": t["end_to_end_words_per_sec"],
                "native_front_words_per_sec":
                    t["native_front_words_per_sec"],
                "python_front_words_per_sec":
                    t["python_front_words_per_sec"],
                "bottleneck": t["bottleneck"]}

    def quick_configs(sub_deadline):
        # single-round two-point lanes for the remaining BASELINE
        # configs (VERDICT r4 weak #4: their numbers were builder-run
        # only) — compile-cache-served, one round each
        out = {}
        for m, bsz in (("lenet", 512), ("lstm", 64)):
            if time.perf_counter() >= sub_deadline:
                break
            fn, _ = make_mode(m, bsz)
            out[m] = {"samples_per_sec": round(fn(), 1), "batch": bsz,
                      "rounds": 1}
        return out

    def pipe_block(sub_deadline):
        # the input path next to the model rate (host-side); n must
        # cover >= 1 batch or the rate reads as a bogus 0
        pipe = bench_pipeline(batch=batch, n=max(1024, 4 * batch), epochs=2)
        out = {"samples_per_sec": pipe["samples_per_sec"]["median"],
               "native": pipe["native"],
               "covers_model_rate":
                   pipe["samples_per_sec"]["median"] >= med}
        # dispatch A/B: sync vs async window x prefetch off/on, with
        # host-blocked fraction per cell (the per-step float(loss) cost
        # this PR removes, measured rather than asserted)
        out["dispatch"] = bench_dispatch(budget_deadline=sub_deadline)
        return out

    def remeasure_block(_):
        # remeasure with the SAME compiled fns: drift is visible
        med2, vs2, _, extra2 = run_rounds(batch, fns=(ours_fn, extra[2]))
        return dict(_stats(extra2[0]),
                    vs_baseline=None if vs2 is None else round(vs2, 4))

    # (name, min_secs, fn(sub_deadline), record_error). min_secs is the
    # slice reserved for the lane BEFORE it may start — the tail lanes'
    # minimums are subtracted from every earlier lane's sub-deadline.
    lanes = [
        ("bert_import", 75,
         lambda sd: bench_bert_import(rounds=rounds), True),
        ("bert_import_at_scale", 75,
         lambda sd: bench_bert_import_at_scale(rounds=rounds), True),
        ("serving", 50, lambda sd: bench_serving(), True),
        ("nlp", 60, lambda sd: nlp_quick(), True),
        ("generate", 50,
         lambda sd: bench_generate(budget_deadline=sd), True),
        ("quick_configs", 45, quick_configs, False),
        ("kernels", 60,
         lambda sd: bench_kernels(rounds=rounds, budget_deadline=sd), True),
        # reserved min-slice raised from 30 (r7): the lane was perpetually
        # "deadline margin exhausted" because it only ran on leftovers;
        # 75s matches bert_import's reservation and covers the dispatch A/B
        ("input_pipeline", 75, pipe_block, True),
        ("quantize", 50,
         lambda sd: bench_quantize(budget_deadline=sd), True),
        ("remeasure", 30, remeasure_block, False),
    ]
    # rotate the starting lane by the cursor persisted in the previous
    # run's artifact, so deadline starvation lands on a different tail
    # each run; every lane still keeps its own min-slice reservation
    cursor = _lane_cursor() % len(lanes)
    lanes = lanes[cursor:] + lanes[:cursor]
    planned = [name for name, _, _, _ in lanes]
    ran, skipped = [], {}
    for idx, (name, min_secs, fn, record_error) in enumerate(lanes):
        now = time.perf_counter()
        remaining = deadline - now
        if remaining < min_secs:
            result[name] = {"skipped": "deadline margin exhausted"}
            skipped[name] = round(remaining, 1)
            continue
        tail_min = sum(l[1] for l in lanes[idx + 1:])
        sub_deadline = now + max(min_secs, remaining - tail_min)
        t0 = time.perf_counter()
        try:
            result[name] = fn(sub_deadline)
            ran.append(name)
        except Exception as e:
            if record_error:
                result[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        block_secs[name] = round(time.perf_counter() - t0, 1)

    result["block_secs"] = block_secs
    result["planned_vs_run"] = {
        "planned": planned, "ran": ran, "skipped": skipped,
        "lane_min_secs": {name: m for name, m, _, _ in lanes}}
    result["lane_rotation"] = {
        "cursor": cursor,
        "next_cursor": (cursor + 1) % len(lanes),
        "order": planned}
    print(json.dumps(result))


if __name__ == "__main__":
    main()
