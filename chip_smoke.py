"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, on whatever TPU machine it is started on:

- *train*: ``zoo.ResNet50`` at full width (224x224x1000, bf16, batch 256 per
  chip) through ``ComputationGraph.fit`` — through ``ParallelWrapper`` when
  there is more than one device;
- *kernels*: every Pallas kernel picked by the op registry at a shape real
  configurations use, compiled by Mosaic and value-checked against its XLA
  lowering; the BiLSTM char-RNN takes the fused LSTM through a layer;
- *serve*: ``zoo.TextGenerationLSTM`` behind ``GenerationEngine`` and
  ``ServingGateway``, streaming to HTTP client threads;
- *ring* (more than one device): ring attention with the flash core.

Weights are random from a seed; widths are real. Any failure raises and the
process exits non-zero. There is no CPU fallback and no switch to allow
one: not on a TPU means exit before any work. The timings printed are
set-up facts (compile seconds, steady step seconds), not a benchmark.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import concurrent.futures
import http.client
import importlib.metadata
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncPrefetchIterator, ListDataSetIterator,
)
from deeplearning4j_tpu.generation import GenerationEngine
from deeplearning4j_tpu.monitoring.compile import configure_compile_cache
from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import get_op
from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
from deeplearning4j_tpu.parallel.sequence import ring_attention
from deeplearning4j_tpu.serving import ServingGateway
from deeplearning4j_tpu.zoo import (
    BidirectionalGravesLSTMCharRnn, ResNet50, TextGenerationLSTM,
)


# --------------------------------------------------------------------- device
def device_stamp() -> dict:
    """The device as JAX reports it — the stamp every result carries."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_memory() -> list:
    """Per-device memory, read straight from PJRT. A backend that does not
    report it (XLA:CPU returns None) is an error here, not an empty dict."""
    return [{"id": d.id, **d.memory_stats()} for d in jax.devices()]


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self)

    def __call__(self, event: str, **_):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def take(self) -> dict:
        """The counts since the last call."""
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def _finite(values, what: str):
    if not all(math.isfinite(v) for v in values):
        raise AssertionError(f"{what}: non-finite value in {values}")


# ---------------------------------------------------------------------- train
def train_leg(*, batch_per_device: int = 256, height: int = 224,
              width: int = 224, num_classes: int = 1000, n_batches: int = 4,
              steady_steps: int = 8, seed: int = 0) -> dict:
    """The north-star path: ResNet-50 bf16 through ``fit`` with the default
    async window, one warm-up (compiling) step then ``steady_steps`` steps
    over ``n_batches`` distinct seeded host batches. With n > 1 devices the
    same fit runs under ``ParallelWrapper`` at global batch
    ``batch_per_device * n``."""
    devices = jax.devices()
    n = len(devices)
    batch = batch_per_device * n
    rng = np.random.default_rng(seed)
    eye = np.eye(num_classes, dtype=np.float32)
    batches = [
        DataSet(rng.standard_normal((batch, height, width, 3),
                                    dtype=np.float32).astype(jnp.bfloat16),
                eye[rng.integers(0, num_classes, batch)])
        for _ in range(n_batches)]

    model = ResNet50(height=height, width=width, num_classes=num_classes,
                     dtype="bf16").init()
    scores = CollectScoresListener()
    model.set_listeners(scores)
    leaves = jax.tree_util.tree_leaves(model.params)
    # host copies: the step donates its param buffers
    before = [np.array(leaves[0]), np.array(leaves[-1])]

    out = {"model": f"ResNet50 {height}x{width}x{num_classes} bf16",
           "global_batch": batch}
    if n > 1:
        wrapper = ParallelWrapper(model, DeviceMesh())
        x = wrapper.mesh.shard_batch(batches[0].features)
        shard_devices = {s.device for s in x.addressable_shards}
        if len(x.addressable_shards) != n or shard_devices != set(devices):
            raise AssertionError(
                f"batch sharded onto {sorted(d.id for d in shard_devices)}, "
                f"expected one shard on each of {n} devices")
        out["batch_shards"] = len(x.addressable_shards)
        # the wrapper stages batches on its own prefetch thread
        fit, stage = wrapper.fit, ListDataSetIterator
    else:
        fit = model.fit

        def stage(batches):
            return AsyncPrefetchIterator(ListDataSetIterator(batches))

    t0 = time.perf_counter()
    fit(stage(batches[:1]))
    jax.block_until_ready(model.params)
    out["compile_and_first_step_s"] = round(time.perf_counter() - t0, 2)

    epochs = -(-steady_steps // n_batches)
    t0 = time.perf_counter()
    fit(stage(batches), epochs=epochs)
    jax.block_until_ready(model.params)
    steps = epochs * n_batches
    out["steady_steps"] = steps
    out["steady_step_s"] = round((time.perf_counter() - t0) / steps, 4)

    losses = [s for _, s in scores.scores]
    if len(losses) != 1 + steps:
        raise AssertionError(f"{len(losses)} losses for {1 + steps} steps")
    _finite(losses, "train losses")
    leaves = jax.tree_util.tree_leaves(model.params)
    after = [np.array(leaves[0]), np.array(leaves[-1])]
    if any(np.array_equal(a, b) for a, b in zip(before, after)):
        raise AssertionError("parameters did not change over the fit")
    _finite([float(np.abs(a).sum()) for a in after], "parameters")
    programs = model._jit_cache["train"]._cache_size()
    if programs != 1:
        raise AssertionError(f"{programs} train-step programs, expected 1")
    out["train_step_programs"] = programs
    out["losses"] = [round(l, 4) for l in losses]
    return out


# -------------------------------------------------------------------- kernels
def max_rel_err(a, b) -> float:
    """max |a - b| / max|b| across the (possibly multi-array) outputs."""
    worst = 0.0
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        xa = np.asarray(jax.device_get(la), np.float32)
        xb = np.asarray(jax.device_get(lb), np.float32)
        denom = max(float(np.max(np.abs(xb))), 1e-6)
        worst = max(worst, float(np.max(np.abs(xa - xb))) / denom)
    return worst


def kernel_cases(*, flash_t: int = 2048, long_t: int = 8192,
                 long_heads: int = 4, rnn_batch: int = 8, rnn_t: int = 4,
                 rnn_hidden: int = 256, blocked_batch: int = 256,
                 blocked_hidden: int = 1024, lrn_shape=(4, 32, 32, 64),
                 seed: int = 0):
    """Yield ``(label, op_name, args, kwargs, body, rel_tol)`` per kernel.

    ``args``/``kwargs`` are what the registry selects on; ``body(fn, *args)``
    runs the case with ``fn`` either the registry op (which must pick the
    Pallas kernel) or the op's XLA lowering — identical math, different
    engine. bf16 flash rows tolerate ~3e-2 (accumulation-order differences
    in half precision); f32 RNN/LRN rows sit at 1e-3/1e-4."""
    rng = np.random.default_rng(seed)

    def r(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.1,
                           dtype=dtype)

    def attn_fwd(**kw):
        return lambda fn, q, k, v: fn(q, k, v, **kw).astype(jnp.float32)

    def attn_bwd(**kw):
        return lambda fn, q, k, v: jax.grad(
            lambda *qkv: fn(*qkv, **kw).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    def qkv(heads, t, d):
        return tuple(r(1, heads, t, d, dtype=jnp.bfloat16) for _ in range(3))

    d64, d128 = qkv(1, flash_t, 64), qkv(1, flash_t, 128)
    km = {"mask": jnp.ones((1, 1, 1, flash_t), jnp.float32)}
    causal = {"causal": True}
    attn = "dot_product_attention"
    yield "flash_fwd_d64", attn, d64, {}, attn_fwd(), 3e-2
    yield "flash_fwd_d128_causal", attn, d128, causal, attn_fwd(**causal), 3e-2
    yield "flash_fwd_masked", attn, d64, km, attn_fwd(**km), 3e-2
    yield "flash_bwd_d64", attn, d64, {}, attn_bwd(), 3e-2
    yield "flash_bwd_masked", attn, d64, km, attn_bwd(**km), 3e-2
    # the long-context training shape (ROADMAP B0): causal, T=8192, D=128 —
    # the 512x1024 fwd and VMEM-planned 1024x1024 bwd tiles at full size
    long = qkv(long_heads, long_t, 128)
    yield "flash_fwd_long_causal", attn, long, causal, attn_fwd(**causal), 3e-2
    yield "flash_bwd_long_causal", attn, long, causal, attn_bwd(**causal), 3e-2

    def rnn_fwd(fn, *a):
        return fn(*a)[0]

    def rnn_bwd(wi):
        return lambda fn, *a: jax.grad(
            lambda W: fn(*a[:wi], W, *a[wi + 1:])[0].sum())(a[wi])

    def lstm_args(B, T, F, H):
        z = jnp.zeros((B, H))
        return (r(B, T, F), z, z, r(F, 4 * H), r(H, 4 * H),
                jnp.zeros((4 * H,)))

    def gru_args(B, T, F, H):
        return (r(B, T, F), jnp.zeros((B, H)), r(F, 3 * H), r(H, 3 * H),
                jnp.zeros((3 * H,)))

    la = lstm_args(rnn_batch, rnn_t, 32, rnn_hidden)
    yield "lstm_fwd", "lstm_layer", la, {}, rnn_fwd, 1e-3
    yield "lstm_bwd", "lstm_layer", la, {}, rnn_bwd(3), 1e-3
    ga = gru_args(rnn_batch, rnn_t, 32, rnn_hidden)
    yield "gru_fwd", "gru_layer", ga, {}, rnn_fwd, 1e-3
    yield "gru_bwd", "gru_layer", ga, {}, rnn_bwd(2), 1e-3
    # batch-blocked plans (nb > 1): at B=256/H=1024 the fwd runs resident
    # batch blocks and the bwd the (64, 512) grid; T=2 keeps it quick
    ba = lstm_args(blocked_batch, 2, 64, blocked_hidden)
    yield "lstm_fwd_batchblocked", "lstm_layer", ba, {}, rnn_fwd, 1e-3
    yield "lstm_bwd_batchblocked", "lstm_layer", ba, {}, rnn_bwd(3), 1e-3
    bg = gru_args(blocked_batch, 2, 64, blocked_hidden)
    yield "gru_fwd_batchblocked", "gru_layer", bg, {}, rnn_fwd, 1e-3
    yield "gru_bwd_batchblocked", "gru_layer", bg, {}, rnn_bwd(2), 1e-3

    xl = (r(*lrn_shape),)
    yield "lrn_fwd", "lrn", xl, {}, lambda fn, x: fn(x), 1e-4
    yield ("lrn_bwd", "lrn", xl, {},
           lambda fn, x: jax.grad(lambda a: (fn(a) ** 2).sum())(x), 1e-4)


def kernel_leg(**sizes) -> dict:
    """Compile every Pallas kernel through the registry and value-check it
    against its XLA lowering at the same shape. A case the registry does not
    route to Pallas, that fails to compile, or that misses its tolerance
    fails the run."""
    out = {}
    for label, name, args, kwargs, body, tol in kernel_cases(**sizes):
        op = get_op(name)
        picked = op.select(*args, **kwargs).platform
        if picked != "pallas":
            raise AssertionError(
                f"{label}: registry picks '{picked}' for op '{name}', "
                f"expected the Pallas kernel")
        t0 = time.perf_counter()
        kernel = jax.jit(lambda *a: body(op, *a)).lower(*args).compile()
        compile_s = round(time.perf_counter() - t0, 2)
        # the SAME compiled executable runs the value check (a bare jit
        # re-dispatch would compile a second time)
        err = max_rel_err(kernel(*args),
                          jax.jit(lambda *a: body(op.xla.fn, *a))(*args))
        if not err <= tol:
            raise AssertionError(
                f"{label}: max rel err {err:.3g} vs XLA exceeds {tol}")
        out[label] = {"compile_s": compile_s,
                      "max_rel_err": float(f"{err:.3g}"), "tol": tol}
    # the comparator must be able to FAIL: a deliberately perturbed output
    # (+1e-3 on every element) has to exceed the tightest tolerance, or the
    # verdicts above are meaningless
    base = jnp.asarray(np.random.default_rng(1).normal(size=(128, 128)),
                       jnp.float32)
    if not max_rel_err(base + 1e-3, base) > 1e-4:
        raise AssertionError("comparator cannot detect a 1e-3 perturbation")
    return out


def charrnn_leg(*, batch: int = 64, steps: int = 3, seed: int = 0,
                **model_kw) -> dict:
    """BASELINE config #3: a few ``MultiLayerNetwork.fit`` steps of the
    bidirectional Graves (peephole) LSTM char-RNN at its defaults (H=200,
    padded to 256 lanes) — the fused LSTM fwd+bwd reached through a layer.
    The reference is the same fit with every op on its XLA lowering."""
    zoo = BidirectionalGravesLSTMCharRnn(**model_kw)
    T, V, H = zoo.timesteps, zoo.vocab_size, zoo.units
    rng = np.random.default_rng(seed)
    eye = np.eye(V, dtype=np.float32)
    batches = [DataSet(eye[rng.integers(0, V, (batch, T))],
                       eye[rng.integers(0, V, (batch, T))])
               for _ in range(steps)]

    def fit_losses():
        model = zoo.init()
        scores = CollectScoresListener()
        model.set_listeners(scores)
        model.fit(ListDataSetIterator(batches))
        jax.block_until_ready(model.params)
        return model, [s for _, s in scores.scores]

    model, losses = fit_losses()
    p = model.params[0]["fwd"]
    x = jax.ShapeDtypeStruct((batch, T, V), jnp.float32)
    h = jax.ShapeDtypeStruct((batch, H), jnp.float32)
    picked = get_op("lstm_layer").select(
        x, h, h, p["W"], p["RW"], p["b"], peephole=p["pW"]).platform
    if picked != "pallas":
        raise AssertionError(
            f"char-RNN layer shape B={batch} T={T} H={H}: registry picks "
            f"'{picked}', expected the fused LSTM kernel")
    was = env.disable_pallas
    env.disable_pallas = True          # the registry's XLA side of the A/B
    try:
        _, ref = fit_losses()
    finally:
        env.disable_pallas = was
    _finite(losses, "char-RNN losses")
    err = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
    if len(losses) != steps or not err <= 5e-3:
        raise AssertionError(
            f"char-RNN losses {losses} vs XLA {ref}: rel err {err:.3g}")
    return {"model": f"BiGravesLSTM char-RNN B={batch} T={T} H={H}",
            "losses": [round(l, 5) for l in losses],
            "max_rel_err_vs_xla": float(f"{err:.3g}")}


# ---------------------------------------------------------------------- serve
def serve_leg(*, n_requests: int = 8, slots: int = 8, max_len: int = 256,
              max_prompt: int = 48, max_new: int = 32, seed: int = 0,
              **model_kw) -> dict:
    """``TextGenerationLSTM`` behind ``GenerationEngine`` and the gateway on
    an ephemeral port; client threads stream ``POST /v1/charlm/generate``
    with mixed prompt lengths. Every stream must return the tokens asked
    for, the first must equal the engine's own answer for the same request,
    and the whole run must replay ONE decode program."""
    zoo = TextGenerationLSTM(**model_kw)
    net = zoo.init()
    rng = np.random.default_rng(seed)
    requests = [
        {"prompt_ids": rng.integers(0, zoo.vocab_size,
                                    int(rng.integers(1, max_prompt))).tolist(),
         "max_new_tokens": int(rng.integers(4, max_new)), "seed": i,
         # request 0 is greedy: it is checked against the engine directly
         "temperature": 0.8 if i else 0.0, "top_k": 40 if i else 0}
        for i in range(n_requests)]

    engine = GenerationEngine(net, slots=slots, max_len=max_len)
    gateway = ServingGateway(port=0).start()
    try:
        gateway.register_generator("charlm", engine)

        def stream(payload):
            conn = http.client.HTTPConnection("127.0.0.1", gateway.port,
                                              timeout=600)
            try:
                conn.request("POST", "/v1/charlm/generate",
                             json.dumps(payload).encode(),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                lines = [json.loads(l) for l in resp if l.strip()]
            finally:
                conn.close()
            if resp.status != 200 or not lines[-1].get("done"):
                raise AssertionError(f"HTTP {resp.status}: {lines[-1:]}")
            return [l["token"] for l in lines[:-1]]

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(n_requests) as pool:
            futures = [pool.submit(stream, p) for p in requests]
            streams = [f.result(timeout=900) for f in futures]
        wall_s = time.perf_counter() - t0
        for req, toks in zip(requests, streams):
            if len(toks) != req["max_new_tokens"]:
                raise AssertionError(
                    f"stream returned {len(toks)} tokens, asked for "
                    f"{req['max_new_tokens']}")
            if not all(0 <= t < zoo.vocab_size for t in toks):
                raise AssertionError(f"token outside the vocabulary: {toks}")
        first = requests[0]
        direct = engine.generate(first["prompt_ids"],
                                 max_new_tokens=first["max_new_tokens"],
                                 seed=first["seed"])
        if streams[0] != direct:
            raise AssertionError(
                f"HTTP stream {streams[0]} != engine.generate {direct}")
        programs = engine.decode_programs
        if programs != 1:
            raise AssertionError(f"{programs} decode programs, expected 1")
    finally:
        gateway.stop(drain=True, timeout=30.0)
    return {"model": f"TextGenerationLSTM {zoo.units}x2 vocab "
                     f"{zoo.vocab_size}",
            "requests": n_requests,
            "prompt_lens": [len(r["prompt_ids"]) for r in requests],
            "tokens": sum(len(t) for t in streams),
            "decode_programs": programs,
            "prefill_programs": engine.prefill_programs,
            "decode_steps": engine.steps_run,
            "wall_s_incl_compile": round(wall_s, 2)}


# ----------------------------------------------------------------------- ring
def ring_leg(*, t_local: int = 2048, heads: int = 2, head_dim: int = 128,
             seed: int = 0) -> dict:
    """Ring attention fwd+bwd over ``seq = n`` devices with the flash core —
    a Pallas call inside ``shard_map`` on physical devices, K/V rotating over
    the interconnect — against single-device XLA attention on the full
    sequence."""
    n = len(jax.devices())
    mesh = DeviceMesh(data=1, seq=n).mesh
    T = t_local * n
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(1, heads, T, head_dim)) * 0.1,
                           jnp.bfloat16) for _ in range(3))
    xla_attention = get_op("dot_product_attention").xla.fn

    def loss(attn):
        return lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum()

    def both(attn):
        return jax.jit(lambda q, k, v: (
            attn(q, k, v), jax.grad(loss(attn), argnums=(0, 1, 2))(q, k, v)))

    t0 = time.perf_counter()
    ring = both(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, impl="flash")).lower(q, k, v).compile()
    compile_s = round(time.perf_counter() - t0, 2)
    got = ring(q, k, v)
    want = both(lambda q, k, v: xla_attention(q, k, v, causal=True))(q, k, v)
    errs = {"out": max_rel_err(got[0], want[0]),
            **{f"d{name}": max_rel_err(g, w)
               for name, g, w in zip("qkv", got[1], want[1])}}
    _finite(errs.values(), "ring attention errors")
    if max(errs.values()) > 3e-2:
        raise AssertionError(f"ring attention vs XLA: {errs}")
    out_devices = {s.device for s in got[0].addressable_shards}
    if len(out_devices) != n:
        raise AssertionError(f"ring output lives on {len(out_devices)} of "
                             f"{n} devices")
    return {"seq_devices": n, "T": T, "t_local": t_local,
            "head_dim": head_dim, "compile_s": compile_s,
            "max_rel_err": {k: float(f"{e:.3g}") for k, e in errs.items()}}


# ----------------------------------------------------------------------- main
def main() -> None:
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform '{stamp['platform']}' "
            f"({stamp['kind']} x{stamp['count']}); there is no CPU fallback")
    if interpret_mode():
        raise SystemExit("chip_smoke: Pallas interpret mode is on, on a TPU")
    cache_dir = configure_compile_cache()
    events = CacheEvents()

    def report(leg: str, result: dict):
        result["compile_cache"] = events.take()
        print(f"{leg}: {json.dumps(result)}", flush=True)

    print("gate: " + json.dumps({
        **stamp, "jax": jax.__version__,
        "jaxlib": importlib.metadata.version("jaxlib"),
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache_dir": cache_dir}), flush=True)

    train = train_leg()
    memory = device_memory()
    if not all(m["bytes_in_use"] > 0 for m in memory):
        raise AssertionError(f"a device holds no bytes after the fit: {memory}")
    train["peak_bytes_in_use"] = max(m["peak_bytes_in_use"] for m in memory)
    train["devices_in_use"] = len(memory)
    train["memory_stats_device0"] = memory[0]
    report("train", train)
    report("kernels", kernel_leg())
    report("charrnn", charrnn_leg())
    report("serve", serve_leg())
    if stamp["count"] > 1:
        report("ring", ring_leg())

    print(json.dumps({"ok": True, "device": stamp}), flush=True)


if __name__ == "__main__":
    main()
