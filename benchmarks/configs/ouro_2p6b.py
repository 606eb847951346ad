"""Ouro-2.6B, a looped decoder language model trained on every pass's exit:
the plain float32 reference and the analytic operation and byte counts.

Imports nothing of the program. The equations (``ouro_2p6b.json`` lists what
the published ``config.json`` leaves to the family's paper, arXiv:2510.25741):

- ``h0 = E[x]``; a block, on ``h``: ``a = Attn(N1(h))``, ``h' = h + N2(a)``,
  ``m = MLP(N3(h'))``, ``h'' = h' + N4(m)``, each ``N`` an RMSNorm with its
  own gain; ``Attn``: q, k, v by ``Wq, Wk, Wv`` (no bias), heads of
  ``head_dim``, rotary positions on q and k (rotate-half pairing, float32
  angles), causal ``softmax(q k^T / sqrt(head_dim)) v``, ``Wo``;
  ``MLP(u) = (silu(u Wg) * (u Wu)) Wd``.
- the loop: for t = 1..``total_ut_steps``, ``z_t = Nf(Stack(z_{t-1}))``,
  ``z_0 = h0``: all held layers in order, then the one final RMSNorm; the
  normed state feeds the next pass. ``logits_t = z_t Wh``, the gate
  ``g_t = sigmoid(z_t . wg + bg)``.
- a position leaves at pass t with ``p_t = g_t prod_{j<t} (1 - g_j)`` (the
  last pass takes what is left); its loss is ``sum_t p_t CE(logits_t, y) -
  beta H(p)``; summed over a row's positions, averaged over the rows.

The parameter tree is keyed as the program keys it: the token table
``{"W"}``, the looped stack ``{"0": block, ..., "norm": {"gamma"}}`` (each
held layer once), the exits ``{"W", "Wg", "bg"}``; the state is the exits'
``exit_share``, each pass's mean exit probability over the step's positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference_train import precision_policy

HIGHEST = lax.Precision.HIGHEST
BLOCK_MATRICES = ("Wq", "Wk", "Wv", "Wo", "Wg", "Wu", "Wd")
BLOCK_GAINS = ("n1_g", "n2_g", "n3_g", "n4_g")


def _sizes(cfg: dict) -> tuple:
    d, heads, dh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    return d, heads * dh, cfg["intermediate_size"], cfg["vocab_size"]


def make_params(key, cfg: dict):
    """(params, state) in float32 from one key: matrices and the token table
    normal(0, ``initializer_range``), gains 1, the gate's bias 0."""
    d, a, ff, vocab = _sizes(cfg)
    n, std = cfg["num_hidden_layers"], cfg["initializer_range"]
    keys = iter(jax.random.split(key, 3 + len(BLOCK_MATRICES) * n))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    shapes = {"Wq": (d, a), "Wk": (d, a), "Wv": (d, a), "Wo": (a, d),
              "Wg": (d, ff), "Wu": (d, ff), "Wd": (ff, d)}
    stack = {}
    for i in range(n):
        block = {k: normal(shapes[k]) for k in BLOCK_MATRICES}
        block.update({g: jnp.ones((d,), jnp.float32) for g in BLOCK_GAINS})
        stack[str(i)] = block
    stack["norm"] = {"gamma": jnp.ones((d,), jnp.float32)}
    params = [{"W": normal((vocab, d))}, stack,
              {"W": normal((d, vocab)), "Wg": normal((d,)), "bg": jnp.zeros((1,), jnp.float32)}]
    state = [{}, {}, {"exit_share": jnp.zeros((cfg["total_ut_steps"],), jnp.float32)}]
    return params, state


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def rotary_angles(positions: int, head_dim: int, theta: float):
    """``[positions, head_dim / 2]`` angles in float32: position x theta^(-2i/head_dim)."""
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    return jnp.arange(positions, dtype=jnp.float32)[:, None] * theta ** (-2.0 * i / head_dim)


def rotate(t, angles):
    """Rotary positions on ``t`` ``[..., positions, head_dim]``, rotate-half
    pairing: feature i with feature i + head_dim / 2, turned by the angle of i."""
    half = t.shape[-1] // 2
    first, second = t[..., :half].astype(jnp.float32), t[..., half:].astype(jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1).astype(t.dtype)


def block(x, p, cfg: dict, angles, product=lambda f: f, qa=lambda x: x):
    """One decoder block over one row ``[seq, hidden]``; one head's scores at
    a time, and kept for the backward pass for one head at a time."""
    def mm(a, w):
        return product(lambda a, w: jnp.dot(a, w, precision=HIGHEST))(a, w)

    t = x.shape[0]
    heads, dh, eps = cfg["num_attention_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    h = _rms_norm(x, p["n1_g"], eps)

    def split(a):
        return a.reshape(t, heads, dh).transpose(1, 0, 2)

    q, k, v = rotate(split(mm(h, p["Wq"])), angles), rotate(split(mm(h, p["Wk"])), angles), \
        split(mm(h, p["Wv"]))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv
        scores = product(lambda a, b: jnp.einsum("td,sd->ts", a, b, precision=HIGHEST))(
            qh, kh) / dh ** 0.5
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1)
        return product(lambda a, b: jnp.einsum("ts,sd->td", a, b, precision=HIGHEST))(probs, vh)

    ctx = lax.map(one_head, (q, k, v)).transpose(1, 0, 2).reshape(t, heads * dh)
    x = qa(x + _rms_norm(mm(ctx, p["Wo"]), p["n2_g"], eps))
    h = _rms_norm(x, p["n3_g"], eps)
    m = mm(jax.nn.silu(mm(h, p["Wg"])) * mm(h, p["Wu"]), p["Wd"])
    return qa(x + _rms_norm(m, p["n4_g"], eps))


def exit_probabilities(gate_logits):
    """(p, log p), each ``[passes, ...]`` in float32, from the gates' logits:
    ``p_t = g_t prod_{j<t} (1 - g_j)``, the last pass taking what is left."""
    gate_logits = gate_logits.astype(jnp.float32)
    passes, stayed, log_p = gate_logits.shape[0], 0.0, []
    for t in range(passes):
        last = t == passes - 1
        log_p.append(stayed + (0.0 if last else jax.nn.log_sigmoid(gate_logits[t])))
        stayed = stayed + jax.nn.log_sigmoid(-gate_logits[t])
    log_p = jnp.stack([jnp.broadcast_to(l, gate_logits.shape[1:]) for l in log_p])
    return jnp.exp(log_p), log_p


def position_loss(ce, gate_logits, beta: float):
    """``sum_t p_t CE_t - beta H(p)`` for every position; ``ce`` and
    ``gate_logits`` ``[passes, ...]``. Also p."""
    p, log_p = exit_probabilities(gate_logits)
    entropy = -(p * log_p).sum(0)
    return (p * ce).sum(0) - beta * entropy, p


def loss_fn(params, state, features, labels, cfg: dict, precision: str = "float32"):
    """The loss over every pass's exit, summed over a row's positions and
    averaged over the rows, and the exits' new state. One row at a time;
    inside it the passes are a scan, one block's input kept for the backward
    pass at a time, and one pass's head at a time. ``precision``:
    ``reference_train.precision_policy``."""
    cast, product, qa = precision_policy(precision)
    params = cast(params)
    table, stack, exits = params
    n, passes, eps = cfg["num_hidden_layers"], cfg["total_ut_steps"], cfg["rms_norm_eps"]
    angles = rotary_angles(features.shape[1], cfg["head_dim"], cfg["rope_theta"])
    one_block = jax.checkpoint(lambda x, p: block(x, p, cfg, angles, product, qa))

    @jax.checkpoint
    def row_loss(tokens, targets):
        def one_pass(z, _):
            for i in range(n):
                z = one_block(z, stack[str(i)])
            z = qa(_rms_norm(z, stack["norm"]["gamma"], eps))
            return z, z

        _, states = lax.scan(one_pass, qa(table["W"][tokens]), None, length=passes)

        @jax.checkpoint
        def one_exit(z):
            logits = product(lambda a, b: jnp.dot(a, b, precision=HIGHEST))(z, exits["W"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ce = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
            gate = jnp.dot(z.astype(jnp.float32), exits["Wg"].astype(jnp.float32),
                           precision=HIGHEST) + exits["bg"].astype(jnp.float32)
            return ce, gate

        per_position, p = position_loss(*lax.map(one_exit, states), cfg["exit_entropy_beta"])
        return per_position.sum(), p.mean(axis=1)

    rows, shares = lax.map(lambda row: row_loss(*row),
                           (features.astype(jnp.int32), labels.astype(jnp.int32)))
    new_state = [state[0], state[1], {"exit_share": lax.stop_gradient(shares.mean(axis=0))}]
    return rows.mean(), new_state


# ---------------------------------------------------------- analytic counts
def _dot_macs_per_token(cfg: dict) -> float:
    """Multiply-accumulates a token, forward, of the products XLA runs: the
    projections and the gated MLP of every layer application, and every
    pass's head and gate."""
    d, a, ff, vocab = _sizes(cfg)
    applications = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    return applications * (4 * d * a + 3 * d * ff) + cfg["total_ut_steps"] * (d * vocab + d)


def dot_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs in the products XLA
    itself runs as ``dot_general`` (3 x 2 x the multiply-accumulates; no
    recomputation counted). Attention's products run in the flash kernel at
    the cell's ``seq`` and are ``attention_flops_per_sample``'s.
    ``conv_dot_roofline`` reads it."""
    return 3 * 2 * float(traffic["seq"] * _dot_macs_per_token(cfg))


def attention_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The attention kernel's operations one sample needs, forward + backward:
    2 products forward (q k^T, p v) and 5 backward (the scores again, dv, dp,
    dq, dk: the kernel's algorithm, whatever ``remat`` adds on top is not
    counted) of ``heads x seq^2 x head_dim`` multiply-accumulates each, halved
    for the causal mask, for every layer application."""
    seq, a = traffic["seq"], cfg["num_attention_heads"] * cfg["head_dim"]
    applications = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    return float(applications * (2 + 5) * 2 * seq * seq * a / 2)


def attention_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the kernel moves for one sample: q, k, v, o and their
    four gradients, each read or written once per layer application, in the
    configuration's compute type."""
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    applications = cfg["num_hidden_layers"] * cfg["total_ut_steps"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    return float(applications * 8 * traffic["seq"] * a * itemsize)


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs, whatever runs them
    (the embedding's gather has none; ``remat``'s recomputation and the
    optimizer do not count). ``step_mfu_pct`` reads it."""
    return dot_flops_per_sample(cfg, traffic) + attention_flops_per_sample(cfg, traffic)
