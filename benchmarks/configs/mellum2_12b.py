"""Mellum2-12B-A2.5B, a sparse-expert decoder language model with grouped
key-value heads and window and full attention mixed: the plain float32
reference of one chip's share and the analytic operation and byte counts.

Imports nothing of the program. The equations (``mellum2_12b.json`` lists what
the published ``config.json`` leaves open; hidden 2,304, no bias anywhere), for
layer type ``t`` of ``layer_types``:

- ``u = N1(x)``; ``q = u Wq`` as ``num_attention_heads`` heads of ``head_dim``,
  ``k = u Wk``, ``v = u Wv`` as ``num_key_value_heads`` heads; ``q <- Nq(q)``,
  ``k <- Nk(k)`` (RMSNorm over a head's features, one gain each); ``q, k <-
  rope_t(q, k)``; ``s_ij = q_i . k_j / sqrt(head_dim)`` for ``j <= i`` and, on a
  sliding layer, ``i - j < sliding_window``; softmax over j; query head h reads
  key-value head ``floor(h / group)``; ``x' = x + Attn Wo``.
- ``u' = N2(x')``; ``r = u' Wr`` (all ``router_experts`` logits, float32); ``p =
  softmax(r)``; S = the ``num_experts_per_tok`` largest of p; ``w_e = p_e /
  sum_{S} p`` ; ``y = sum_{e in S, held} w_e (silu(u' Wg_e) * (u' Wu_e)) Wd_e``
  over the ``num_experts`` experts held here, ``experts_held_first`` on;
  ``out = x' + y``.
- the score adds, a layer, ``router_aux_loss_coef x router_experts x sum_e f_e
  P_e``: ``f_e`` the share of tokens that chose e (summing to
  ``num_experts_per_tok``), ``P_e`` the mean of ``p_e``, over all experts.
- ``rope_sliding``: rotate-half, theta; ``rope_full``: YaRN (``yarn_inv_freq``),
  cos and sin multiplied by ``attention_factor``.
- a final RMSNorm, an untied head, cross-entropy at every position; a row's
  score is the sum over its positions (with the layers' terms once a position),
  the loss the mean over the rows.

The experts are applied one at a time, each densely to every token and weighted
by ``w_e`` (0 where not chosen): no sort, no gather, no grouped product. The
attention is an explicit band mask over blocks of queries; the head is taken in
blocks of positions. The parameter tree is keyed as the program keys it: the
token table ``{"W"}``, a block ``{"Wq", "Wk", "Wv", "Wo", "n1_g", "n3_g", "q_g",
"k_g", "mlp": {"Wr", "Wg", "Wu", "Wd"}}``, the final norm ``{"gamma"}``, the
head ``{"W"}``; a block's state is its term of the score and its load.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference_train import precision_policy

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 1024          # queries whose scores against every key live at once, a key-value head
HEAD_BLOCK = 1024           # positions whose logits live at once


def _sizes(cfg: dict) -> tuple:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return (d, cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh,
            cfg["moe_intermediate_size"], cfg["vocab_size"])


def layer_types(cfg: dict) -> list:
    """The held layers' types: the first ``num_hidden_layers`` of the published list."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def make_params(key, cfg: dict):
    """(params, state) in float32 from one key: matrices normal(0,
    ``initializer_range``), the token table normal(0,
    ``embedding_initializer_range``), gains 1."""
    d, a, kv, f, vocab = _sizes(cfg)
    n, held, std = cfg["num_hidden_layers"], cfg["num_experts"], cfg["initializer_range"]
    shapes = {"Wq": (d, a), "Wk": (d, kv), "Wv": (d, kv), "Wo": (a, d)}
    experts = {"Wr": (d, cfg["router_experts"]), "Wg": (held, d, f), "Wu": (held, d, f),
               "Wd": (held, f, d)}
    keys = iter(jax.random.split(key, 2 + (len(shapes) + len(experts)) * n))

    def normal(shape, std=std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params, state = [{"W": normal((vocab, d), cfg["embedding_initializer_range"])}], [{}]
    for _ in range(n):
        block = {k: normal(s) for k, s in shapes.items()}
        block["mlp"] = {k: normal(s) for k, s in experts.items()}
        block.update(n1_g=jnp.ones((d,)), n3_g=jnp.ones((d,)),
                     q_g=jnp.ones((cfg["head_dim"],)), k_g=jnp.ones((cfg["head_dim"],)))
        params.append(block)
        state.append({"loss_term": jnp.zeros(()), "moe_stats": jnp.zeros((4,))})
    params += [{"gamma": jnp.ones((d,))}, {"W": normal((d, vocab))}]
    return params, state + [{}, {}]


def _rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------- positions
def yarn_inv_freq(head_dim: int, section: dict):
    """``inv_freq_i = (1 - m_i) / (factor b^(2i/d)) + m_i / b^(2i/d)``, ``m_i = 1 -
    clip((i - low) / (high - low), 0, 1)``, ``low = floor(d ln(L / (beta_fast 2
    pi)) / (2 ln b))``, ``high = ceil(d ln(L / (beta_slow 2 pi)) / (2 ln b))``,
    both kept inside [0, d - 1]; L the original positions, b theta."""
    b, length = section["rope_theta"], section["original_max_position_embeddings"]

    def correction(turns):
        return head_dim * math.log(length / (turns * 2 * math.pi)) / (2 * math.log(b))

    low = min(max(math.floor(correction(section["beta_fast"])), 0), head_dim - 1)
    high = min(max(math.ceil(correction(section["beta_slow"])), 0), head_dim - 1)
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    m = 1.0 - jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    plain = b ** (-2.0 * i / head_dim)
    return (1.0 - m) * plain / section["factor"] + m * plain


def rotary(positions: int, head_dim: int, section: dict) -> tuple:
    """(angles ``[positions, head_dim / 2]`` in float32, the factor cos and sin take)."""
    if section["rope_type"] == "yarn":
        inv_freq, factor = yarn_inv_freq(head_dim, section), section["attention_factor"]
    else:
        i = jnp.arange(head_dim // 2, dtype=jnp.float32)
        inv_freq, factor = section["rope_theta"] ** (-2.0 * i / head_dim), 1.0
    return jnp.arange(positions, dtype=jnp.float32)[:, None] * inv_freq, factor


def rotate(t, rope):
    """Rotary positions on ``t`` ``[..., positions, head_dim]``, rotate-half
    pairing: feature i with feature i + head_dim / 2, turned by the angle of i."""
    angles, factor = rope
    half = t.shape[-1] // 2
    first, second = t[..., :half].astype(jnp.float32), t[..., half:].astype(jnp.float32)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1).astype(t.dtype)


# ------------------------------------------------------------------- the layer
def attention(q, k, v, window, product):
    """Causal attention of one row: ``q`` ``[kv heads, group, T, dh]``, ``k`` /
    ``v`` ``[kv heads, T, dh]``; under ``window`` query i sees keys j with ``0 <=
    i - j < window``. One key-value head's group and one block of queries at a
    time, the scores against every key under an explicit mask."""
    t, dh = q.shape[2], q.shape[3]
    block = min(QUERY_BLOCK, t)
    j = jnp.arange(t)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv                    # [group, T, dh], [T, dh], [T, dh]

        @jax.checkpoint
        def one_block(start):
            qb = lax.dynamic_slice_in_dim(qh, start, block, axis=1)
            i = start + jnp.arange(block)[:, None]
            keep = j <= i
            if window is not None:
                keep &= i - j < window
            scores = product(lambda a, b: jnp.einsum("gtd,sd->gts", a, b, precision=HIGHEST))(
                qb, kh) / dh ** 0.5
            probs = jax.nn.softmax(jnp.where(keep, scores, jnp.finfo(scores.dtype).min), axis=-1)
            return product(lambda a, b: jnp.einsum("gts,sd->gtd", a, b, precision=HIGHEST))(
                probs, vh)

        out = lax.map(one_block, jnp.arange(0, t, block))          # [blocks, group, block, dh]
        return out.transpose(1, 0, 2, 3).reshape(qh.shape)

    return lax.map(one_head, (q, k, v))


def route(probs, cfg: dict):
    """(chosen ``[tokens, k]``, their weights renormalised to sum to 1)."""
    top_p, chosen = lax.top_k(probs, cfg["num_experts_per_tok"])
    return chosen, top_p / top_p.sum(axis=-1, keepdims=True)


def experts(u, p, cfg: dict, product):
    """(y, the router's term, the load) over the tokens ``u`` ``[tokens, hidden]``:
    each held expert applied to every token and weighted by its renormalised
    router probability, 0 where the token did not choose it."""
    first, held, total = cfg["experts_held_first"], cfg["num_experts"], cfg["router_experts"]
    logits = jnp.dot(u.astype(jnp.float32), p["Wr"].astype(jnp.float32), precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    chosen, weights = route(probs, cfg)
    # [tokens, total]: a token's weight for each expert, 0 where not chosen
    dense = (jax.nn.one_hot(chosen, total, dtype=jnp.float32) * weights[..., None]).sum(axis=1)

    def mm(a, w):
        return product(lambda a, w: jnp.dot(a, w, precision=HIGHEST))(a, w)

    @jax.checkpoint
    def one_expert(y, e):
        wg, wu, wd = (lax.dynamic_index_in_dim(p[k], e, keepdims=False) for k in ("Wg", "Wu", "Wd"))
        out = mm(jax.nn.silu(mm(u, wg)) * mm(u, wu), wd)
        weight = lax.dynamic_index_in_dim(dense, first + e, axis=1, keepdims=True)
        return y + weight * out.astype(jnp.float32), None

    y, _ = lax.scan(one_expert, jnp.zeros(u.shape, jnp.float32), jnp.arange(held))
    counts = jax.nn.one_hot(chosen, total, dtype=jnp.float32).sum(axis=(0, 1))
    aux = total * (counts / u.shape[0] * probs.mean(axis=0)).sum()
    here = counts[first:first + held]
    served = lax.dynamic_slice_in_dim(dense, first, held, axis=1).sum(axis=1) > 0
    load = jnp.stack([here.max() * held / jnp.maximum(here.sum(), 1.0), here.sum(),
                      (~served).sum().astype(jnp.float32), aux])
    return y.astype(u.dtype), aux, lax.stop_gradient(load)


def block(x, p, cfg: dict, kind: str, rope, product=lambda f: f, qa=lambda x: x):
    """One decoder block over the batch ``[rows, T, hidden]``: the attention a
    row at a time, the experts over all the batch's tokens."""
    def mm(a, w):
        return product(lambda a, w: jnp.dot(a, w, precision=HIGHEST))(a, w)

    rows, t, d = x.shape
    heads, kv, dh, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                          cfg["head_dim"], cfg["rms_norm_eps"])
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    u = _rms_norm(x, p["n1_g"], eps)

    def one_row(u):
        q = _rms_norm(mm(u, p["Wq"]).reshape(t, heads, dh), p["q_g"], eps).transpose(1, 0, 2)
        k = _rms_norm(mm(u, p["Wk"]).reshape(t, kv, dh), p["k_g"], eps).transpose(1, 0, 2)
        v = mm(u, p["Wv"]).reshape(t, kv, dh).transpose(1, 0, 2)
        ctx = attention(rotate(q, rope).reshape(kv, heads // kv, t, dh), rotate(k, rope), v,
                        window, product)
        return mm(ctx.reshape(heads, t, dh).transpose(1, 0, 2).reshape(t, heads * dh), p["Wo"])

    x = qa(x + lax.map(one_row, u))
    y, aux, load = experts(_rms_norm(x, p["n3_g"], eps).reshape(rows * t, d), p["mlp"], cfg,
                           product)
    return qa(x + y.reshape(x.shape)), aux, load


def loss_fn(params, state, features, labels, cfg: dict, precision: str = "float32"):
    """The loss (cross-entropy at every position and the routers' terms, summed
    over a row's positions, averaged over the rows) and the blocks' new state.
    One block's input is kept for the backward pass at a time, one block of
    positions' logits at a time. ``precision``:
    ``reference_train.precision_policy``."""
    cast, product, qa = precision_policy(precision)
    params = cast(params)
    table, *blocks, norm, head = params
    rows, t = features.shape
    kinds, coef = layer_types(cfg), cfg["router_aux_loss_coef"]
    # in the layers' order: a set's order changes from process to process, and with it
    # the traced program and its key in the compile cache
    ropes = {kind: rotary(t, cfg["head_dim"], cfg["rope_parameters"][kind])
             for kind in dict.fromkeys(kinds)}
    x = qa(table["W"][features.astype(jnp.int32)])
    new_state, terms = [state[0]], 0.0
    for p, kind in zip(blocks, kinds):
        x, aux, load = jax.checkpoint(
            lambda x, p, kind=kind: block(x, p, cfg, kind, ropes[kind], product, qa))(x, p)
        term = coef * t * aux
        terms = terms + term
        new_state.append({"loss_term": lax.stop_gradient(term), "moe_stats": load})
    z = qa(_rms_norm(x, norm["gamma"], cfg["rms_norm_eps"])).reshape(rows * t, -1)
    targets = labels.astype(jnp.int32).reshape(rows * t)
    step = min(HEAD_BLOCK, rows * t)

    @jax.checkpoint
    def one_block(start):
        zb = lax.dynamic_slice_in_dim(z, start, step)
        yb = lax.dynamic_slice_in_dim(targets, start, step)
        logits = product(lambda a, b: jnp.dot(a, b, precision=HIGHEST))(zb, head["W"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0].sum()

    ce = lax.map(one_block, jnp.arange(0, rows * t, step)).sum()
    return ce / rows + terms, new_state + [state[-2], state[-1]]


# ---------------------------------------------------------- analytic counts
def _dot_macs_per_token(cfg: dict) -> float:
    """Multiply-accumulates a token, forward, of the products XLA runs as
    ``dot_general``: the four projections and the router of every layer, and
    the head."""
    d, a, kv, _, vocab = _sizes(cfg)
    return cfg["num_hidden_layers"] * (2 * d * a + 2 * d * kv + d * cfg["router_experts"]) + d * vocab


def dot_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs in the products XLA
    itself runs as ``dot_general`` (3 x 2 x the multiply-accumulates; no
    recomputation counted): ``conv_dot_roofline`` reads it. Attention's products
    run in the flash kernel at the cell's ``seq``; the experts' are grouped
    products, which the TPU compiler turns into kernel calls of its own
    (``ragged-dot-*``: no ``dot_general`` in their name, so not in
    ``step_conv_dot_ms``): both are counted apart, below."""
    return 3 * 2 * float(traffic["seq"] * _dot_macs_per_token(cfg))


def _visible_pairs(seq: int, window) -> int:
    """(query, key) pairs a causal layer works: ``sum_i min(i + 1, window)``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The attention kernel's operations one sample needs, forward + backward:
    2 products forward (q k^T, p v) and 5 backward (the scores again, dv, dp,
    dq, dk) of ``head_dim`` multiply-accumulates for every query head and every
    visible (query, key) pair: the band's pairs on a sliding layer, the causal
    half on a full one."""
    heads, dh, seq = cfg["num_attention_heads"], cfg["head_dim"], traffic["seq"]
    pairs = sum(_visible_pairs(seq, cfg["sliding_window"] if kind == "sliding_attention" else None)
                for kind in layer_types(cfg))
    return float((2 + 5) * 2 * heads * dh * pairs)


def attention_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the kernel moves for one sample: q, o and their two
    gradients a query head, k, v and their two gradients a key-value head (read
    once a group), each once a layer, in the configuration's compute type."""
    dh = cfg["head_dim"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    width = 4 * cfg["num_attention_heads"] * dh + 4 * cfg["num_key_value_heads"] * dh
    return float(cfg["num_hidden_layers"] * traffic["seq"] * width * itemsize)


def _pairs_per_token(cfg: dict) -> float:
    """The (token, expert) pairs a token is expected to have among the experts
    held here: ``num_experts_per_tok`` x held / all, under an even router."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_experts"]


def expert_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The grouped products' operations one sample needs, forward + backward: 3
    products a pair forward (gate, up, down) and 6 backward, each ``hidden x
    moe_intermediate_size`` multiply-accumulates, for the expected held pairs;
    what ``remat`` recomputes is not counted, so a share over it errs low."""
    d, _, _, f, _ = _sizes(cfg)
    pairs = traffic["seq"] * _pairs_per_token(cfg)
    return float(cfg["num_hidden_layers"] * 9 * 2 * d * f * pairs)


def expert_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the grouped products move for one sample: the held
    experts' three matrices read forward and backward and their gradients
    written, and a pair's rows in and out of each product (hidden in, 2 x
    intermediate out, intermediate in, hidden out) forward and twice that
    backward, in the configuration's compute type."""
    d, _, _, f, _ = _sizes(cfg)
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    pairs = traffic["seq"] * _pairs_per_token(cfg)
    weights = 3 * 3 * cfg["num_experts"] * d * f
    rows = 3 * pairs * (2 * d + 3 * f)
    return float(cfg["num_hidden_layers"] * (weights + rows) * itemsize)


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs, whatever runs them (the
    embedding's gather, the sort and the permutations of routing have none;
    ``remat``'s recomputation and the optimizer do not count). ``step_mfu_pct``
    reads it."""
    return (dot_flops_per_sample(cfg, traffic) + attention_flops_per_sample(cfg, traffic)
            + expert_flops_per_sample(cfg, traffic))
