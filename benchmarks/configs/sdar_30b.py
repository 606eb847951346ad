"""SDAR-30B-A3B, a sparse-expert decoder trained as a block-diffusion language
model: the plain float32 reference of one chip's share and the analytic
operation and byte counts.

Imports nothing of the program. The equations (``sdar_30b.json`` lists what
the published ``config.json`` leaves open under ``assumed``; hidden 2,048, no
bias anywhere), for a batch of clean rows ``x_0`` ``[rows, L]``:

- the step's noise, from ``key = fold_in(key(noise_seed), noise_step)`` with
  ``noise_seed`` and the counter ``noise_step`` in the first layer's state:
  ``k_rate, k_mask = split(key)``; a rate ``t ~ U(noise.t_min, 1)`` a block of ``noise.block``
  positions a row (``uniform(k_rate, [rows, L / block])``); a position masked
  where ``uniform(k_mask, [rows, L]) < t`` of its block; ``x_t`` is ``x_0`` with
  ``noise.mask_id`` at the masked positions; the counter goes up by one.
- the stack's input: the table's rows of ``x_t || x_0``, 2L positions.
- every layer: ``u = N1(x)``; ``q = u Wq`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = u Wk``, ``v = u Wv`` as ``num_key_value_heads`` heads; ``q
  <- Nq(q)``, ``k <- Nk(k)`` (RMSNorm over a head's features, one gain each); q
  and k turned by rotary positions (rotate-half, ``rope_theta``) at positions
  ``0 .. L - 1`` in each half; ``s_ij = q_i . k_j / sqrt(head_dim)`` where
  ``visible(i, j)``: a noisy query of block b sees the noisy keys of block b
  and the clean keys of blocks < b, a clean query of block b the clean keys
  of blocks <= b; softmax over j; query head h reads key-value head ``floor(h
  / group)``; ``x' = x + Attn Wo``; then the experts on all 2L positions as
  in ``mellum2_12b``: ``r = N2(x') Wr`` (all ``router_experts`` logits, float32),
  softmax, the ``num_experts_per_tok`` largest renormalised, the held experts'
  ``(silu(u' Wg_e) * (u' Wu_e)) Wd_e`` weighted and summed; the score adds
  ``router_aux_loss_coef x router_experts x sum_e f_e P_e`` once a position
  of the 2L.
- a final RMSNorm, an untied head over the noised half alone: the logit at
  noisy position i predicts ``labels[i]``; a row's score is ``sum_i w_i
  CE_i`` with ``w_i = 1 / t`` at a masked position and 0 elsewhere, the loss
  the mean over the rows.

The attention is taken ``QUERY_BLOCK`` queries of one key-value head's group at
a time, each block its own checkpoint: the block's scores against all 2L keys
under the mask built densely for the block (``visible``). The experts are
applied one at a time, each densely to every token; the head in blocks of
positions. The parameter tree is keyed as the program keys it: the token table
``{"W"}``, a block ``{"Wq", "Wk", "Wv", "Wo", "n1_g", "n3_g", "q_g", "k_g",
"mlp": {"Wr", "Wg", "Wu", "Wd"}}``, the final norm ``{"gamma"}``, the head
``{"W"}``; the first layer's state is the noise's seed and counter, a block's
its term of the score and its load.

Which 16 of a layer's 128 experts this chip holds is the deployment's
placement (``place_experts``): every masked position embeds the same row, so a
layer's router sends about a quarter of the stack's positions to much the same
8 experts, and a placement that gives each of the group's 8 chips one of them
makes every chip's load alike and a property of the deployment, not of the
seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference_train import precision_policy

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 1024          # queries whose scores against every key live at once, a key-value head
HEAD_BLOCK = 1024           # positions whose logits live at once
PROBE_SEQ = 1024            # clean positions of the row by which the experts are placed
TILE = 512                  # the flash kernels' tiles, by which the tile counts are given


def _sizes(cfg: dict) -> tuple:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return (d, cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh,
            cfg["moe_intermediate_size"], cfg["vocab_size"])


def make_params(key, cfg: dict):
    """(params, state) in float32 from one key: matrices normal(0,
    ``initializer_range``), the token table normal(0,
    ``embedding_initializer_range``), the mask id's row among them; each
    router's columns placed (``place_experts``); gains 1; the noise's seed an
    int32 drawn from the key, its counter at 0."""
    d, a, kv, f, vocab = _sizes(cfg)
    n, held, std = cfg["num_hidden_layers"], cfg["num_experts"], cfg["initializer_range"]
    shapes = {"Wq": (d, a), "Wk": (d, kv), "Wv": (d, kv), "Wo": (a, d)}
    experts = {"Wr": (d, cfg["router_experts"]), "Wg": (held, d, f), "Wu": (held, d, f),
               "Wd": (held, f, d)}
    keys = iter(jax.random.split(key, 4 + (len(shapes) + len(experts)) * n))

    def normal(shape, std=std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params = [{"W": normal((vocab, d), cfg["embedding_initializer_range"])}]
    state = [{"noise_seed": jax.random.randint(next(keys), (), 0, jnp.iinfo(jnp.int32).max,
                                               jnp.int32),
              "noise_step": jnp.zeros((), jnp.int32)}]
    for _ in range(n):
        block = {k: normal(s) for k, s in shapes.items()}
        block["mlp"] = {k: normal(s) for k, s in experts.items()}
        block.update(n1_g=jnp.ones((d,)), n3_g=jnp.ones((d,)),
                     q_g=jnp.ones((cfg["head_dim"],)), k_g=jnp.ones((cfg["head_dim"],)))
        params.append(block)
        state.append({"loss_term": jnp.zeros(()), "moe_stats": jnp.zeros((4,))})
    params += [{"gamma": jnp.ones((d,))}, {"W": normal((d, vocab))}]
    return place_experts(params, next(keys), cfg), state + [{}, {}]


def place_experts(params, key, cfg: dict) -> list:
    """``params`` with each layer's router columns renumbered so that the
    experts held here (``num_experts`` from ``experts_held_first``) are this
    chip's share under a placement balanced for the mask token: the
    ``num_experts_per_tok x num_experts / router_experts`` experts its masked
    positions rank first (their share of the token's experts when every chip
    of the group holds as many) and the experts they rank last; the others keep
    their order around them. The ranking is read off a probe: one row of
    ``PROBE_SEQ`` clean ids drawn from ``key``, each noisy position masked with
    probability 1/2, taken through the layers as placed so far; a layer ranks
    the experts by the mean of its router's logits over the masked positions.
    A renumbering of columns drawn alike leaves the router's law as it was."""
    first, held, total = cfg["experts_held_first"], cfg["num_experts"], cfg["router_experts"]
    share, mask_id = cfg["num_experts_per_tok"] * held // total, cfg["noise"]["mask_id"]
    k_ids, k_mask = jax.random.split(key)
    clean = jax.random.randint(k_ids, (1, PROBE_SEQ), 0, mask_id)
    masked = jax.random.uniform(k_mask, (1, PROBE_SEQ)) < 0.5
    x = params[0]["W"][jnp.concatenate([jnp.where(masked, mask_id, clean), clean], axis=1)]
    weight = jnp.concatenate([masked[0], jnp.zeros(PROBE_SEQ, bool)]).astype(jnp.float32)
    angles = rotary(PROBE_SEQ, cfg["head_dim"], cfg["rope_theta"])
    placed = params[:1]
    for p in params[1:-2]:
        x = attend(x, p, cfg, angles)
        u = _rms_norm(x, p["n3_g"], cfg["rms_norm_eps"])[0]
        ranked = jnp.argsort(-jnp.dot(weight, jnp.dot(u, p["mlp"]["Wr"], precision=HIGHEST),
                                      precision=HIGHEST))
        here = jnp.concatenate([ranked[:share], ranked[total - (held - share):]])
        rest = jnp.argsort(jnp.zeros(total, bool).at[here].set(True), stable=True)[:total - held]
        order = jnp.concatenate([rest[:first], here, rest[first:]])
        p = {**p, "mlp": {**p["mlp"], "Wr": p["mlp"]["Wr"][:, order]}}
        x = x + experts(u, p["mlp"], cfg, lambda f: f)[0][None]
        placed.append(p)
    return placed + params[-2:]


def _rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- noise
def noise(cfg: dict, seed, step, rows: int, seq: int) -> tuple:
    """(masked ``[rows, seq]``, weights ``[rows, seq]``) of the step whose
    seed and counter are ``seed`` and ``step``: the configuration's ``noise``
    draws."""
    spec = cfg["noise"]
    k_rate, k_mask = jax.random.split(jax.random.fold_in(jax.random.key(seed), step))
    rates = jax.random.uniform(k_rate, (rows, seq // spec["block"]), jnp.float32,
                               spec["t_min"], 1.0)
    rate = rates[:, jnp.arange(seq) // spec["block"]]
    masked = jax.random.uniform(k_mask, (rows, seq), jnp.float32) < rate
    return masked, jnp.where(masked, 1.0 / rate, 0.0)


# ------------------------------------------------------------------ positions
def rotary(seq: int, head_dim: int, theta: float):
    """Angles ``[2 seq, head_dim / 2]``: positions 0 .. seq - 1, in each half."""
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    pos = jnp.arange(seq, dtype=jnp.float32)
    return jnp.concatenate([pos, pos])[:, None] * theta ** (-2.0 * i / head_dim)


def rotate(t, angles):
    """Rotary positions on ``t`` ``[..., positions, head_dim]``, rotate-half
    pairing: feature i with feature i + head_dim / 2, turned by the angle of i."""
    half = t.shape[-1] // 2
    first, second = t[..., :half].astype(jnp.float32), t[..., half:].astype(jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1).astype(t.dtype)


# ------------------------------------------------------------------ the layer
def visible(i, j, seq: int, block: int):
    """Which keys ``j`` each query ``i`` sees (broadcast), over ``x_t || x_0`` of
    ``seq`` clean positions in blocks of ``block``."""
    qi_clean, kj_clean = i >= seq, j >= seq
    qb = jnp.where(qi_clean, i - seq, i) // block
    kb = jnp.where(kj_clean, j - seq, j) // block
    noisy_query = jnp.where(kj_clean, kb < qb, kb == qb)
    clean_query = kj_clean & (kb <= qb)
    return jnp.where(qi_clean, clean_query, noisy_query)


def attention(q, k, v, block: int, product):
    """Attention of one row under the block-diffusion mask: ``q`` ``[kv heads,
    group, 2L, dh]``, ``k`` / ``v`` ``[kv heads, 2L, dh]``. One key-value head's
    group and one block of queries at a time."""
    t, dh = q.shape[2], q.shape[3]
    step = min(QUERY_BLOCK, t)
    j = jnp.arange(t)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv

        @jax.checkpoint
        def one_block(start):
            qb = lax.dynamic_slice_in_dim(qh, start, step, axis=1)
            keep = visible(start + jnp.arange(step)[:, None], j, t // 2, block)
            scores = product(lambda a, b: jnp.einsum("gtd,sd->gts", a, b, precision=HIGHEST))(
                qb, kh) / dh ** 0.5
            probs = jax.nn.softmax(jnp.where(keep, scores, jnp.finfo(scores.dtype).min), axis=-1)
            return product(lambda a, b: jnp.einsum("gts,sd->gtd", a, b, precision=HIGHEST))(
                probs, vh)

        out = lax.map(one_block, jnp.arange(0, t, step))
        return out.transpose(1, 0, 2, 3).reshape(qh.shape)

    return lax.map(one_head, (q, k, v))


def experts(u, p, cfg: dict, product):
    """(y, the router's term, the load) over the tokens ``u`` ``[tokens, hidden]``:
    each held expert applied to every token and weighted by its renormalised
    router probability, 0 where the token did not choose it."""
    first, held, total = cfg["experts_held_first"], cfg["num_experts"], cfg["router_experts"]
    logits = jnp.dot(u.astype(jnp.float32), p["Wr"].astype(jnp.float32), precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, chosen = lax.top_k(probs, cfg["num_experts_per_tok"])
    weights = top_p / top_p.sum(axis=-1, keepdims=True)
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


def attend(x, p, cfg: dict, angles, product=lambda f: f, qa=lambda x: x):
    """The attention half of a decoder block over the batch ``[rows, 2L,
    hidden]``, a row at a time, with its residual."""
    def mm(a, w):
        return product(lambda a, w: jnp.dot(a, w, precision=HIGHEST))(a, w)

    t = x.shape[1]
    heads, kv, dh, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                          cfg["head_dim"], cfg["rms_norm_eps"])
    u = _rms_norm(x, p["n1_g"], eps)

    def one_row(u):
        q = _rms_norm(mm(u, p["Wq"]).reshape(t, heads, dh), p["q_g"], eps).transpose(1, 0, 2)
        k = _rms_norm(mm(u, p["Wk"]).reshape(t, kv, dh), p["k_g"], eps).transpose(1, 0, 2)
        v = mm(u, p["Wv"]).reshape(t, kv, dh).transpose(1, 0, 2)
        ctx = attention(rotate(q, angles).reshape(kv, heads // kv, t, dh), rotate(k, angles), v,
                        cfg["noise"]["block"], product)
        return mm(ctx.reshape(heads, t, dh).transpose(1, 0, 2).reshape(t, heads * dh), p["Wo"])

    return qa(x + lax.map(one_row, u))


def block(x, p, cfg: dict, angles, product=lambda f: f, qa=lambda x: x):
    """One decoder block over the batch ``[rows, 2L, hidden]``: the attention a
    row at a time, the experts over all the batch's positions."""
    x = attend(x, p, cfg, angles, product, qa)
    rows, t, d = x.shape
    y, aux, load = experts(_rms_norm(x, p["n3_g"], cfg["rms_norm_eps"]).reshape(rows * t, d),
                           p["mlp"], cfg, product)
    return qa(x + y.reshape(x.shape)), aux, load


def loss_fn(params, state, features, labels, cfg: dict, precision: str = "float32"):
    """The loss (cross-entropy at the noised half's masked positions weighted
    ``1 / t``, and the routers' terms once a position of the 2L, summed over a
    row, averaged over the rows) and the new state: the counter one on, the
    blocks' terms and loads. One block's input is kept for the backward pass at
    a time, one block of positions' logits at a time. ``precision``:
    ``reference_train.precision_policy``."""
    cast, product, qa = precision_policy(precision)
    params = cast(params)
    table, *blocks, norm, head = params
    rows, seq = features.shape
    clean = features.astype(jnp.int32)
    seed, step = state[0]["noise_seed"], state[0]["noise_step"]
    masked, weights = noise(cfg, seed, step, rows, seq)
    ids = jnp.concatenate([jnp.where(masked, cfg["noise"]["mask_id"], clean), clean], axis=1)
    angles = rotary(seq, cfg["head_dim"], cfg["rope_theta"])
    coef = cfg["router_aux_loss_coef"]
    x = qa(table["W"][ids])
    new_state, terms = [{"noise_seed": seed, "noise_step": step + 1}], 0.0
    for p in blocks:
        x, aux, load = jax.checkpoint(
            lambda x, p: block(x, p, cfg, angles, product, qa))(x, p)
        term = coef * 2 * seq * aux
        terms = terms + term
        new_state.append({"loss_term": lax.stop_gradient(term), "moe_stats": load})
    z = qa(_rms_norm(x[:, :seq], norm["gamma"], cfg["rms_norm_eps"])).reshape(rows * seq, -1)
    targets = labels.astype(jnp.int32).reshape(rows * seq)
    w = weights.reshape(rows * seq)
    size = min(HEAD_BLOCK, rows * seq)

    @jax.checkpoint
    def one_block(start):
        zb = lax.dynamic_slice_in_dim(z, start, size)
        yb = lax.dynamic_slice_in_dim(targets, start, size)
        wb = lax.dynamic_slice_in_dim(w, start, size)
        logits = product(lambda a, b: jnp.dot(a, b, precision=HIGHEST))(zb, head["W"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -(wb * jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]).sum()

    ce = lax.map(one_block, jnp.arange(0, rows * seq, size)).sum()
    return ce / rows + terms, new_state + [state[-2], state[-1]]


# ---------------------------------------------------------- analytic counts
def visible_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the mask shows over ``x_t || x_0`` of ``seq`` clean
    positions, a head: ``seq x block`` noisy with noisy, ``block^2 sum_{b <
    n} b`` noisy to clean and ``block^2 sum_{b <= n} b`` clean to clean, n =
    seq / block blocks."""
    n = seq // block
    return seq * block + block * block * (n * (n - 1) // 2) + block * block * (n * (n + 1) // 2)


def tiles_visited(seq: int, block: int, tile: int = TILE) -> int:
    """The ``tile x tile`` tiles of the ``2 seq`` square that hold a visible
    pair, where ``tile`` is a multiple of ``block`` that divides ``seq``: on the
    noisy rows the diagonal tile and the clean tiles up to the row's own, on
    the clean rows the clean tiles up to their own."""
    n = seq // tile
    return n + 2 * (n * (n + 1) // 2)


def _dot_macs_per_token(cfg: dict) -> float:
    """Multiply-accumulates a position of the stack, forward, of the products
    XLA runs as ``dot_general``: the four projections and the router of every
    layer (the head is counted apart: it sees the noised half alone)."""
    d, a, kv, _, _ = _sizes(cfg)
    return cfg["num_hidden_layers"] * (2 * d * a + 2 * d * kv + d * cfg["router_experts"])


def dot_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs in the products XLA
    itself runs as ``dot_general`` (3 x 2 x the multiply-accumulates; no
    recomputation counted): the projections and routers over the 2L
    positions, the head over the L noised ones. ``conv_dot_roofline`` reads
    it. The attention runs in the flash kernels and the experts in grouped
    products: both are counted apart, below."""
    d, vocab, seq = cfg["hidden_size"], cfg["vocab_size"], traffic["seq"]
    return 3 * 2 * float(2 * seq * _dot_macs_per_token(cfg) + seq * d * vocab)


def block_diffusion_attention_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The block-mask flash kernels' operations one sample needs, forward +
    backward: 2 products forward (q k^T, p v) and 5 backward (the scores again,
    dv, dp, dq, dk) of ``head_dim`` multiply-accumulates, 4 x ``head_dim``
    operations a pair forward and 10 x backward, for every query head and every
    **visible** pair of every layer. The kernels work whole tiles that hold a
    visible pair, so a share over this count errs low."""
    heads, dh = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = visible_pairs(traffic["seq"], cfg["noise"]["block"])
    return float(cfg["num_hidden_layers"] * (4 + 10) * dh * heads * pairs)


def block_diffusion_attention_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the kernels move for one sample: q, o and their two
    gradients a query head, k, v and their two gradients a key-value head (read
    once a group), over the 2L positions, each once a layer, in the
    configuration's compute type."""
    dh = cfg["head_dim"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    width = 4 * cfg["num_attention_heads"] * dh + 4 * cfg["num_key_value_heads"] * dh
    return float(cfg["num_hidden_layers"] * 2 * traffic["seq"] * width * itemsize)


def _pairs_per_token(cfg: dict) -> float:
    """The (token, expert) pairs a position is expected to have among the
    experts held here: ``num_experts_per_tok`` x held / all, under an even
    router; nearly every masked position has exactly that many (``place_experts``)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_experts"]


def expert_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The grouped products' operations one sample needs, forward + backward: 3
    products a pair forward (gate, up, down) and 6 backward, each ``hidden x
    moe_intermediate_size`` multiply-accumulates, for the expected held pairs
    of the 2L positions."""
    d, _, _, f, _ = _sizes(cfg)
    pairs = 2 * traffic["seq"] * _pairs_per_token(cfg)
    return float(cfg["num_hidden_layers"] * 9 * 2 * d * f * pairs)


def expert_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the grouped products move for one sample: the held
    experts' three matrices read forward and backward and their gradients
    written, and a pair's rows in and out of each product (hidden in, 2 x
    intermediate out, intermediate in, hidden out) forward and twice that
    backward, for the expected held pairs of the 2L positions, in the
    configuration's compute type."""
    d, _, _, f, _ = _sizes(cfg)
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    pairs = 2 * traffic["seq"] * _pairs_per_token(cfg)
    weights = 3 * 3 * cfg["num_experts"] * d * f
    rows = 3 * pairs * (2 * d + 3 * f)
    return float(cfg["num_hidden_layers"] * (weights + rows) * itemsize)


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs, whatever runs them (the
    embedding's gather, the noise and the permutations of routing have none;
    ``remat``'s recomputation and the optimizer do not count): the linear work
    over 2L positions, the attention over the visible pairs, the head over L.
    ``step_mfu_pct`` reads it."""
    return (dot_flops_per_sample(cfg, traffic)
            + block_diffusion_attention_flops_per_sample(cfg, traffic)
            + expert_flops_per_sample(cfg, traffic))
