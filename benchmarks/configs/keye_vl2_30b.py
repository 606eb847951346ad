"""Keye-VL-2.0-30B-A3B's language model, a sparse-expert decoder whose attention
chooses its keys with a learned indexer (DeepSeek Sparse Attention): the plain
float32 reference of one chip's share and the analytic operation and byte
counts.

Imports nothing of the program. The equations (``keye_vl2_30b.json`` lists what
the published ``config.json`` leaves open under ``assumed``; hidden 2,048, no
bias anywhere), for every layer:

- ``u = N1(x)``; ``q = u Wq`` as ``num_attention_heads`` heads of ``head_dim``,
  ``k = u Wk``, ``v = u Wv`` as ``num_key_value_heads`` heads; ``q <- Nq(q)``,
  ``k <- Nk(k)`` (RMSNorm over a head's features, one gain each); q and k turned
  by M-RoPE: the ``head_dim / 2`` frequencies ``rope_theta^(-2i/head_dim)`` in
  runs of ``mrope_section``, run n turning by position row n (text: the three
  rows are the token's one position).
- the indexer, from ``sg(u)``: ``q_i = u Wq_i`` as ``indexer_num_heads`` heads
  of ``indexer_head_dim``, ``k_i = LN(u Wk_i)`` (gain and bias), the first
  ``indexer_rope_dim`` features of both turned by plain rotary positions,
  ``w = u Ww_i / sqrt(indexer_num_heads x indexer_head_dim)``; ``I[t, s] =
  sum_j w[t, j] relu(q_i[t, j] . k_i[s])`` for ``s <= t``; ``S_t`` the ``topk``
  largest (``lax.top_k``: a tie goes to the lower position), all ``s <= t``
  while ``t < topk``.
- ``o[t, n] = sum_{s in S_t} softmax_s(q[t, n] . k[s, g(n)] / sqrt(head_dim))
  v[s, g(n)]``, query head n reading key-value head ``floor(n / group)``;
  ``x' = x + o Wo``.
- the indexer's loss, a row: ``sum_t KL(p_t || softmax_{s in S_t} I[t, s])``,
  ``p[t, s]`` the mean over the query heads of the weight on s, held fixed;
  it is added to the score over the rows (a coefficient of 1).
- ``u' = N2(x')``; ``r = u' Wr`` (all ``router_experts`` logits, float32); ``p =
  softmax(r)``; S = the ``num_experts_per_tok`` largest; ``w_e = p_e / sum_S
  p``; ``y = sum_{e in S, held} w_e (silu(u' Wg_e) * (u' Wu_e)) Wd_e`` over the
  ``num_experts`` experts held here, ``experts_held_first`` on; ``out = x' +
  y``; the score adds ``router_aux_loss_coef x router_experts x sum_e f_e P_e``
  a layer, once a position.
- a final RMSNorm, an untied head, cross-entropy at every position; a row's
  score is the sum over its positions, the loss the mean over the rows.

The attention, the choice and the indexer's loss are taken ``QUERY_BLOCK``
queries at a time, each block its own checkpoint: a block's scores against
every key under an explicit mask. The experts are applied one at a time, each
densely to every token; the head in blocks of positions. The parameter tree is
keyed as the program keys it: the token table ``{"W"}``, a block ``{"mixer":
{"Wq", "Wk", "Wv", "Wo", "q_g", "k_g", "indexer": {"Wq", "Wk", "Ww",
"k_norm_g", "k_norm_b"}}, "mlp": {"Wr", "Wg", "Wu", "Wd"}, "n1_g", "n3_g"}``,
the final norm ``{"gamma"}``, the head ``{"W"}``; a block's state is its two
terms of the score, its load and the share of causal tiles its choice reaches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference_train import precision_policy

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 256           # queries whose scores against every key live at once
HEAD_BLOCK = 1024           # positions whose logits live at once
TILE = 512                  # sa_config's q_chunk_size / kv_chunk_size: the gauge's tiles


def _sizes(cfg: dict) -> tuple:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return (d, cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh,
            cfg["moe_intermediate_size"], cfg["vocab_size"])


def _indexer_sizes(cfg: dict) -> tuple:
    sa = cfg["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], cfg["indexer_rope_dim"], sa["topk"]


def make_params(key, cfg: dict):
    """(params, state) in float32 from one key: matrices normal(0,
    ``initializer_range``), the token table normal(0,
    ``embedding_initializer_range``), gains 1, the indexer's LayerNorm bias 0."""
    d, a, kv, f, vocab = _sizes(cfg)
    heads_i, dim_i, _, _ = _indexer_sizes(cfg)
    n, held, std = cfg["num_hidden_layers"], cfg["num_experts"], cfg["initializer_range"]
    shapes = {"Wq": (d, a), "Wk": (d, kv), "Wv": (d, kv), "Wo": (a, d)}
    indexer = {"Wq": (d, heads_i * dim_i), "Wk": (d, dim_i), "Ww": (d, heads_i)}
    experts = {"Wr": (d, cfg["router_experts"]), "Wg": (held, d, f), "Wu": (held, d, f),
               "Wd": (held, f, d)}
    keys = iter(jax.random.split(key, 2 + (len(shapes) + len(indexer) + len(experts)) * n))

    def normal(shape, std=std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    params, state = [{"W": normal((vocab, d), cfg["embedding_initializer_range"])}], [{}]
    for _ in range(n):
        mixer = {k: normal(s) for k, s in shapes.items()}
        mixer.update(q_g=jnp.ones((cfg["head_dim"],)), k_g=jnp.ones((cfg["head_dim"],)))
        mixer["indexer"] = {k: normal(s) for k, s in indexer.items()}
        mixer["indexer"].update(k_norm_g=jnp.ones((dim_i,)), k_norm_b=jnp.zeros((dim_i,)))
        params.append({"mixer": mixer, "mlp": {k: normal(s) for k, s in experts.items()},
                       "n1_g": jnp.ones((d,)), "n3_g": jnp.ones((d,))})
        state.append({"loss_term": jnp.zeros(()), "loss_term_indexer": jnp.zeros(()),
                      "moe_stats": jnp.zeros((4,)), "sparse_tiles_visited": jnp.zeros(())})
    params += [{"gamma": jnp.ones((d,))}, {"W": normal((d, vocab))}]
    return params, state + [{}, {}]


def _rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x, gain, bias, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    centred = xf - mean
    return (centred * lax.rsqrt((centred * centred).mean(-1, keepdims=True) + eps)
            * gain + bias).astype(x.dtype)


# ------------------------------------------------------------------- positions
def mrope_angles(positions, head_dim: int, theta: float, sections) -> jnp.ndarray:
    """``[T, head_dim / 2]`` angles: frequency i is ``theta^(-2i/head_dim)`` and
    turns by the position row of the run of ``sections`` it falls in;
    ``positions`` ``[len(sections), T]``."""
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    row = np.concatenate([np.full(n, r) for r, n in enumerate(sections)])
    return jnp.asarray(positions, jnp.float32)[row].T * theta ** (-2.0 * i / head_dim)


def rotate(t, angles):
    """Rotary positions on ``t`` ``[..., T, width]`` by ``angles`` ``[T, width
    / 2]``, rotate-half pairing: feature i with feature i + width / 2."""
    half = t.shape[-1] // 2
    first, second = t[..., :half].astype(jnp.float32), t[..., half:].astype(jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1).astype(t.dtype)


# ------------------------------------------------------------------- the layer
def _keep_topk(scores, topk: int, start):
    """Boolean ``[rows, T]``: the ``topk`` largest of each row of ``scores``
    (``-inf`` past the row's own position ``start + row``), ties to the lower
    position, within the row's causal reach."""
    rows, t = scores.shape
    _, chosen = lax.top_k(scores, min(topk, t))
    keep = jnp.zeros((rows, t), bool).at[jnp.arange(rows)[:, None], chosen].set(True)
    return keep & (jnp.arange(t)[None, :] <= start + jnp.arange(rows)[:, None])


def attention(q, k, v, q_i, k_i, w, cfg: dict, product):
    """One row: ``(o [heads, T, dh], the indexer's loss, the share of the causal
    tiles the choice reaches)``. ``q`` ``[kv heads, group, T, dh]``, ``k`` / ``v``
    ``[kv heads, T, dh]``, ``q_i`` ``[index heads, T, d_I]``, ``k_i`` ``[T,
    d_I]``, ``w`` ``[T, index heads]``. A block of queries at a time."""
    kvh, group, t, dh = q.shape
    topk = cfg["sa_config"]["topk"]
    block = min(QUERY_BLOCK, t)
    tiles = -(-t // TILE)

    def mm(spec):
        return product(lambda a, b: jnp.einsum(spec, a, b, precision=HIGHEST))

    @jax.checkpoint
    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=2)
        qib = lax.dynamic_slice_in_dim(q_i, start, block, axis=1)
        wb = lax.dynamic_slice_in_dim(w, start, block, axis=0)
        causal = jnp.arange(t)[None, :] <= start + jnp.arange(block)[:, None]
        index = (jnp.maximum(mm("jtd,sd->jts")(qib, k_i), 0.0)
                 * wb.T[:, :, None]).sum(axis=0)
        index = jnp.where(causal, index, -jnp.inf)
        keep = _keep_topk(lax.stop_gradient(index), topk, start)
        scores = mm("gntd,gsd->gnts")(qb, k) / dh ** 0.5
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        out = mm("gnts,gsd->gntd")(probs, v)
        target = lax.stop_gradient(probs.mean(axis=(0, 1)))
        log_q = jax.nn.log_softmax(jnp.where(keep, index, -jnp.inf), axis=-1)
        kl = jnp.where(keep, jax.scipy.special.xlogy(target, target)
                       - target * jnp.where(keep, log_q, 0.0), 0.0).sum()
        reached = jnp.pad(keep, ((0, 0), (0, tiles * TILE - t))).reshape(
            block, tiles, TILE).any(axis=(0, 2))
        return out, kl, reached

    out, kl, reached = lax.map(one_block, jnp.arange(0, t, block))
    out = out.transpose(1, 2, 0, 3, 4).reshape(kvh * group, t, dh)
    # a TILE of queries is TILE / block blocks (or the one block of a shorter row)
    reached = reached.reshape(tiles, -1, tiles).any(axis=1)
    share = lax.stop_gradient(reached.sum() / (tiles * (tiles + 1) // 2))
    return out, kl.sum(), share


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


def mixer(u, m, cfg: dict, rope, index_rope, product=lambda f: f):
    """The sparse-attention mixer on one row's normed input ``u`` ``[T, hidden]``:
    (its output, the indexer's loss, the share of the causal tiles the choice
    reaches). ``m``: the block's ``"mixer"`` parameters; ``rope`` /
    ``index_rope``: ``mrope_angles`` of the heads and of the indexer's turned
    features."""
    def mm(a, w):
        return product(lambda a, w: jnp.dot(a, w, precision=HIGHEST))(a, w)

    t = u.shape[0]
    heads, kv, dh, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                          cfg["head_dim"], cfg["rms_norm_eps"])
    heads_i, dim_i, rope_i, _ = _indexer_sizes(cfg)
    ix = m["indexer"]

    def turned(t_):
        return jnp.concatenate([rotate(t_[..., :rope_i], index_rope), t_[..., rope_i:]], axis=-1)

    q = _rms_norm(mm(u, m["Wq"]).reshape(t, heads, dh), m["q_g"], eps).transpose(1, 0, 2)
    k = _rms_norm(mm(u, m["Wk"]).reshape(t, kv, dh), m["k_g"], eps).transpose(1, 0, 2)
    v = mm(u, m["Wv"]).reshape(t, kv, dh).transpose(1, 0, 2)
    h = lax.stop_gradient(u)
    q_i = turned(mm(h, ix["Wq"]).reshape(t, heads_i, dim_i).transpose(1, 0, 2))
    k_i = turned(_layer_norm(mm(h, ix["Wk"]), ix["k_norm_g"], ix["k_norm_b"], eps))
    w = mm(h, ix["Ww"]).astype(jnp.float32) / (heads_i * dim_i) ** 0.5
    ctx, kl, share = attention(rotate(q, rope).reshape(kv, heads // kv, t, dh),
                               rotate(k, rope), v, q_i, k_i, w, cfg, product)
    return mm(ctx.transpose(1, 0, 2).reshape(t, heads * dh), m["Wo"]), kl, share


def block(x, p, cfg: dict, rope, index_rope, product=lambda f: f, qa=lambda x: x):
    """One decoder block over the batch ``[rows, T, hidden]``: (out, the
    indexer's loss summed over the rows, the share of tiles reached, the
    routers' term, the load). The mixer a row at a time, the experts over all
    the batch's tokens."""
    rows, t, d = x.shape
    eps = cfg["rms_norm_eps"]
    attended, kl, share = lax.map(
        lambda u: mixer(u, p["mixer"], cfg, rope, index_rope, product),
        _rms_norm(x, p["n1_g"], eps))
    x = qa(x + attended)
    y, aux, load = experts(_rms_norm(x, p["n3_g"], eps).reshape(rows * t, d), p["mlp"], cfg,
                           product)
    return qa(x + y.reshape(x.shape)), kl.sum(), share.mean(), aux, load


def loss_fn(params, state, features, labels, cfg: dict, precision: str = "float32"):
    """The loss (cross-entropy at every position, the routers' terms once a
    position and the indexers' losses, summed over a row's positions and
    averaged over the rows) and the blocks' new state. One block's input is
    kept for the backward pass at a time, one block of positions' logits at a
    time. ``precision``: ``reference_train.precision_policy``."""
    cast, product, qa = precision_policy(precision)
    params = cast(params)
    table, *blocks, norm, head = params
    rows, t = features.shape
    coef = cfg["router_aux_loss_coef"]
    sections = cfg["rope_scaling"]["mrope_section"]
    text = jnp.broadcast_to(jnp.arange(t), (len(sections), t))      # one position a token, each row
    rope = mrope_angles(text, cfg["head_dim"], cfg["rope_theta"], sections)
    rope_i = cfg["indexer_rope_dim"]
    index_rope = mrope_angles(text[:1], rope_i, cfg["rope_theta"], [rope_i // 2])
    x = qa(table["W"][features.astype(jnp.int32)])
    new_state, terms = [state[0]], 0.0
    for p in blocks:
        x, kl, share, aux, load = jax.checkpoint(
            lambda x, p: block(x, p, cfg, rope, index_rope, product, qa))(x, p)
        term, index_term = coef * t * aux, kl / rows
        terms = terms + term + index_term
        new_state.append({"loss_term": lax.stop_gradient(term),
                          "loss_term_indexer": lax.stop_gradient(index_term),
                          "moe_stats": load, "sparse_tiles_visited": share})
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
def selected_pairs(cfg: dict, traffic: dict) -> int:
    """(query, key) pairs a layer attends over, a row: ``sum_t min(t + 1, topk)``."""
    seq, topk = traffic["seq"], cfg["sa_config"]["topk"]
    if seq <= topk:
        return seq * (seq + 1) // 2
    return topk * (topk + 1) // 2 + (seq - topk) * topk


def causal_pairs(traffic: dict) -> int:
    return traffic["seq"] * (traffic["seq"] + 1) // 2


def _dot_macs_per_token(cfg: dict) -> float:
    """Multiply-accumulates a token, forward, of the products XLA runs as
    ``dot_general`` over the hidden size: the four projections, the indexer's
    three and the router of every layer, and the head."""
    d, a, kv, _, vocab = _sizes(cfg)
    heads_i, dim_i, _, _ = _indexer_sizes(cfg)
    layer = 2 * d * a + 2 * d * kv + d * (heads_i * dim_i + dim_i + heads_i) + d * cfg["router_experts"]
    return cfg["num_hidden_layers"] * layer + d * vocab


def indexer_loss_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The indexer's loss, forward and backward, over the chosen pairs: the
    attention's weights again (q k^T of every query head), the indexer's
    scores again, and the two products of their gradient (``q_i`` and
    ``k_i``). Its kernels work whole tiles of the causal triangle and repeat
    the weights and scores in the backward: those are time, not work."""
    heads_i, dim_i, _, _ = _indexer_sizes(cfg)
    per_pair = cfg["num_attention_heads"] * cfg["head_dim"] + 3 * heads_i * dim_i
    return float(cfg["num_hidden_layers"] * 2 * per_pair * selected_pairs(cfg, traffic))


def dot_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Operations one sample needs in the products XLA itself runs as
    ``dot_general``: the projections (the indexer's three among them), router
    and head, forward and backward (3 x 2 x the multiply-accumulates; no
    recomputation counted); ``conv_dot_roofline`` reads it. The indexer's
    scores for the choice, the attention over it and the indexer's loss run in
    the Pallas kernels (``indexer_flops_per_sample``,
    ``sparse_attention_flops_per_sample``, ``indexer_loss_flops_per_sample``),
    the experts' grouped products in kernel calls of the TPU compiler's own:
    all four are counted apart."""
    return 3 * 2 * float(traffic["seq"] * _dot_macs_per_token(cfg))


def indexer_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The indexer's scores for the choice, every causal pair, forward only (the
    choice has no gradient): ``2 x indexer_num_heads x indexer_head_dim`` a pair."""
    heads_i, dim_i, _, _ = _indexer_sizes(cfg)
    return float(cfg["num_hidden_layers"] * 2 * heads_i * dim_i * causal_pairs(traffic))


def indexer_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the choice's kernel moves for one sample: ``q_i`` and
    ``k_i`` in the compute type and ``w`` in float32 read once, and the choice
    written, one bit a causal pair."""
    heads_i, dim_i, _, _ = _indexer_sizes(cfg)
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    operands = traffic["seq"] * ((heads_i + 1) * dim_i * itemsize + heads_i * 4)
    return float(cfg["num_hidden_layers"] * (operands + causal_pairs(traffic) / 8))


def sparse_attention_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The attention over the chosen keys, forward + backward: 2 products
    forward (q k^T, p v) and 5 backward (the scores again, dv, dp, dq, dk) of
    ``head_dim`` multiply-accumulates for every query head and every **chosen**
    pair. The kernels work whole tiles of the causal triangle that hold a
    chosen pair, so a share over this count errs low."""
    heads, dh = cfg["num_attention_heads"], cfg["head_dim"]
    return float(cfg["num_hidden_layers"] * (2 + 5) * 2 * heads * dh * selected_pairs(cfg, traffic))


def sparse_attention_bytes_per_sample(cfg: dict, traffic: dict) -> float:
    """The least bytes the kernels move for one sample: q, o and their two
    gradients a query head, k, v and their two gradients a key-value head (read
    once a group), each once a layer, in the configuration's compute type, and
    the choice, one bit a chosen pair, read forward and backward."""
    dh = cfg["head_dim"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    width = 4 * cfg["num_attention_heads"] * dh + 4 * cfg["num_key_value_heads"] * dh
    choice = 2 * selected_pairs(cfg, traffic) / 8
    return float(cfg["num_hidden_layers"] * (traffic["seq"] * width * itemsize + choice))


def _pairs_per_token(cfg: dict) -> float:
    """The (token, expert) pairs a token is expected to have among the experts
    held here: ``num_experts_per_tok`` x held / all, under an even router."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_experts"]


def expert_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """The grouped products' operations one sample needs, forward + backward: 3
    products a pair forward (gate, up, down) and 6 backward, each ``hidden x
    moe_intermediate_size`` multiply-accumulates, for the expected held pairs."""
    d, _, _, f, _ = _sizes(cfg)
    pairs = traffic["seq"] * _pairs_per_token(cfg)
    return float(cfg["num_hidden_layers"] * 9 * 2 * d * f * pairs)


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs, whatever runs them (the
    embedding's gather, the choice's counts and the permutations of routing
    have none; ``remat``'s recomputation and the optimizer do not count).
    ``step_mfu_pct`` reads it."""
    return (dot_flops_per_sample(cfg, traffic) + indexer_flops_per_sample(cfg, traffic)
            + sparse_attention_flops_per_sample(cfg, traffic)
            + indexer_loss_flops_per_sample(cfg, traffic) + expert_flops_per_sample(cfg, traffic))
