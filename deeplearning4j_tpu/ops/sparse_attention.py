"""Learned sparse attention — DeepSeek Sparse Attention's lightning indexer, its
exact top-k choice of keys, attention over the chosen keys, and the indexer's
own loss.

No reference analog. Per row, with the indexer's queries ``q_i`` ``[H_I, T,
d_I]``, its one key head ``k_i`` ``[T, d_I]`` and its head weights ``w`` ``[T,
H_I]``:

    I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])                  s <= t
    S_t     = the ``topk`` largest I[t, s] over s <= t, a tie going to the lower s
              (every s <= t while t < topk)
    o[t, n] = sum_{s in S_t} softmax_s(q[t, n] . k[s, g(n)] / sqrt(d)) v[s, g(n)]
    L_I     = sum_t KL(p_t || softmax_{s in S_t} I[t, s]),
              p[t, s] = the mean over the query heads of the attention's weight on s

Three ops, each with a plain lowering here and, on the chip, the Pallas
kernels of ``ops/pallas/sparse_attention.py``:

- ``op("indexer_topk")(q_i, k_i, w, topk=)``: the choice, ``int8 [B, T, T]``, 1
  on a chosen (query, key) pair. No gradient: the choice is discrete. The
  plain lowering finds each row's threshold by a radix select over the
  scores' order-preserving int32 keys (``_order_key``), 32 halvings, then
  the cut among the ties at the threshold by position: the same steps the
  kernel takes, and the same choice wherever the scores are the same.
- ``op("sparse_attention")(q, k, v, selected)``: ``(o, lse)``, the attention
  over the chosen keys and each query head's log-sum-exp over them (float32,
  ``[B, H, T]``), differentiated in ``q``, ``k`` and ``v``. ``k`` and ``v``
  may hold a divisor of ``q``'s heads (query head n reads key-value head ``n //
  (H / H_kv)``).
- ``op("indexer_loss")(q, k, lse, selected, q_i, k_i, w)``: ``L_I`` summed over
  the batch's rows. Only ``q_i``, ``k_i`` and ``w`` are differentiated: the
  attention's weights are a target (``q``, ``k`` and ``lse`` are held fixed),
  and the choice has no gradient. The plain lowering works ``LOSS_BLOCK``
  queries at a time, each block a checkpoint of its own, so that the
  per-head scores of one block are all that is alive at once.

``visited_tiles`` is the kernels' table of which ``TILE x TILE`` tiles hold a
chosen pair; ``tiles_visited_share`` the share of the causal tiles that do,
the layer's gauge.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.special import xlogy

from deeplearning4j_tpu.ops.registry import register_op

#: the tiling of queries and keys (``sa_config``'s ``q_chunk_size`` /
#: ``kv_chunk_size``): the kernels' tiles and the gauge's
TILE = 512
#: queries the plain lowering of ``indexer_loss`` works at a time
LOSS_BLOCK = 128
INT_MIN = -(2 ** 31)
_F32 = jnp.float32


def _order_key(x):
    """int32 keys that order as the float32 values ``x`` do."""
    bits = jax.lax.bitcast_convert_type(x.astype(_F32), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def position_bits(seq: int) -> int:
    """Bits a position below ``seq`` takes: the cut among the ties halves that often."""
    return max(1, (seq - 1).bit_length())


def indexer_scores(q_i, k_i, w):
    """``I`` ``[B, T, T]`` float32, -inf where ``s > t``: the heads summed in
    order, as the kernel sums them."""
    z = jnp.einsum("bjtd,bsd->bjts", q_i, k_i, preferred_element_type=_F32)
    wf = w.astype(_F32)
    total = jnp.zeros(z.shape[:1] + z.shape[2:], _F32)
    for j in range(z.shape[1]):
        total = total + wf[:, :, j, None] * jnp.maximum(z[:, j], 0.0)
    t = jnp.arange(total.shape[-1])
    return jnp.where(t[None, :] <= t[:, None], total, -jnp.inf)


def select_topk(scores, topk: int):
    """``int8`` of ``scores``' shape ``[..., T, T]``: 1 where ``s`` is among the
    ``topk`` largest ``scores[t, s]`` over ``s <= t``, a tie going to the lower
    ``s``. A radix select of each row's threshold over the scores' int32 keys,
    then a halving search for the position that cuts the ties."""
    seq = scores.shape[-1]
    t = jnp.arange(seq)
    causal = t[None, :] <= t[:, None]
    key = jnp.where(causal, _order_key(scores), INT_MIN)

    def count(hit):
        return hit.sum(axis=-1, keepdims=True, dtype=jnp.int32)

    def threshold_bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count(key >= (cand ^ INT_MIN)) >= topk, cand, prefix)

    prefix = jax.lax.fori_loop(0, 32, threshold_bit,
                               jnp.zeros(scores.shape[:-1] + (1,), jnp.int32))
    thr = prefix ^ INT_MIN
    need = topk - count(key > thr)
    ties = key == thr
    bits = position_bits(seq)

    def cut_bit(i, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count(ties & (t < cand)) < need, cand, cut)

    cut = jax.lax.fori_loop(0, bits, cut_bit, jnp.zeros_like(prefix))
    chosen = ((key > thr) | (ties & (t <= cut))) & causal
    return chosen.astype(jnp.int8)


@register_op("indexer_topk", scope="indexer_topk")
def indexer_topk(q_i, k_i, w, *, topk: int):
    """The choice of keys, ``int8 [B, T, T]``: ``q_i`` ``[B, H_I, T, d_I]``,
    ``k_i`` ``[B, T, d_I]``, ``w`` ``[B, T, H_I]``."""
    q_i, k_i, w = (jax.lax.stop_gradient(a) for a in (q_i, k_i, w))
    return select_topk(indexer_scores(q_i, k_i, w), topk)


def visited_tiles(selected):
    """``int32 [B, T / TILE, T / TILE]`` (ragged edges padded): 1 where the tile
    holds a chosen pair."""
    b, tq, tk = selected.shape
    nq, nk = -(-tq // TILE), -(-tk // TILE)
    if (nq * TILE, nk * TILE) != (tq, tk):
        # (``jnp.pad`` is a jitted function: under ``remat`` its output is kept
        # as a residual of its own, a second copy of the choice, where there
        # is nothing to pad)
        selected = jnp.pad(selected, ((0, 0), (0, nq * TILE - tq), (0, nk * TILE - tk)))
    return selected.reshape(b, nq, TILE, nk, TILE).max(axis=(2, 4)).astype(jnp.int32)


def tiles_visited_share(selected):
    """Of the causal ``TILE x TILE`` tiles (those the causal triangle reaches),
    the share that holds a chosen pair, the mean over the rows."""
    table = visited_tiles(selected)
    n = table.shape[-1]
    causal = n * (n + 1) // 2
    return table.sum(axis=(1, 2)).astype(_F32).mean() / causal


def _grouped(k, heads: int):
    return k if k.shape[1] == heads else jnp.repeat(k, heads // k.shape[1], axis=1)


@register_op("sparse_attention", scope="sparse_attention")
def sparse_attention(q, k, v, selected):
    """``(o [B, H, T, Dv], lse [B, H, T] float32)``: softmax attention of each
    query over its chosen keys (``selected`` ``[B, T, T]``, every row with at
    least one), scores scaled by ``head_dim^-1/2``."""
    scale = q.shape[-1] ** -0.5
    heads = q.shape[1]
    k, v = _grouped(k, heads), _grouped(v, heads)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, preferred_element_type=_F32) * scale
    s = jnp.where(selected[:, None] > 0, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v), lse


def _block_kl(q, k, lse, selected, q_i, k_i, w, scale):
    """``L_I`` of one block of queries: ``q`` ``[B, H, Tb, D]``, ``lse`` ``[B, H,
    Tb]``, ``selected`` ``[B, Tb, T]``, ``q_i`` ``[B, H_I, Tb, d_I]``, ``w``
    ``[B, Tb, H_I]``; ``k`` and ``k_i`` every key's."""
    keep = selected > 0
    s = jnp.einsum("bhtd,bhsd->bhts", q, _grouped(k, q.shape[1]),
                   preferred_element_type=_F32) * scale
    p = jnp.where(keep[:, None], jnp.exp(s - lse[..., None]), 0.0).mean(axis=1)
    z = jnp.einsum("bjtd,bsd->bjts", q_i, k_i, preferred_element_type=_F32)
    index = jnp.einsum("bjts,btj->bts", jnp.maximum(z, 0.0), w.astype(_F32))
    index = jnp.where(keep, index, -jnp.inf)
    log_q = index - jax.nn.logsumexp(index, axis=-1, keepdims=True)
    return jnp.where(keep, xlogy(p, p) - p * log_q, 0.0).sum()


@register_op("indexer_loss")
def indexer_loss(q, k, lse, selected, q_i, k_i, w):
    """``L_I`` summed over the batch's rows and positions (float32)."""
    scale = q.shape[-1] ** -0.5
    q, k, lse, selected = (jax.lax.stop_gradient(a) for a in (q, k, lse, selected))
    seq = q.shape[2]
    block = LOSS_BLOCK if seq % LOSS_BLOCK == 0 else seq
    if block == seq:
        return _block_kl(q, k, lse, selected, q_i, k_i, w, scale)

    @jax.checkpoint
    def one(start):
        rows = lambda a, axis: jax.lax.dynamic_slice_in_dim(a, start, block, axis)  # noqa: E731
        return _block_kl(rows(q, 2), k, rows(lse, 2), rows(selected, 1), rows(q_i, 2), k_i,
                         rows(w, 1), scale)

    return jax.lax.map(one, jnp.arange(0, seq, block)).sum()
