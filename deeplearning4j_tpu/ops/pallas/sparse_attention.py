"""Learned sparse attention on the chip: the indexer's top-k choice as one
kernel, attention over the chosen keys as a forward and backward pair, and the
indexer's loss as two passes whose forward makes the gradient.

``indexer_topk`` (``ops/sparse_attention.py`` has the equations): a grid step
takes ``SELECT_ROWS`` queries. It scores them against every key their causal
reach holds, ``TILE`` keys at a time (the ``H_I`` heads' products on the
matrix unit, the ReLU and the weighted sum on the vector unit, float32), and
keeps the scores in VMEM as order-preserving int32 keys: ``[rows, T]``, 8 MB at
128 rows and 16,384 keys. The row's threshold is then built bit by bit over
those keys (32 counts over the row's reach), the cut among the ties at it by
position (``position_bits`` counts), and the choice written as ``int8`` rows.
No score leaves VMEM.

``sparse_attention``: the flash kernels' blocked online softmax
(``flash_attention.py``) over ``TILE x TILE`` tiles with the choice's tile as a
mask, causal, key-value heads read through the index map by their group of
query heads. A table of the tiles that hold a chosen pair (``visited_tiles``,
in SMEM ahead of the grid) lets a grid step skip a tile whose keys no query of
it chose, as it skips one past the diagonal; the index maps hold such a step
on the block before it, so that it moves nothing. The backward is one call on
the k-blocks' grid that keeps a head's whole ``dq`` in VMEM, as the flash
kernel's fused backward does.

``indexer_loss``: ``indexer_loss_lse`` forms the indexer's scores alone and
keeps a query's log-sum-exp over its chosen keys; ``indexer_loss_kl`` then
forms the attention's mean weights over the query heads once a tile, sums the
loss's rows and, differentiated, the gradient: the loss's cotangent is a
scalar, so the backward only scales what the forward made. The same table
skips tiles in both.

The attention forward's ``out`` and ``lse``, and the indexer loss's gradients,
carry names for ``remat`` to keep (``nn/layers/base.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl
from deeplearning4j_tpu.ops.sparse_attention import (
    INT_MIN, TILE, _order_key, position_bits, visited_tiles,
)

#: ``checkpoint_name``s of the attention forward's results that its backward reads,
#: and of the indexer loss's gradients, which its forward makes
SAVED_OUT, SAVED_LSE = "sparse_attention_out", "sparse_attention_lse"
SAVED_INDEX_GRADS = "indexer_loss_grads"
#: queries a grid step of the choice works
SELECT_ROWS = 128
#: scoped VMEM a call may take: the choice keeps a block's scores (8 MB at 16,384
#: keys), the backward a head's ``dq`` (8 MB); the v5e has 128 MiB
VMEM_LIMIT = 64 << 20
_F32 = jnp.float32


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT)


# --------------------------------------------------------------------------
# the choice
# --------------------------------------------------------------------------


def _select_kernel(q_ref, kt_ref, w_ref, sel_ref, key_scr, *, rows, tile, heads, topk, seq):
    t0 = pl.program_id(1) * rows
    reach = (t0 + rows + tile - 1) // tile          # the key tiles a query of the block reaches
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
    w = w_ref[0]                                    # [rows, heads] float32
    head = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    weights = [jnp.sum(jnp.where(head == j, w, 0.0), axis=1, keepdims=True) for j in range(heads)]

    def at(c):
        return pl.ds(pl.multiple_of(c * tile, tile), tile)

    def score(c, carry):
        kt = kt_ref[0, :, at(c)]                    # [d_I, tile]
        total = jnp.zeros((rows, tile), _F32)
        for j in range(heads):
            z = jax.lax.dot_general(q_ref[0, j], kt, (((1,), (0,)), ((), ())),
                                    preferred_element_type=_F32)
            total = total + weights[j] * jnp.maximum(z, 0.0)
        key_scr[:, at(c)] = jnp.where(c * tile + lane <= t, _order_key(total), INT_MIN)
        return carry

    jax.lax.fori_loop(0, reach, score, 0)

    def count(hit):
        def body(c, total):
            return total + jnp.sum(hit(key_scr[:, at(c)], c * tile + lane).astype(_F32),
                                   axis=1, keepdims=True)
        return jax.lax.fori_loop(0, reach, body, jnp.zeros((rows, 1), _F32))

    def threshold_bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        signed = cand ^ INT_MIN
        return jnp.where(count(lambda key, s: key >= signed) >= topk, cand, prefix)

    thr = jax.lax.fori_loop(0, 32, threshold_bit, jnp.zeros((rows, 1), jnp.int32)) ^ INT_MIN
    need = topk - count(lambda key, s: key > thr)
    bits = position_bits(seq)

    def cut_bit(i, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        n = count(lambda key, s: (key == thr) & (s < cand))
        return jnp.where(n < need, cand, cut)

    cut = jax.lax.fori_loop(0, bits, cut_bit, jnp.zeros((rows, 1), jnp.int32))

    def write(c, carry):
        key, s = key_scr[:, at(c)], c * tile + lane
        chosen = ((key > thr) | ((key == thr) & (s <= cut))) & (s <= t)
        sel_ref[0, :, at(c)] = jnp.where(chosen, 1, 0).astype(jnp.int8)
        return carry

    def blank(c, carry):
        sel_ref[0, :, at(c)] = jnp.zeros((rows, tile), jnp.int8)
        return carry

    jax.lax.fori_loop(0, reach, write, 0)
    jax.lax.fori_loop(reach, seq // tile, blank, 0)


def indexer_topk(q_i, k_i, w, *, topk: int):
    """``op("indexer_topk")`` by the kernel: ``int8 [B, T, T]``."""
    # the choice has no gradient: nothing of it may reach a tangent
    q_i, k_i, w = (jax.lax.stop_gradient(a) for a in (q_i, k_i, w))
    B, H, T, D = q_i.shape
    rows = min(SELECT_ROWS, T)
    return pl.pallas_call(
        functools.partial(_select_kernel, rows=rows, tile=TILE, heads=H, topk=topk, seq=T),
        name="indexer_topk",
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.int8),
        grid=(B, T // rows),
        in_specs=[pl.BlockSpec((1, H, rows, D), lambda b, r: (b, 0, r, 0)),
                  pl.BlockSpec((1, D, T), lambda b, r: (b, 0, 0)),
                  pl.BlockSpec((1, rows, H), lambda b, r: (b, r, 0))],
        out_specs=pl.BlockSpec((1, rows, T), lambda b, r: (b, r, 0)),
        scratch_shapes=[pltpu.VMEM((rows, T), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret_mode(),
    )(q_i, k_i.transpose(0, 2, 1), w.astype(_F32))


def _select_requires(q_i, k_i, w, *, topk: int):
    return q_i.shape[2] % TILE == 0 and q_i.dtype == k_i.dtype


# --------------------------------------------------------------------------
# attention over the choice
# --------------------------------------------------------------------------


def _tile_seen(table_ref, b, qi, ki, *, heads, n):
    """Whether tile (qi, ki) of row ``b // heads`` is worked: on or under the
    diagonal, and holding a chosen pair (square tiles: a query block's
    diagonal tile is the key block of the same index)."""
    return (ki <= qi) & (table_ref[(b // heads) * n * n + qi * n + ki] > 0)


def _fwd_kernel(table_ref, q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, tiles):
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_seen(table_ref, b, qi, ki, **tiles))
    def _body():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
        s = jnp.where(m_ref[0].astype(jnp.int32) > 0, s, -jnp.inf)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0]
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=_F32)
        m_scr[:] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        m_safe = jnp.where(jnp.isfinite(m_scr[:]), m_scr[:], 0.0)
        lse_ref[0] = jnp.where(l > 0.0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)


def _bwd_kernel(table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, m_ref,
                dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, tiles):
    b, kj, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * TILE, TILE), TILE)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # k-block 0 comes first for every q-block: its rows of dq start there
    @pl.when(kj == 0)
    def _init_dq():
        dq_ref[0, rows, :] = jnp.zeros((TILE, dq_ref.shape[-1]), dq_ref.dtype)

    @pl.when(_tile_seen(table_ref, b, qi, kj, **tiles))
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        keep = m_ref[0].astype(jnp.int32) > 0
        p = jnp.where(keep, jnp.exp(s * scale - lse_ref[0]), 0.0)
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=_F32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        ds = jnp.where(keep, p * (dp - delta_ref[0]), 0.0)
        dk_scr[:] += scale * jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                                                 preferred_element_type=_F32)
        dq_ref[0, rows, :] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=_F32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _layout(q, k):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    return B, H, T, D, Hkv, H // Hkv, dict(heads=H, n=T // TILE)


def _forward(q, k, v, selected, table, scale):
    B, H, T, D, Hkv, group, tiles = _layout(q, k)
    Dv = v.shape[-1]
    # a step past q-block i's diagonal stays on its diagonal block and moves nothing
    kv = lambda b, i, j, tab: (b // group, jnp.minimum(j, i), 0)     # noqa: E731
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, tiles=tiles),
        name="sparse_attention_fwd",
        out_shape=(jax.ShapeDtypeStruct((B * H, T, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, T, 1), _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, T // TILE, T // TILE),
            in_specs=[pl.BlockSpec((1, TILE, D), lambda b, i, j, tab: (b, i, 0)),
                      pl.BlockSpec((1, TILE, D), kv),
                      pl.BlockSpec((1, TILE, Dv), kv),
                      pl.BlockSpec((1, TILE, TILE),
                                   lambda b, i, j, tab: (b // H, i, jnp.minimum(j, i)))],
            out_specs=(pl.BlockSpec((1, TILE, Dv), lambda b, i, j, tab: (b, i, 0)),
                       pl.BlockSpec((1, TILE, 1), lambda b, i, j, tab: (b, i, 0))),
            scratch_shapes=[pltpu.VMEM((TILE, 1), _F32), pltpu.VMEM((TILE, 1), _F32),
                            pltpu.VMEM((TILE, Dv), _F32)]),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(),
    )(table.reshape(-1), q.reshape(B * H, T, D), k.reshape(B * Hkv, T, D),
      v.reshape(B * Hkv, T, Dv), selected)
    return o.reshape(B, H, T, Dv), lse.reshape(B, H, T)


def _backward(q, k, v, selected, table, o, lse, do, scale):
    B, H, T, D, Hkv, group, tiles = _layout(q, k)
    Dv = v.shape[-1]
    delta = (do.astype(_F32) * o.astype(_F32)).sum(axis=-1)
    # a step before k-block j's diagonal stays on its diagonal block and moves nothing
    qrow = lambda b, j, i, tab: (b, jnp.maximum(i, j), 0)          # noqa: E731
    kv = lambda b, j, i, tab: (b // group, j, 0)                            # noqa: E731
    mine = lambda b, j, i, tab: (b, j, 0)                                   # noqa: E731
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, tiles=tiles),
        name="sparse_attention_bwd",
        out_shape=(jax.ShapeDtypeStruct((B * H, T, D), _F32),
                   jax.ShapeDtypeStruct((B * H, T, D), _F32),
                   jax.ShapeDtypeStruct((B * H, T, Dv), _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, T // TILE, T // TILE),
            in_specs=[pl.BlockSpec((1, TILE, D), qrow),
                      pl.BlockSpec((1, TILE, D), kv, pipeline_mode=pl.Buffered(1)),
                      pl.BlockSpec((1, TILE, Dv), kv, pipeline_mode=pl.Buffered(1)),
                      pl.BlockSpec((1, TILE, Dv), qrow),
                      pl.BlockSpec((1, TILE, 1), qrow),
                      pl.BlockSpec((1, TILE, 1), qrow),
                      pl.BlockSpec((1, TILE, TILE),
                                   lambda b, j, i, tab: (b // H, jnp.maximum(i, j), j))],
            out_specs=(pl.BlockSpec((1, T, D), lambda b, j, i, tab: (b, 0, 0),
                                    pipeline_mode=pl.Buffered(1)),
                       pl.BlockSpec((1, TILE, D), mine, pipeline_mode=pl.Buffered(1)),
                       pl.BlockSpec((1, TILE, Dv), mine, pipeline_mode=pl.Buffered(1))),
            scratch_shapes=[pltpu.VMEM((TILE, D), _F32), pltpu.VMEM((TILE, Dv), _F32)]),
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret_mode(),
    )(table.reshape(-1), q.reshape(B * H, T, D), k.reshape(B * Hkv, T, D),
      v.reshape(B * Hkv, T, Dv), do.reshape(B * H, T, Dv), lse.reshape(B * H, T, 1),
      delta.reshape(B * H, T, 1), selected)
    # a key-value head's gradient is the sum over its group of query heads
    dk, dv = (t.reshape(B, Hkv, group, T, t.shape[-1]).sum(axis=2) for t in (dk, dv))
    return dq.reshape(B, H, T, D), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _attend(q, k, v, selected, table, scale):
    return _forward(q, k, v, selected, table, scale)


def _attend_fwd(q, k, v, selected, table, scale):
    o, lse = _forward(q, k, v, selected, table, scale)
    o, lse = checkpoint_name(o, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)
    return (o, lse), (q, k, v, selected, table, o, lse)


def _attend_bwd(scale, residuals, cotangents):
    q, k, v, selected, table, o, lse = residuals
    dq, dk, dv = _backward(q, k, v, selected, table, o, lse, cotangents[0], scale)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def sparse_attention(q, k, v, selected):
    """``op("sparse_attention")`` by the kernel pair."""
    return _attend(q, k, v, selected, visited_tiles(selected), q.shape[-1] ** -0.5)


def _attend_requires(q, k, v, selected):
    T = q.shape[2]
    return (T % TILE == 0 and k.shape[2] == T and q.shape[1] % k.shape[1] == 0
            and k.shape[1] == v.shape[1] and q.shape[-1] == k.shape[-1]
            and q.shape[-1] % 64 == 0 and v.shape[-1] % 64 == 0)


# --------------------------------------------------------------------------
# the indexer's loss
# --------------------------------------------------------------------------


def _mean_weights_t(q_ref, k_ref, lse_ref, *, scale, group):
    """``p^T`` ``[bk, bq]``: the mean over the query heads of the attention's
    weights on a tile's pairs, keys down and queries across, so that each
    head's log-sum-exp is a row ``[1, bq]`` of ``lse_ref``'s block."""
    heads = q_ref.shape[1]

    def head(h, total):
        s = jax.lax.dot_general(k_ref[0, h // group], q_ref[0, h], (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32)
        return total + jnp.exp(s * scale - lse_ref[0, pl.ds(h, 1), :])

    shape = (k_ref.shape[2], q_ref.shape[2])
    return jax.lax.fori_loop(0, heads, head, jnp.zeros(shape, _F32)) / heads


def _index_t(qi_ref, ki_ref, j):
    """``z_j^T`` ``[bk, bq]``: index head j's products, keys down."""
    return jax.lax.dot_general(ki_ref[0], qi_ref[0, j], (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _index_tile(qi_ref, ki_ref, w_ref, m_ref):
    """(keep, I^T) of a tile, keys down: the choice and the indexer's scores."""
    keep = m_ref[0].astype(_F32).T > 0

    def head(j, total):
        return total + w_ref[0, pl.ds(j, 1), :] * jnp.maximum(_index_t(qi_ref, ki_ref, j), 0.0)

    return keep, jax.lax.fori_loop(0, qi_ref.shape[1], head, jnp.zeros(keep.shape, _F32))


def _lse_kernel(table_ref, qi_ref, ki_ref, w_ref, m_ref, lse_i_ref, m_scr, l_scr, *, tiles):
    b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(_tile_seen(table_ref, b, qi, kj, **tiles))
    def _body():
        keep, index = _index_tile(qi_ref, ki_ref, w_ref, m_ref)
        masked = jnp.where(keep, index, -jnp.inf)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, masked.max(axis=0, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[:] = l_scr[:] * corr + jnp.sum(jnp.where(keep, jnp.exp(masked - m_safe), 0.0),
                                             axis=0, keepdims=True)
        m_scr[:] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        lse_i_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _kl_kernel(table_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, m_ref, lse_i_ref,
               kl_ref, *refs, scale, group, tiles, grads):
    # with ``grads`` the outputs dq_i, dw (both held across the key tiles) and
    # dk_i's part; then the three sums
    cross_scr, ent_scr, mass_scr = sums = refs[-3:]
    b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seen = _tile_seen(table_ref, b, qi, kj, **tiles)

    @pl.when(kj == 0)
    def _init():
        for ref in (refs[:2] if grads else ()) + sums:
            ref[...] = jnp.zeros_like(ref)

    @pl.when(seen)
    def _body():
        keep, index = _index_tile(qi_ref, ki_ref, w_ref, m_ref)
        p = jnp.where(keep, _mean_weights_t(q_ref, k_ref, lse_ref, scale=scale, group=group), 0.0)
        cross_scr[:] += jnp.sum(jnp.where(keep, p * index, 0.0), axis=0, keepdims=True)
        ent_scr[:] += jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0),
                              axis=0, keepdims=True)
        mass_scr[:] += jnp.sum(p, axis=0, keepdims=True)
        if not grads:
            return
        dqi_ref, dw_ref, dki_ref = refs[:3]
        # d L_I / d I = softmax over the chosen keys of I less the target
        d_index = jnp.where(keep, jnp.exp(index - lse_i_ref[0]) - p, 0.0)

        def head(j, dki):
            z = _index_t(qi_ref, ki_ref, j)
            dw_ref[0, pl.ds(j, 1), :] += jnp.sum(d_index * jnp.maximum(z, 0.0), axis=0,
                                                 keepdims=True)
            dz = jnp.where(z > 0, d_index * w_ref[0, pl.ds(j, 1), :], 0.0)
            ki, qj = ki_ref[0], qi_ref[0, j]
            dqi_ref[0, j] += jax.lax.dot_general(dz.astype(ki.dtype), ki, (((0,), (0,)), ((), ())),
                                                 preferred_element_type=_F32)
            return dki + jax.lax.dot_general(dz.astype(qj.dtype), qj, (((1,), (0,)), ((), ())),
                                             preferred_element_type=_F32)

        dki_ref[0, 0] = jax.lax.fori_loop(0, qi_ref.shape[1], head,
                                          jnp.zeros(dki_ref.shape[2:], _F32))

    if grads:
        @pl.when(jnp.logical_not(seen))
        def _nothing():
            refs[2][0, 0] = jnp.zeros(refs[2].shape[2:], _F32)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        # sum_s p (log p - (I - lse_I)) over the chosen keys
        kl_ref[0] = ent_scr[:] - cross_scr[:] + lse_i_ref[0] * mass_scr[:]


def _kl_specs(q, k, q_i):
    B, H, T, D = q.shape
    Hkv, HI, DI = k.shape[1], q_i.shape[1], q_i.shape[-1]
    key = lambda j, i: jnp.minimum(j, i)                       # noqa: E731
    return (B, T, H // Hkv, dict(heads=1, n=T // TILE), [
        pl.BlockSpec((1, H, TILE, D), lambda b, i, j, tab: (b, 0, i, 0)),
        pl.BlockSpec((1, Hkv, TILE, D), lambda b, i, j, tab: (b, 0, key(j, i), 0)),
        pl.BlockSpec((1, H, TILE), lambda b, i, j, tab: (b, 0, i)),
        pl.BlockSpec((1, HI, TILE, DI), lambda b, i, j, tab: (b, 0, i, 0)),
        pl.BlockSpec((1, TILE, DI), lambda b, i, j, tab: (b, key(j, i), 0)),
        pl.BlockSpec((1, HI, TILE), lambda b, i, j, tab: (b, 0, i)),
        pl.BlockSpec((1, TILE, TILE), lambda b, i, j, tab: (b, i, key(j, i)))])


def _kl_passes(q, k, lse, selected, table, q_i, k_i, w_t, scale, grads):
    """The loss's rows ``[B, 1, T]`` and, with ``grads``, its unscaled
    gradients in ``q_i``, ``k_i`` and ``w_t``, float32: the log-sum-exp pass,
    then the pass that forms the mean weights."""
    B, T, group, tiles, in_specs = _kl_specs(q, k, q_i)
    HI, DI = q_i.shape[1], q_i.shape[-1]
    nq = T // TILE
    row = pl.BlockSpec((1, 1, TILE), lambda b, i, j, tab: (b, 0, i))
    lse_i = pl.pallas_call(
        functools.partial(_lse_kernel, tiles=tiles),
        name="indexer_loss_lse",
        out_shape=jax.ShapeDtypeStruct((B, 1, T), _F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nq, T // TILE), in_specs=in_specs[3:],
            out_specs=row, scratch_shapes=[pltpu.VMEM((1, TILE), _F32)] * 2),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(),
    )(table.reshape(-1), q_i, k_i, w_t, selected)
    out_shape, out_specs = [jax.ShapeDtypeStruct((B, 1, T), _F32)], [row]
    if grads:
        out_shape += [jax.ShapeDtypeStruct((B, HI, T, DI), _F32),
                      jax.ShapeDtypeStruct((B, HI, T), _F32),
                      jax.ShapeDtypeStruct((B, nq, T, DI), _F32)]
        out_specs += [pl.BlockSpec((1, HI, TILE, DI), lambda b, i, j, tab: (b, 0, i, 0)),
                      pl.BlockSpec((1, HI, TILE), lambda b, i, j, tab: (b, 0, i)),
                      pl.BlockSpec((1, 1, TILE, DI), lambda b, i, j, tab: (b, i, j, 0))]
    out = pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, group=group, tiles=tiles, grads=grads),
        name="indexer_loss_kl",
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nq, T // TILE), in_specs=in_specs + [row],
            out_specs=out_specs, scratch_shapes=[pltpu.VMEM((1, TILE), _F32)] * 3),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(),
    )(table.reshape(-1), q, k, lse, q_i, k_i, w_t, selected, lse_i)
    if not grads:
        return out[0]
    rows, dqi, dw, dki = out
    # a key's gradient is the sum of its parts from every block of queries
    return rows, (dqi, dki.sum(axis=1), dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _kl(q, k, lse, selected, table, q_i, k_i, w_t, scale, dtype):
    return _kl_passes(q, k, lse, selected, table, q_i, k_i, w_t, scale, grads=False).sum()


def _kl_fwd(q, k, lse, selected, table, q_i, k_i, w_t, scale, dtype):
    rows, grads = _kl_passes(q, k, lse, selected, table, q_i, k_i, w_t, scale, grads=True)
    # kept under ``remat``: neither call is made again for the backward
    return rows.sum(), checkpoint_name(grads, SAVED_INDEX_GRADS)


def _kl_bwd(scale, dtype, grads, g):
    dqi, dki, dw = grads
    return (None, None, None, None, None, (g * dqi).astype(dtype), (g * dki).astype(dtype),
            g * dw)


_kl.defvjp(_kl_fwd, _kl_bwd)


def indexer_loss(q, k, lse, selected, q_i, k_i, w):
    """``op("indexer_loss")`` by two kernels over the tiles of ``TILE``
    queries by ``TILE`` keys, worked keys down (so that a head's log-sum-exp
    and weight are rows). ``indexer_loss_lse`` forms the indexer's scores
    alone and keeps a query's online log-sum-exp over its chosen keys,
    ``lse_I``. ``indexer_loss_kl`` then forms the attention's mean weights
    ``p`` from the query heads' products, the costly part, and the scores
    again, once a tile: it sums a query's cross term, entropy and mass, and,
    differentiated, the gradient ``softmax(I) - p`` over the chosen keys
    through the ReLU to ``q_i``, ``w`` (held in VMEM across the key tiles)
    and ``k_i`` (a part a block of queries, summed after). ``lse_I`` comes
    first because the softmax needs it on every tile. The loss's cotangent is
    a scalar and ``p`` a fixed target, so the gradient is made in the forward
    and the backward only scales it: the float32 gradients are the residuals,
    kept by name under ``remat`` (``d_I`` padded to 128 lanes: at 16 heads of
    64 and 16,384 tokens ``dq_i`` 134 MB a layer, ``dk_i`` 8, ``dw`` 1)."""
    q, k, lse, selected = (jax.lax.stop_gradient(a) for a in (q, k, lse, selected))
    return _kl(q, k, lse.astype(_F32), selected, visited_tiles(selected), q_i, k_i,
               w.astype(_F32).transpose(0, 2, 1), q.shape[-1] ** -0.5, q_i.dtype)


def _kl_requires(q, k, lse, selected, q_i, k_i, w):
    return (_attend_requires(q, k, k, selected) and q_i.shape[2] == q.shape[2]
            and q_i.dtype == k_i.dtype)


def _on_chip(*args, **kwargs):
    # off the chip the kernels would run in the interpreter
    return not interpret_mode()


register_impl("indexer_topk", platform="pallas", requires=_select_requires,
              predicate=_on_chip, scope="indexer_topk")(indexer_topk)
register_impl("sparse_attention", platform="pallas", requires=_attend_requires,
              predicate=_on_chip, scope="sparse_attention")(sparse_attention)
register_impl("indexer_loss", platform="pallas", requires=_kl_requires,
              predicate=_on_chip, scope="indexer_loss")(indexer_loss)
