"""Flash attention — blocked online-softmax Pallas kernels, fwd AND bwd.

Reference analog: the role cuDNN's fused multi-head attention plays for the
reference's SelfAttentionLayer (deeplearning4j-cuda LayerHelper tier); the
algorithm is FlashAttention-style blocking: the [Tq, Tk] score matrix is
never materialized in HBM — each (batch*head, q-block) program streams
k/v-blocks through VMEM maintaining running max/denominator, so HBM traffic
is O(T*D) instead of O(T^2).

Forward grid: (B*H, Tq/bq, Tk/bk) with the k-axis innermost; m/l/acc scratch
persists across the k iterations of one q-block (TPU grids execute the
minor-most dimension sequentially). The forward also emits the per-row
logsumexp, which makes the backward pass O(T*D) too: instead of
re-materializing softmax(QK^T), the backward recomputes one [bq, bk]
probability tile at a time as exp(s - lse).

Backward: one kernel, ``flash_attention_bwd``, on the grid (B*H, Tk/bk,
Tq/bq) with the q-axis innermost. Every visible tile is worked once — p, dv
+= p^T do, dp = do v^T, ds = p (dp - delta), dk += scale ds^T q, dq[rows of
the q-block] += scale ds k: the 5 products the gradients need. dk / dv
accumulate in scratch across the q-blocks of one k-block; a head's whole dq
[Tq, D] float32 is an output block that stays in VMEM across the head's whole
grid. Where that dq does not fit beside the tiles (``bwd_tiles`` decides from
the shapes: from about T = 24,576 at head 128), two calls do the same sums
in the same order, ``flash_attention_bwd_dq`` (q-blocks outer) and
``flash_attention_bwd_dkv`` (k-blocks outer), each working every tile: 7
products for the 5.

A causal ``window`` (query i sees keys j with ``0 <= i - j < window``) makes
the inner grid axis relative: it runs over the blocks a block's band can reach
and no further (``_band_blocks``), the index maps clamp to the band so that a
step outside it moves nothing, and the band's two edges are masked inside the
tiles they cross. Key-value heads shared by a group of query heads (``k``, ``v``
with fewer heads than ``q``) are read through the index map, one k/v block for
the group's query heads; dk and dv come out a query head each and the wrapper
sums them over the group. With ``window=None`` and equal head counts every call
is the program it was before either existed.

Block-level primitives ``flash_block_fwd`` / ``flash_block_bwd`` are exposed
for ring attention (parallel/sequence.py): the ring merges per-step (o, lse)
pairs online and runs the backward with the *global* lse, so sequence-
parallel long-context training inherits the same sub-quadratic memory.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl

#: ``jax.ad_checkpoint.checkpoint_name``s of the forward's two results that the
#: backward needs (``_flash_fwd``): what a layer's checkpoint has to keep for
#: ``remat`` not to run the forward kernel again (``nn/layers/base.py``)
SAVED_OUT, SAVED_LSE = "flash_attention_out", "flash_attention_lse"


def _sds(shape, dtype, vma=None):
    """ShapeDtypeStruct with varying-mesh-axes annotation when running under
    shard_map (ring attention) with VMA checking on."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _band_blocks(outer, block_outer, block_inner, n_inner, back, ahead):
    """(first, last) inner block that meets the positions ``[outer *
    block_outer - back, outer * block_outer + block_outer - 1 + ahead]``, kept
    inside the ``n_inner`` blocks there are. A q-block's keys under a causal
    window: ``back = window - 1, ahead = 0``; a k-block's queries: ``back = 0,
    ahead = window - 1``. On ints (the grid's size) and on program ids."""
    most, least = ((max, min) if isinstance(outer, int)
                   else (jnp.maximum, jnp.minimum))
    first = most(outer * block_outer - back, 0) // block_inner
    last = least((outer * block_outer + block_outer - 1 + ahead) // block_inner,
                 n_inner - 1)
    return first, last


def _band_steps(n_outer, *band):
    """The most inner blocks any outer block's band holds: the inner grid
    axis's length under a window."""
    return max(last - first + 1 for first, last in
               (_band_blocks(o, *band) for o in range(n_outer)))


def _kv_row(group):
    """Row of the flattened ``[B * Hkv, T, D]`` k / v that the grid's row ``b``
    of ``[B * H, ...]`` reads: query head h reads key-value head h // group."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, *rest, causal, scale, block_q, block_k,
                  seq_k, has_kmask, window=None):
    if has_kmask:
        km_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        km_ref = None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if window is None:
        ki = step
        # causal block skipping: a k-block whose first key is past this
        # q-block's last query contributes nothing — skip its FLOPs entirely
        # (roughly halves the causal work; the standard flash-attention
        # optimization)
        visible = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    else:
        # the inner axis runs over the band's blocks alone
        first, last = _band_blocks(qi, block_q, block_k, pl.cdiv(seq_k, block_k),
                                   window - 1, 0)
        ki = first + step
        visible = ki <= last

    @pl.when(visible)
    def _body():
        # native-dtype MXU dot with f32 accumulation (bf16 inputs run at
        # full MXU rate); the scale is applied to the f32 product
        q = q_ref[0]                                      # [bq, D]
        k = k_ref[0]                                      # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_k), 1)
        # mask the ragged tail block (out-of-bounds key columns read padding)
        s = jnp.where(kpos < seq_k, s, -jnp.inf)
        if km_ref is not None:
            # key-padding mask [1, bk]: broadcast over the q rows. The
            # existing -inf machinery (m_safe / p guard / lse=+inf) already
            # handles rows where every key is masked.
            s = jnp.where(km_ref[0] > 0, s, -jnp.inf)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
            if window is not None:
                s = jnp.where(qpos - kpos < window, s, -jnp.inf)

        m_prev = m_scr[:]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # all-masked rows keep m=-inf; guard the exp
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0]
        # zero padded tail rows of v: 0-weight x NaN-padding would poison the dot
        vrow = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vrow < seq_k, v, jnp.zeros((), v.dtype))
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(step == steps - 1)
    def _finalize():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        m_safe = jnp.where(jnp.isfinite(m_scr[:]), m_scr[:], 0.0)
        # +inf for fully-masked rows so the bwd's exp(s - lse) is exactly 0
        lse_ref[0] = jnp.where(l > 0.0, m_safe + jnp.log(jnp.maximum(l, 1e-30)),
                               jnp.inf)


def _flash_forward(q, k, v, *, causal, scale, block_q, block_k, interpret,
                   kmask=None, vma=None, window=None):
    """Returns (out [B,H,Tq,D], lse [B,H,Tq,1] float32). ``k`` / ``v`` are
    ``[B, Hkv, Tk, D]`` with ``Hkv`` dividing ``H``.

    ``kmask``: optional key-padding mask [B, Tk] (>0 = key visible) — the
    shape DL4J's per-example feature masks reduce to; blocked per (batch,
    k-block) with the batch index derived as ``b // H`` from the flattened
    batch*head grid axis, so the mask is never materialized per-head."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * Hkv, Tk, D)
    vf = v.reshape(B * Hkv, Tk, D)
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    kv_row = _kv_row(H // Hkv)
    if window is None:
        steps, k_block = nk, lambda i, j: j
    else:
        band = (bq, bk, nk, window - 1, 0)
        steps = _band_steps(nq, *band)

        def k_block(i, j):
            first, last = _band_blocks(i, *band)
            return jnp.minimum(first + j, last)

    grid = (B * H, nq, steps)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (kv_row(b), k_block(i, j), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (kv_row(b), k_block(i, j), 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [qf, kf, vf]
    if kmask is not None:
        # [B, 1, Tk] so the block's trailing dims are (1, bk) — Mosaic's
        # (8, 128)-divisibility rule applies to the last two dims and a
        # middle dim of exactly 1 satisfies the equal-to-array case
        in_specs.append(pl.BlockSpec((1, 1, bk),
                                     lambda b, i, j: (b // H, 0, k_block(i, j)),
                                     memory_space=pltpu.VMEM))
        operands.append(kmask.astype(jnp.float32).reshape(B, 1, Tk))
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, seq_k=Tk,
                          has_kmask=kmask is not None, window=window),
        name="flash_attention_fwd",
        out_shape=(_sds(qf.shape, q.dtype, vma),
                   _sds((B * H, Tq, 1), jnp.float32, vma)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, Tq, D), lse.reshape(B, H, Tq, 1)


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, lse_ref, km_ref, *, qi, ki, causal, scale,
                 block_q, block_k, seq_q, seq_k, window=None):
    """Recompute one [bq, bk] probability tile exp(s - lse), fully masked."""
    q = q_ref[0]
    k = k_ref[0]
    krow = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
    k = jnp.where(krow < seq_k, k, jnp.zeros((), k.dtype))
    s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse_ref[0])                           # lse [bq, 1]
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    valid = (qpos < seq_q) & (kpos < seq_k)
    if km_ref is not None:
        valid &= km_ref[0] > 0                            # [1, bk] broadcast
    if causal:
        valid &= qpos >= kpos
        if window is not None:
            valid &= qpos - kpos < window
    return jnp.where(valid, p, 0.0), k, valid


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                     causal, scale, block_q, block_k, seq_q, seq_k, has_kmask,
                     window=None):
    if has_kmask:
        km_ref, dq_ref, dq_scr = rest
    else:
        km_ref = None
        dq_ref, dq_scr = rest
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if window is None:
        ki = step
        visible = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    else:
        first, last = _band_blocks(qi, block_q, block_k, pl.cdiv(seq_k, block_k),
                                   window - 1, 0)
        ki = first + step
        visible = ki <= last

    @pl.when(visible)
    def _body():
        p, k, valid = _recompute_p(q_ref, k_ref, lse_ref, km_ref, qi=qi, ki=ki,
                                   causal=causal, scale=scale, block_q=block_q,
                                   block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                                   window=window)
        do = do_ref[0]
        v = v_ref[0]
        vrow = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vrow < seq_k, v, jnp.zeros((), v.dtype))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bq,bk]
        ds = jnp.where(valid, p * (dp - delta_ref[0]), 0.0)
        dq_scr[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == steps - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                      causal, scale, block_q, block_k, seq_q, seq_k,
                      has_kmask, with_dq, window=None):
    """dk and dv of one k-block, summed over its visible q-blocks; with
    ``with_dq`` dq too, from the same tiles: ``dq_ref`` is then the head's
    whole [Tq, D] float32, resident across every (ki, qi), and each tile adds
    its product to the rows of its q-block."""
    rest = list(rest)
    km_ref = rest.pop(0) if has_kmask else None
    dq_ref = rest.pop(0) if with_dq else None
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if window is None:
        # k-block 0 comes first for every q-block, and no mask hides it whole
        qi, first_sight = step, ki == 0
    else:
        # the inner axis runs over the q-blocks the k-block's band reaches; a
        # step past the band stays on its last block and works nothing
        first, last = _band_blocks(ki, block_k, block_q, pl.cdiv(seq_q, block_q),
                                   0, window - 1)
        in_band = first + step <= last
        qi = jnp.minimum(first + step, last)
        # the first k-block whose band reaches this q-block
        first_sight = in_band & (ki == _band_blocks(
            qi, block_q, block_k, pl.cdiv(seq_k, block_k), window - 1, 0)[0])

    if with_dq:
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

        @pl.when(first_sight)
        def _init_dq():
            dq_ref[0, rows, :] = jnp.zeros((block_q, dq_ref.shape[-1]),
                                           dq_ref.dtype)

    if window is not None:
        visible = in_band
    else:
        visible = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(visible)
    def _body():
        p, k, valid = _recompute_p(q_ref, k_ref, lse_ref, km_ref, qi=qi, ki=ki,
                                   causal=causal, scale=scale, block_q=block_q,
                                   block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                                   window=window)
        q = q_ref[0]
        qrow = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
        q = jnp.where(qrow < seq_q, q, jnp.zeros((), q.dtype))
        do = do_ref[0]
        do = jnp.where(qrow < seq_q, do, jnp.zeros((), do.dtype))
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bq,bk]
        ds = jnp.where(valid, p * (dp - delta_ref[0]), 0.0)
        # dk += ds^T @ q, with the chain-rule scale
        dk_scr[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            # dq[rows] += ds @ k: the dq kernel's product, in its order (ki
            # ascending for a q-block's rows)
            dq_ref[0, rows, :] += scale * jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(step == steps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward_at(tiles, q, k, v, do, lse, delta, *, causal, scale,
                       interpret, kmask=None, vma=None, window=None):
    """The backward at ``tiles`` (a ``BwdTiles``). Fused: one call on the
    dk/dv kernel's grid, every visible tile worked once. Else a dq call
    (q-blocks outer) and a dk/dv call (k-blocks outer), each working every
    visible tile, 7 products for the 5 the gradients need: for the shapes
    whose whole dq does not fit beside the tiles, and the reference the
    fused call is held to, bit for bit."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    bq = min(tiles.block_q, Tq)
    bk = min(tiles.block_k, Tk)
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    kv_row = _kv_row(H // Hkv)
    operands = [q.reshape(B * H, Tq, D), k.reshape(B * Hkv, Tk, D),
                v.reshape(B * Hkv, Tk, D), do.reshape(B * H, Tq, D),
                lse.reshape(B * H, Tq, 1), delta.reshape(B * H, Tq, 1)]
    if kmask is not None:
        operands.append(kmask.astype(jnp.float32).reshape(B, 1, Tk))
    kernel_kw = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
                     seq_q=Tq, seq_k=Tk, has_kmask=kmask is not None,
                     window=window)
    if window is None:
        q_steps, k_steps = nq, nk
        q_block = k_block = lambda outer, step: step
    else:
        q_band, k_band = (bk, bq, nq, 0, window - 1), (bq, bk, nk, window - 1, 0)
        q_steps, k_steps = _band_steps(nk, *q_band), _band_steps(nq, *k_band)

        def q_block(j, step):       # the q-blocks of k-block j's band, clamped
            first, last = _band_blocks(j, *q_band)
            return jnp.minimum(first + step, last)

        def k_block(i, step):       # the k-blocks of q-block i's band, clamped
            first, last = _band_blocks(i, *k_band)
            return jnp.minimum(first + step, last)

    def float32(rows):
        return _sds((B * H, rows, D), jnp.float32, vma)

    # k-blocks outer, q-blocks inner. k, v, dk and dv stay put along the
    # inner axis: one buffer each. With two, at (1024, 1024) the dk/dv call
    # needs 16.18-16.68 MB of scoped VMEM whenever XLA keeps none of its
    # operands or results in VMEM, over the v5e's 16 MB: it compiled or not
    # by what XLA's memory-space assignment did around it (alone at
    # T >= 8,192 it never did). The second buffer bought overlap once per
    # k-block only.
    q_spec = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, q_block(j, i), 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (kv_row(b), j, 0),
                          memory_space=pltpu.VMEM,
                          pipeline_mode=pl.Buffered(1))
    # dk and dv of one query head: the group's are summed below
    dk_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0),
                           memory_space=pltpu.VMEM,
                           pipeline_mode=pl.Buffered(1))
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, q_block(j, i), 0),
                            memory_space=pltpu.VMEM)
    in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
    if kmask is not None:
        in_specs.append(pl.BlockSpec((1, 1, bk),
                                     lambda b, j, i: (b // H, 0, j),
                                     memory_space=pltpu.VMEM))
    out_shape, out_specs = [float32(Tk)] * 2, [dk_spec] * 2
    if tiles.fused:
        # a head's whole dq, in whole q-blocks of rows so that the last
        # block's slice stays inside: a ragged tail's rows receive zeros (ds
        # is masked there) and are cut off below
        out_shape.insert(0, float32(nq * bq))
        out_specs.insert(0, pl.BlockSpec((1, nq * bq, D),
                                         lambda b, j, i: (b, 0, 0),
                                         memory_space=pltpu.VMEM,
                                         pipeline_mode=pl.Buffered(1)))
    *dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, with_dq=tiles.fused, **kernel_kw),
        name="flash_attention_bwd" if tiles.fused else "flash_attention_bwd_dkv",
        out_shape=out_shape,
        grid=(B * H, nk, q_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(*operands)

    if tiles.fused:
        dq, = dq
        if nq * bq != Tq:
            dq = dq[:, :Tq]
    else:
        q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                              memory_space=pltpu.VMEM)
        k_spec = pl.BlockSpec((1, bk, D),
                              lambda b, i, j: (kv_row(b), k_block(i, j), 0),
                              memory_space=pltpu.VMEM)
        row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                                memory_space=pltpu.VMEM)
        in_specs = [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec]
        if kmask is not None:
            in_specs.append(pl.BlockSpec((1, 1, bk),
                                         lambda b, i, j: (b // H, 0, k_block(i, j)),
                                         memory_space=pltpu.VMEM))
        dq = pl.pallas_call(
            functools.partial(_flash_dq_kernel, **kernel_kw),
            name="flash_attention_bwd_dq",
            out_shape=float32(Tq),
            grid=(B * H, nq, k_steps),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret,
        )(*operands)
    if Hkv != H:
        # a key-value head's gradient is the sum over its group of query heads
        dk, dv = (t.reshape(B, Hkv, H // Hkv, Tk, D).sum(axis=2) for t in (dk, dv))
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, Hkv, Tk, D),
            dv.reshape(B, Hkv, Tk, D))


def _flash_backward(q, k, v, do, lse, delta, *, causal, scale, block_q,
                    block_k, interpret, kmask=None, vma=None, window=None):
    """O(T*D)-memory flash backward. lse/delta: [B,H,Tq,1] float32.

    ``block_q`` / ``block_k`` are the least tiles the caller wants;
    ``bwd_tiles`` picks the tiles and the layout from the shapes. Returns
    (dq, dk, dv) in float32 (callers cast to input dtypes)."""
    tiles = bwd_tiles(block_q, block_k, q.shape[-1], q.shape[2], k.shape[2],
                      q.dtype.itemsize)
    return _flash_backward_at(tiles, q, k, v, do, lse, delta, causal=causal,
                              scale=scale, interpret=interpret, kmask=kmask,
                              vma=vma, window=window)


# --------------------------------------------------------------------------
# block-level primitives (used here and by ring attention)
# --------------------------------------------------------------------------


def flash_block_fwd(q, k, v, *, causal, scale, block_q=512, block_k=1024,
                    kmask=None, vma=None):
    """(o, lse) for one attention block pair; lse is [B,H,Tq,1] float32."""
    return _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret_mode(), kmask=kmask, vma=vma)


def flash_block_bwd(q, k, v, do, lse, delta, *, causal, scale,
                    block_q=1024, block_k=1024, kmask=None, vma=None):
    """(dq, dk, dv) float32 given the (possibly global) lse and
    delta = rowsum(do * o)."""
    return _flash_backward(q, k, v, do, lse, delta, causal=causal,
                           scale=scale, block_q=block_q, block_k=block_k,
                           interpret=interpret_mode(), kmask=kmask, vma=vma)


# --------------------------------------------------------------------------
# custom_vjp wiring
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kmask, causal, scale, block_q, block_k, window):
    out, _ = _flash_forward(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret_mode(), kmask=kmask,
                            window=window)
    return out


def _flash_fwd(q, k, v, kmask, causal, scale, block_q, block_k, window):
    """The forward under differentiation: ``out`` and the residuals the
    backward kernels read. ``out`` and ``lse`` carry names, so a
    ``jax.checkpoint`` whose policy keeps them (``checkpoint_layer``) stores
    T-sized results instead of running the T^2 forward again on the backward
    pass; q, k and v are XLA's products and are recomputed from the
    checkpoint's input as before. Under no such policy a name is the
    identity and lowers to nothing."""
    out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret_mode(), kmask=kmask,
                              window=window)
    # the named ``out`` is the primal result too: whatever reads it downstream
    # (the output projection's weight gradient) then reads the kept value
    out, lse = checkpoint_name(out, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)
    return out, (q, k, v, kmask, out, lse)


#: what one kernel may hold on the v5e with no ``vmem_limit_bytes`` raised,
#: less the half MB by which a call's need moves with what XLA places around it
VMEM_BUDGET_BYTES = (16 << 20) - (512 << 10)

#: the fused backward's tiles, the fastest first (measured on the v5e at
#: (2, 16, 4096, 128) bfloat16, causal: 3.67, 4.00 and 4.21 ms a call, the two
#: calls 5.12; (1024, 1024) needs ``vmem_limit_bytes`` raised and is 0.8 %
#: faster alone but 0.5 % slower inside a training step: PERF.md, PR 33)
FUSED_TILES = ((512, 1024), (1024, 512), (512, 512))


class BwdTiles(NamedTuple):
    block_q: int
    block_k: int
    fused: bool


def bwd_vmem_bytes(bq, bk, head_dim, itemsize, dq_rows=0):
    """Scoped VMEM a backward call over k-blocks needs at tiles (bq, bk): the
    dk/dv call, or with ``dq_rows`` the fused call that keeps a head's dq.

    What is there: q and do blocks, two buffers each; the ``lse`` and
    ``delta`` row blocks, two buffers each, a ``[bq, 1]`` float32 block padded
    to 128 lanes (0.5 MB a buffer at bq 1,024); k and v, one buffer; dk and
    dv, an output block and an accumulator each; the resident dq; a head
    dimension of 64 padded to 128 lanes; and the compiler's own [bq, bk]
    temporaries, taken as two and a half float32 tiles (its figures come to
    1.8-2.7). Against the compiler's own figures for a described v5e
    (bfloat16, head 128): fused at T 4,096 17.07 MB at (1024, 1024), 10.21 at
    (512, 1024), 9.83 at (1024, 512), 6.67 at (512, 512); fused at T 16,384
    16.96 at (512, 1024), 13.42 at (512, 512); dk/dv at (1024, 1024) 13.68
    (T 4,096) to 15.18 (T 16,384), 24.98 at (2048, 1024). This reads 17.5,
    11.0, 11.25, 7.25; 17.0, 13.25; 15.5, 28.5 (MB of 2**20 bytes)."""
    lanes = -(-head_dim // 128) * 128
    moving = 2 * 2 * bq * (lanes * itemsize + 128 * 4)
    stationary = 2 * bk * lanes * (itemsize + 2 * 4)
    return (moving + stationary + dq_rows * lanes * 4
            + 5 * bq * bk * 4 // 2)


def bwd_tiles(block_q, block_k, head_dim, seq_q, seq_k, itemsize):
    """The backward's tiles and layout, from the shapes alone.

    Fused (one call, every score tile worked once) wherever a head's whole
    dq, ``seq_q x head_dim`` float32, fits in VMEM beside the tiles at one of
    ``FUSED_TILES``: at head 128 up to T = 20,480. Past that the two calls,
    at the largest tiles that fit from (1024, 1024) up (``block_q`` /
    ``block_k`` raise the start): the backward wants larger tiles than the
    forward (measured on the v5e at T = 8,192: 1024 x 1024 is ~3x faster than
    128 x 128), and a large head dimension scales them back down. Tiles
    clamp to the sequence lengths."""
    for bq, bk in FUSED_TILES:
        bq, bk = min(bq, seq_q), min(bk, seq_k)
        dq_rows = -(-seq_q // bq) * bq
        if (bwd_vmem_bytes(bq, bk, head_dim, itemsize, dq_rows)
                <= VMEM_BUDGET_BYTES):
            return BwdTiles(bq, bk, True)
    bq, bk = max(block_q, 1024), max(block_k, 1024)
    while (bwd_vmem_bytes(bq, bk, head_dim, itemsize) > VMEM_BUDGET_BYTES
           and max(bq, bk) > 128):
        if bq >= bk:
            bq //= 2
        else:
            bk //= 2
    return BwdTiles(min(bq, seq_q), min(bk, seq_k), False)


def _flash_bwd(causal, scale, block_q, block_k, window, res, g):
    # flash backward: only [bq, bk] probability tiles are ever materialized,
    # recomputed from the saved logsumexp — HBM stays O(T*D), which is what
    # makes long-context *training* (not just inference) sub-quadratic
    q, k, v, kmask, out, lse = res
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    dq, dk, dv = _flash_backward(q, k, v, g, lse, delta, causal=causal,
                                 scale=scale, block_q=block_q,
                                 block_k=block_k, interpret=interpret_mode(),
                                 kmask=kmask, window=window)
    dkm = None if kmask is None else jnp.zeros_like(kmask)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dkm


_flash.defvjp(_flash_fwd, _flash_bwd)


def _as_key_padding(mask, batch, seq_k):
    """Reduce a broadcastable-to-[B,H,Tq,Tk] mask to a [B, Tk] key-padding
    mask, or return None (mask=None) / raise (not expressible).

    DL4J feature masks arrive as [B, Tk] per-example time masks; the layer
    tier (nn/layers/attention.py:_attn_mask) lifts them to [B,1,1,Tk]. Both
    forms — plus head/query-broadcast variants — reduce losslessly."""
    if mask is None:
        return None
    m = jnp.asarray(mask)
    if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1:
        m = m[:, 0, 0, :]
    elif m.ndim != 2:
        raise ValueError(
            f"flash_attention supports key-padding masks ([B, Tk] or "
            f"[B, 1, 1, Tk]); got mask shape {mask.shape} — the registry "
            f"predicate routes general masks to the XLA lowering")
    if m.shape[-1] != seq_k:
        raise ValueError(f"mask key axis {m.shape[-1]} != Tk {seq_k}")
    m = jnp.broadcast_to(m, (batch, seq_k))
    return m.astype(jnp.float32)


def _is_key_padding(mask, q, k):
    if mask is None:
        return True
    shp = tuple(mask.shape)
    if len(shp) == 4:
        return (shp[1] == 1 and shp[2] == 1 and shp[3] == k.shape[-2]
                and shp[0] in (1, q.shape[0]))
    return (len(shp) == 2 and shp[1] == k.shape[-2]
            and shp[0] in (1, q.shape[0]))


def flash_attention(q, k, v, *, mask=None, bias=None, scale=None,
                    causal=False, window=None, block_q: int = 512,
                    block_k: int = 1024):
    """Public entry: same signature as the XLA dot_product_attention.

    ``k`` / ``v`` may hold fewer heads than ``q`` (a divisor of its count):
    query head h reads key-value head ``h // (H // Hkv)``. ``window`` (with
    ``causal``): query i sees keys j with ``0 <= i - j < window``.

    Default tiles are the v5e sweet spot measured at T=8192 (fwd 512x1024;
    the backward's come from ``bwd_tiles``): small 128-tiles leave >2x on the
    table — grid overhead dominates; 2048-tiles exceed the 16M VMEM scoped
    limit. Tiles clamp to the actual sequence lengths for short inputs.

    ``mask`` accepts key-padding masks ([B, Tk] or the layer tier's
    [B, 1, 1, Tk]); general [Tq, Tk]-varying masks are structurally
    rejected (registry routes them to the XLA lowering)."""
    if bias is not None:
        raise ValueError(
            "flash_attention does not support additive logit biases; the "
            "registry's requires predicate routes bias calls to the XLA "
            "lowering")
    km = _as_key_padding(mask, q.shape[0], k.shape[-2])
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if window is not None and not causal:
        raise ValueError("flash_attention's window is a causal one: pass causal=True")
    return _flash(q, k, v, km, causal, float(scale), block_q, block_k,
                  None if window is None else int(window))


def _flash_requires(q, k, v, *, mask=None, scale=None, causal=False,
                    window=None, **kw):
    # structural: masks are supported iff they reduce to a key-padding mask
    # over Tk; the kernel's causal mask is start-aligned (query i sees keys
    # <= i) which only matches the XLA lowering's end-aligned tril when
    # Tq == Tk. Additive logit biases (the import optimizer's fused
    # exporter-mask form) are not expressible in the kernel — XLA lowering.
    # A window is a band under the causal diagonal; k and v may hold a
    # divisor of q's heads.
    return (kw.get("bias") is None
            and _is_key_padding(mask, q, k)
            and (not causal or q.shape[-2] == k.shape[-2])
            and (window is None or causal)
            and q.shape[1] % k.shape[1] == 0 and k.shape[1] == v.shape[1])


def _flash_applicable(q, k, v, *, mask=None, scale=None, causal=False,
                      window=None, **kw):
    # perf heuristic: long-sequence, lane/block-aligned shapes. head_dim 64
    # (the BERT-class geometry) runs natively: the QK^T contraction fills
    # half the MXU's K dimension but the kernel's win is HBM traffic, and
    # the P@V / dV contractions (over bk) stay full-rate.
    #
    # The T >= 2048 threshold is MEASURED, not assumed (r4, v5e two-point
    # A/B, BASELINE.md): at T=512/1024 XLA's fused attention wins (0.27x-
    # 0.92x for the kernel across D=64/128, fwd and train — the [T,T]
    # scores still fit on-chip and the kernel's grid overhead dominates);
    # from T=2048 the kernel wins ~1.7x and grows with T (2.7-2.9x at
    # 4096). The r1-r3 threshold of 512 was selecting the kernel in
    # regimes where it loses.
    return (q.shape[-2] >= 2048 and q.shape[-1] % 64 == 0
            and q.shape[-2] % 128 == 0 and k.shape[-2] % 128 == 0)


register_impl("dot_product_attention", platform="pallas",
              predicate=_flash_applicable, requires=_flash_requires,
              priority=1,
              scope="flash_attention")(flash_attention)
