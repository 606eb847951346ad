"""Sequence / context parallelism — ring attention.

Reference analog: NONE — the reference's only long-sequence mechanism is
truncated BPTT on one device (MultiLayerConfiguration tBPTTLength; SURVEY.md
§5 "Long-context"). This is net-new capability, designed TPU-first: the
sequence axis is sharded over the mesh's "seq" axis; each device holds a
query block and rotates K/V blocks around the ICI ring with ppermute while
accumulating attention online (flash-attention-style running max/denominator),
so peak memory is O(T/n) and the T^2 work is evenly spread.

Two local cores, selected per shape:
- the Pallas flash kernel path (``_ring_flash``): each ring step runs the
  blocked flash forward on its current K/V block and merges (o, lse) pairs
  online; its custom_vjp re-rotates K/V around the ring while dk/dv partial
  gradients travel WITH their blocks, so backward memory is O(T/n * D) per
  device too — long-context *training* stays sub-quadratic end to end.
- a plain-XLA einsum path for small/unaligned shapes (materializes the local
  [Tq, Tk] tile per step; fine at toy scale, and exercised by the same
  parity tests).

Also provides Ulysses-style head-scatter attention (all_to_all swapping the
shard axis from sequence to heads), the bandwidth-cheaper alternative when
n_heads >= n_devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

_pvary = functools.partial(lax.pcast, to="varying")


def _ring_attention_local(q, k, v, kmask=None, *, axis, causal, scale):
    """Per-device body. q/k/v local blocks [B, H, Tq, D] / [B, H, Tk, D];
    ``kmask`` an optional key-padding shard [B, Tk] (>0 = visible) that
    rotates around the ring WITH its K/V block (r4)."""
    axis_size = lax.psum(1, axis)
    my_idx = lax.axis_index(axis)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    neg = jnp.finfo(jnp.float32).min

    q32 = q.astype(jnp.float32) * scale
    # The accumulators become device-varying inside the loop (they depend on
    # my_idx via the causal mask and on the rotating K/V); mark them varying
    # up front so the fori_loop carry types are stable.
    m0 = _pvary(jnp.full((B, H, Tq, 1), neg, jnp.float32), (axis,))
    l0 = _pvary(jnp.zeros((B, H, Tq, 1), jnp.float32), (axis,))
    o0 = _pvary(jnp.zeros((B, H, Tq, D), jnp.float32), (axis,))
    qpos = my_idx * Tq + jnp.arange(Tq)
    # kmask is a TRACE-time branch: without a mask the carry omits the mask
    # shard entirely (no dead ppermute per ring step)
    has_km = kmask is not None

    def body(i, carry):
        if has_km:
            m, l, o, k, v, km = carry
        else:
            m, l, o, k, v = carry
        src = (my_idx - i) % axis_size  # which global block we currently hold
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32, k.astype(jnp.float32))
        if causal:
            kpos = src * Tk + jnp.arange(Tk)
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask, logits, neg)
        if has_km:
            logits = jnp.where(km[:, None, None, :] > 0, logits, neg)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1, keepdims=True)
        o = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k = lax.ppermute(k, axis, perm)
        v = lax.ppermute(v, axis, perm)
        if has_km:
            km = lax.ppermute(km, axis, perm)
            return m_new, l, o, k, v, km
        return m_new, l, o, k, v

    carry0 = (m0, l0, o0, k, v)
    if has_km:
        carry0 = carry0 + (kmask.astype(jnp.float32),)
    out = lax.fori_loop(0, axis_size, body, carry0)
    l, o = out[1], out[2]
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


# --------------------------------------------------------------------------
# flash-kernel ring core (sub-quadratic fwd AND bwd)
# --------------------------------------------------------------------------


def _rotate(x, axis, axis_size):
    return lax.ppermute(x, axis, [(j, (j + 1) % axis_size) for j in range(axis_size)])


def _merge_lse(o, lse, o_i, lse_i):
    """Combine two softmax partial results normalized with their own lse.

    The flash forward kernel emits lse=+inf for fully-masked rows (so its
    backward's exp(s - lse) is exactly 0). For the MERGE contract +inf is
    poison — logaddexp(x, +inf)=+inf would zero both weights and discard the
    other side's accumulated rows — so normalize the sentinel to -inf ("this
    side contributes nothing") before merging. Relevant for cross-attention
    or unequal q/k lengths where a ring step can see fully-masked rows."""
    lse = jnp.where(jnp.isposinf(lse), -jnp.inf, lse)
    lse_i = jnp.where(jnp.isposinf(lse_i), -jnp.inf, lse_i)
    lse_new = jnp.logaddexp(lse, lse_i)
    w_old = jnp.where(jnp.isfinite(lse), jnp.exp(lse - lse_new), 0.0)
    w_new = jnp.where(jnp.isfinite(lse_i), jnp.exp(lse_i - lse_new), 0.0)
    return o * w_old + o_i.astype(jnp.float32) * w_new, lse_new


def _ring_flash_fwd_impl(q, k, v, kmask, axis, causal, scale, block_q,
                         block_k):
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_block_fwd

    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    B, H, Tq, D = q.shape
    o = jnp.zeros((B, H, Tq, D), jnp.float32)
    lse = jnp.full((B, H, Tq, 1), -jnp.inf, jnp.float32)
    o, lse = _pvary(o, (axis,)), _pvary(lse, (axis,))
    k_cur, v_cur = k, v
    km_cur = None if kmask is None else kmask.astype(jnp.float32)
    blk = functools.partial(flash_block_fwd, scale=scale,
                            block_q=block_q, block_k=block_k, vma=(axis,))
    for i in range(n):
        if i == 0:
            # the diagonal block: start-aligned causal mask is exact here
            o_i, lse_i = blk(q, k_cur, v_cur, causal=causal, kmask=km_cur)
        elif causal:
            src = (my - i) % n  # which global K/V block we currently hold
            o_i, lse_i = lax.cond(
                src < my,
                lambda kv: blk(q, kv[0], kv[1], causal=False, kmask=kv[2]),
                lambda kv: (jnp.zeros((B, H, Tq, D), q.dtype),
                            jnp.full((B, H, Tq, 1), -jnp.inf, jnp.float32)),
                (k_cur, v_cur, km_cur))
        else:
            o_i, lse_i = blk(q, k_cur, v_cur, causal=False, kmask=km_cur)
        # a fully-masked step emits lse=+inf; _merge_lse normalizes it to
        # "contributes nothing", so padded-out blocks drop out exactly
        o, lse = _merge_lse(o, lse, o_i, lse_i)
        if i < n - 1:
            k_cur = _rotate(k_cur, axis, n)
            v_cur = _rotate(v_cur, axis, n)
            if km_cur is not None:
                km_cur = _rotate(km_cur, axis, n)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_flash(q, k, v, kmask, axis, causal, scale, block_q, block_k):
    return _ring_flash_fwd_impl(q, k, v, kmask, axis, causal, scale,
                                block_q, block_k)[0]


def _ring_flash_vjp_fwd(q, k, v, kmask, axis, causal, scale, block_q,
                        block_k):
    o, lse = _ring_flash_fwd_impl(q, k, v, kmask, axis, causal, scale,
                                  block_q, block_k)
    return o, (q, k, v, kmask, o, lse)


def _ring_flash_vjp_bwd(axis, causal, scale, block_q, block_k, res, do):
    """True ring backward: K/V (and the key-padding mask shard) re-rotate
    while each block's dk/dv partial travels WITH it; after n steps every
    carry is home with contributions from every device. Per-device memory
    stays O(Tq/n * D)."""
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_block_bwd

    q, k, v, kmask, o, lse = res
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    dq = _pvary(jnp.zeros(q.shape, jnp.float32), (axis,))
    dk_carry = _pvary(jnp.zeros(k.shape, jnp.float32), (axis,))
    dv_carry = _pvary(jnp.zeros(v.shape, jnp.float32), (axis,))
    k_cur, v_cur = k, v
    km_cur = None if kmask is None else kmask.astype(jnp.float32)
    # the backward's own tiles come from the block's shapes (bwd_tiles)
    blk = functools.partial(flash_block_bwd, scale=scale, block_q=block_q,
                            block_k=block_k, vma=(axis,))
    for i in range(n):
        if i == 0:
            dq_i, dk_i, dv_i = blk(q, k_cur, v_cur, do, lse, delta,
                                   causal=causal, kmask=km_cur)
        elif causal:
            src = (my - i) % n
            dq_i, dk_i, dv_i = lax.cond(
                src < my,
                lambda kv: blk(q, kv[0], kv[1], do, lse, delta,
                               causal=False, kmask=kv[2]),
                lambda kv: (jnp.zeros(q.shape, jnp.float32),
                            jnp.zeros(k.shape, jnp.float32),
                            jnp.zeros(v.shape, jnp.float32)),
                (k_cur, v_cur, km_cur))
        else:
            dq_i, dk_i, dv_i = blk(q, k_cur, v_cur, do, lse, delta,
                                   causal=False, kmask=km_cur)
        dq = dq + dq_i
        dk_carry = dk_carry + dk_i
        dv_carry = dv_carry + dv_i
        # the carries rotate every step INCLUDING the last — that final hop
        # lands each block's accumulated gradient back on its home device;
        # k/v themselves are dead after the last compute, so skip their hop
        if i < n - 1:
            k_cur = _rotate(k_cur, axis, n)
            v_cur = _rotate(v_cur, axis, n)
            if km_cur is not None:
                km_cur = _rotate(km_cur, axis, n)
        dk_carry = _rotate(dk_carry, axis, n)
        dv_carry = _rotate(dv_carry, axis, n)
    dkm = None if kmask is None else jnp.zeros_like(kmask)
    return (dq.astype(q.dtype), dk_carry.astype(k.dtype),
            dv_carry.astype(v.dtype), dkm)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def _ring_flash_local(q, k, v, kmask=None, *, axis, causal, scale,
                      block_q=512, block_k=1024):
    return _ring_flash(q, k, v, kmask, axis, causal, scale,
                       min(block_q, q.shape[2]), min(block_k, k.shape[2]))


def _flash_core_ok(head_dim: int, t_local: int) -> bool:
    """Mosaic wants lane-aligned head_dim; sublane-aligned local seq."""
    return head_dim % 128 == 0 and t_local % 8 == 0 and t_local >= 8


def _select_ring_core(head_dim: int, t_local: int):
    """(local_fn, check_vma) for the ring attention core — single decision
    point shared by ring_attention and sequence_parallel_encoder. The Pallas
    core needs the VMA checker off (pallas_call in interpret mode can't
    satisfy it yet — jax hlo_interpreter dynamic_slice limitation); the
    einsum path keeps full checking."""
    if _flash_core_ok(head_dim, t_local):
        return _ring_flash_local, False
    return _ring_attention_local, True


def ring_attention(q, k, v, mesh, *, axis: str = "seq", causal: bool = False,
                   scale: float | None = None, impl: str | None = None,
                   mask=None):
    """Ring attention over a mesh axis.

    q/k/v: [B, H, T, D] with T sharded over ``axis`` (logically; pass the
    full array — shard_map splits it). Returns [B, H, T, D] sharded the same.

    impl: None (auto: flash kernel core when shapes are TPU-aligned),
    "flash", or "einsum".

    mask (r4): optional key-padding mask [B, T] (>0 = key visible), sharded
    over ``axis`` like the keys; each shard travels the ring WITH its K/V
    block, so padded-batch long-context training works without ever
    materializing a [T, T] mask. Rows whose keys are ALL masked follow the
    local core's convention (flash core: exact zeros; einsum core: uniform
    attention, matching the plain XLA lowering)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    size = mesh.shape[axis]
    if impl is None:
        local, check_vma = _select_ring_core(q.shape[-1], q.shape[2] // size)
    elif impl == "flash":
        if not _flash_core_ok(q.shape[-1], q.shape[2] // size):
            raise ValueError(
                "ring_attention(impl='flash') needs head_dim % 128 == 0 and "
                f"local seq % 8 == 0; got head_dim={q.shape[-1]}, "
                f"T_local={q.shape[2] // size} — use impl='einsum' or pad")
        local, check_vma = _ring_flash_local, False
    else:
        local, check_vma = _ring_attention_local, True
    body = functools.partial(local, axis=axis, causal=causal, scale=scale)
    if mask is None:
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, axis, None),) * 3,
            out_specs=P(None, None, axis, None),
            check_vma=check_vma,
        )
        return fn(q, k, v)
    if tuple(mask.shape) != (q.shape[0], k.shape[2]):
        raise ValueError(f"ring_attention mask must be a key-padding mask "
                         f"[B, T] = {(q.shape[0], k.shape[2])}; got "
                         f"{tuple(mask.shape)}")
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3 + (P(None, axis),),
        out_specs=P(None, None, axis, None),
        check_vma=check_vma,
    )
    return fn(q, k, v, mask)


def _ulysses_local(q, k, v, *, axis, causal, scale):
    """Ulysses: all_to_all turns seq-sharded [B,H,Tl,D] into head-sharded
    [B,Hl,T,D], runs full-sequence attention locally, then swaps back."""
    # gather sequence, scatter heads
    q = lax.all_to_all(q, axis, split_axis=1, concat_axis=2, tiled=True)
    k = lax.all_to_all(k, axis, split_axis=1, concat_axis=2, tiled=True)
    v = lax.all_to_all(v, axis, split_axis=1, concat_axis=2, tiled=True)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        T = logits.shape[-1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)
    # scatter sequence back, gather heads
    return lax.all_to_all(o, axis, split_axis=2, concat_axis=1, tiled=True)


def ulysses_attention(q, k, v, mesh, *, axis: str = "seq", causal: bool = False,
                      scale: float | None = None):
    """Ulysses-style sequence parallelism (head all-to-all). Requires
    n_heads % axis_size == 0."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    fn = shard_map(
        functools.partial(_ulysses_local, axis=axis, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
    )
    return fn(q, k, v)


def _ulysses_causal_guard(n_heads, mesh, axis):
    size = mesh.shape[axis]
    if n_heads % size:
        raise ValueError(f"ulysses needs n_heads ({n_heads}) divisible by "
                         f"mesh axis '{axis}' size ({size})")


def sequence_parallel_encoder(params, x, mesh, *, n_heads: int,
                              axis: str = "seq", causal: bool = False,
                              impl: str = "ring", activation: str = "gelu"):
    """TransformerEncoderLayer forward with activations sequence-sharded.

    Takes the SAME param dict as nn.layers.attention.TransformerEncoderLayer
    (pre-norm form) and produces identical outputs, but every activation is
    sharded [B, T/n, D] over the mesh's ``axis``: LN, QKV/output projections
    and the MLP are per-token (no communication), and only the attention core
    communicates — ppermute KV rotation (impl="ring") or head all-to-all
    (impl="ulysses"). This is the long-context training path the reference
    lacks entirely (its only tool is single-device truncated BPTT,
    MultiLayerConfiguration.tBPTTLength — SURVEY.md §5).

    x: [B, T, D] with T divisible by the axis size. Returns [B, T, D].

    impl="zigzag" (causal only) uses the load-balanced zig-zag ring core
    and runs ENTIRELY in the permuted domain: pass x already permuted with
    ``zigzag_shard(x, mesh, seq_axis=1)`` (done ONCE per run, together with
    labels/masks); the output comes back zig-zag-permuted too. All
    per-token math in the block is order-agnostic, so stacking layers and
    computing per-token losses needs no unpermute — that is the "at scale"
    path with zero per-step gathers.
    """
    from deeplearning4j_tpu.nn.layers.base import resolve_activation

    act = resolve_activation(activation)
    if impl == "ulysses":
        _ulysses_causal_guard(n_heads, mesh, axis)
    elif impl == "zigzag":
        if not causal:
            raise ValueError("impl='zigzag' is the load-balanced CAUSAL "
                             "ring; use impl='ring' for non-causal")
        _zigzag_guard(x.shape[1], mesh.shape[axis], x.shape[-1] // n_heads)
    elif impl != "ring":
        raise ValueError(
            f"impl must be 'ring', 'zigzag' or 'ulysses', got {impl!r}")
    # decided here (not in the traced body) so check_vma below can match
    if impl == "ring":
        _ring_local, _check_vma = _select_ring_core(
            x.shape[-1] // n_heads, x.shape[1] // mesh.shape[axis])
    elif impl == "zigzag":
        def _ring_local(q, k, v, *, axis, causal, scale):
            return _ring_zigzag_local(q, k, v, axis=axis, scale=scale)

        _check_vma = False
    else:
        _ring_local, _check_vma = None, True

    def _ln(h, g, b):
        m = h.mean(-1, keepdims=True)
        v = h.var(-1, keepdims=True)
        return (h - m) * jax.lax.rsqrt(v + 1e-5) * g + b

    def block(p, xl):
        B, Tl, D = xl.shape
        dh = D // n_heads
        scale = 1.0 / (dh ** 0.5)

        h = _ln(xl, p["ln1_g"], p["ln1_b"])
        # per-token projections on the local shard
        def heads(w, b):
            y = h @ w + b
            return y.reshape(B, Tl, n_heads, dh).transpose(0, 2, 1, 3)

        q = heads(p["Wq"], p["bq"])
        k = heads(p["Wk"], p["bk"])
        v = heads(p["Wv"], p["bv"])
        local = _ulysses_local if impl == "ulysses" else _ring_local
        a = local(q, k, v, axis=axis, causal=causal, scale=scale)
        a = a.transpose(0, 2, 1, 3).reshape(B, Tl, D) @ p["Wo"] + p["bo"]
        xl = xl + a

        h = _ln(xl, p["ln2_g"], p["ln2_b"])
        m = act(h @ p["W1"] + p["b1"]) @ p["W2"] + p["b2"]
        return xl + m

    fn = shard_map(
        block, mesh=mesh,
        in_specs=(P(), P(None, axis, None)),
        out_specs=P(None, axis, None),
        check_vma=_check_vma,
    )
    return fn(params, x)


# --------------------------------------------------------------------------
# zig-zag (load-balanced) causal ring attention
# --------------------------------------------------------------------------
#
# With contiguous sequence sharding, causal masking makes the ring
# triangular: device 0 attends 1 block, device n-1 attends n — wall-clock is
# set by the last device while the rest idle. Zig-zag sharding gives every
# device TWO stripes, one from each end (device i holds stripes i and
# 2n-1-i of 2n), which balances the visible work exactly: at t=0 each
# device runs two diagonal tiles + one full tile; at every later step each
# device runs exactly two full tiles (the pair (b_i, a_s) is always
# visible, and exactly one of (a_i, a_s) / (b_i, b_s) is, depending on the
# sign of i - s). The flash kernels stay the per-tile core, and the
# backward rotates dk/dv carries with their blocks exactly like the
# contiguous ring.


def zigzag_permutation(T: int, n: int):
    """(perm, inverse): sequence index permutation placing stripes
    [i, 2n-1-i] on device i. T must divide into 2n stripes."""
    if T % (2 * n):
        raise ValueError(f"zigzag needs T ({T}) divisible by 2*{n} stripes")
    S = T // (2 * n)
    order = []
    for i in range(n):
        order.extend(range(i * S, (i + 1) * S))
        order.extend(range((2 * n - 1 - i) * S, (2 * n - i) * S))
    perm = np.asarray(order)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(T)
    return perm, inv


def _zz_none(B, H, S, D):
    return (jnp.zeros((B, H, S, D), jnp.float32),
            jnp.full((B, H, S, 1), -jnp.inf, jnp.float32))


def _ring_zigzag_fwd_impl(q, k, v, axis, scale, block_q, block_k):
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_block_fwd

    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    B, H, Tl, D = q.shape
    S = Tl // 2
    blk = functools.partial(flash_block_fwd, scale=scale,
                            block_q=block_q, block_k=block_k, vma=(axis,))
    qa, qb = q[:, :, :S], q[:, :, S:]
    ka, kb = k[:, :, :S], k[:, :, S:]
    va, vb = v[:, :, :S], v[:, :, S:]

    # t = 0: (a,a) diag, (b,b) diag, (b,a) full — all static
    oa, la = blk(qa, ka, va, causal=True)
    oa, la = oa.astype(jnp.float32), la
    ob1, lb1 = blk(qb, kb, vb, causal=True)
    ob2, lb2 = blk(qb, ka, va, causal=False)
    ob, lb = _merge_lse(ob1.astype(jnp.float32), lb1, ob2, lb2)

    k_cur, v_cur = k, v
    for t in range(1, n):
        k_cur = _rotate(k_cur, axis, n)
        v_cur = _rotate(v_cur, axis, n)
        kac, kbc = k_cur[:, :, :S], k_cur[:, :, S:]
        vac, vbc = v_cur[:, :, :S], v_cur[:, :, S:]
        s = (my - t) % n
        # always visible: (b_i, a_s) full
        ob_c, lb_c = blk(qb, kac, vac, causal=False)
        ob, lb = _merge_lse(ob, lb, ob_c, lb_c)
        # exactly one of (a_i, a_s) / (b_i, b_s), by sign of i - s
        def _f32(pair):
            o, l = pair
            return o.astype(jnp.float32), l  # match the dead branch's dtype

        contrib = lax.cond(
            my > s,
            lambda kv: (*_f32(blk(qa, kv[0], kv[1], causal=False)),
                        *_zz_none(B, H, S, D)),
            lambda kv: (*_zz_none(B, H, S, D),
                        *_f32(blk(qb, kv[2], kv[3], causal=False))),
            (kac, vac, kbc, vbc))
        oa_c, la_c, ob2_c, lb2_c = contrib
        oa, la = _merge_lse(oa, la, oa_c, la_c)
        ob, lb = _merge_lse(ob, lb, ob2_c, lb2_c)
    out = jnp.concatenate([oa, ob], axis=2).astype(q.dtype)
    lse = jnp.concatenate([la, lb], axis=2)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_zigzag(q, k, v, axis, scale, block_q, block_k):
    return _ring_zigzag_fwd_impl(q, k, v, axis, scale, block_q, block_k)[0]


def _ring_zigzag_vjp_fwd(q, k, v, axis, scale, block_q, block_k):
    o, lse = _ring_zigzag_fwd_impl(q, k, v, axis, scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _ring_zigzag_vjp_bwd(axis, scale, block_q, block_k, res, do):
    from deeplearning4j_tpu.ops.pallas.flash_attention import flash_block_bwd

    q, k, v, o, lse = res
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    B, H, Tl, D = q.shape
    S = Tl // 2
    blk = functools.partial(flash_block_bwd, scale=scale, block_q=block_q,
                            block_k=block_k, vma=(axis,))
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1,
                                                                 keepdims=True)
    qa, qb = q[:, :, :S], q[:, :, S:]
    doa, dob = do[:, :, :S], do[:, :, S:]
    la, lb = lse[:, :, :S], lse[:, :, S:]
    da, db = delta[:, :, :S], delta[:, :, S:]

    zq = jnp.zeros((B, H, S, D), jnp.float32)
    dqa = _pvary(zq, (axis,))
    dqb = _pvary(zq, (axis,))
    dk_carry = _pvary(jnp.zeros(k.shape, jnp.float32), (axis,))
    dv_carry = _pvary(jnp.zeros(v.shape, jnp.float32), (axis,))
    k_cur, v_cur = k, v
    for t in range(n):
        kac, kbc = k_cur[:, :, :S], k_cur[:, :, S:]
        vac, vbc = v_cur[:, :, :S], v_cur[:, :, S:]
        dka = jnp.zeros((B, H, S, D), jnp.float32)
        dva = jnp.zeros((B, H, S, D), jnp.float32)
        dkb = jnp.zeros((B, H, S, D), jnp.float32)
        dvb = jnp.zeros((B, H, S, D), jnp.float32)
        if t == 0:
            g1 = blk(qa, kac, vac, doa, la, da, causal=True)
            dqa, dka, dva = dqa + g1[0], dka + g1[1], dva + g1[2]
            g2 = blk(qb, kbc, vbc, dob, lb, db, causal=True)
            dqb, dkb, dvb = dqb + g2[0], dkb + g2[1], dvb + g2[2]
            g3 = blk(qb, kac, vac, dob, lb, db, causal=False)
            dqb, dka, dva = dqb + g3[0], dka + g3[1], dva + g3[2]
        else:
            s = (my - t) % n
            g3 = blk(qb, kac, vac, dob, lb, db, causal=False)
            dqb, dka, dva = dqb + g3[0], dka + g3[1], dva + g3[2]
            ga, gb = lax.cond(
                my > s,
                lambda kv: (blk(qa, kv[0], kv[1], doa, la, da, causal=False),
                            (zq, zq, zq)),
                lambda kv: ((zq, zq, zq),
                            blk(qb, kv[2], kv[3], dob, lb, db, causal=False)),
                (kac, vac, kbc, vbc))
            dqa, dka, dva = dqa + ga[0], dka + ga[1], dva + ga[2]
            dqb, dkb, dvb = dqb + gb[0], dkb + gb[1], dvb + gb[2]
        dk_carry = dk_carry + jnp.concatenate([dka, dkb], axis=2)
        dv_carry = dv_carry + jnp.concatenate([dva, dvb], axis=2)
        # carries rotate with K/V every step incl. the last (lands home);
        # K/V skip the final dead hop
        if t < n - 1:
            k_cur = _rotate(k_cur, axis, n)
            v_cur = _rotate(v_cur, axis, n)
        dk_carry = _rotate(dk_carry, axis, n)
        dv_carry = _rotate(dv_carry, axis, n)
    dq = jnp.concatenate([dqa, dqb], axis=2)
    return (dq.astype(q.dtype), dk_carry.astype(k.dtype),
            dv_carry.astype(v.dtype))


_ring_zigzag.defvjp(_ring_zigzag_vjp_fwd, _ring_zigzag_vjp_bwd)


def _ring_zigzag_local(q, k, v, *, axis, scale, block_q=512, block_k=1024):
    return _ring_zigzag(q, k, v, axis, scale,
                        min(block_q, q.shape[2] // 2),
                        min(block_k, k.shape[2] // 2))


def zigzag_shard(x, mesh, *, seq_axis: int, axis: str = "seq"):
    """Apply the zig-zag stripe permutation along ``seq_axis`` ONCE.

    ``seq_axis`` is intentionally required: the permutation silently
    "succeeds" on any axis whose length divides into 2n stripes, so a
    defaulted axis on a [B, T, D] vs [B, H, T, D] layout mix-up would
    corrupt data instead of erroring (2 for q/k/v, 1 for encoder inputs).

    The at-scale usage of the balanced causal ring: permute inputs (and
    anything position-aligned with them — labels, masks, position ids) one
    time up front, run N train steps / N layers on permuted data via
    ``ring_attention_zigzag(pre_permuted=True)`` or
    ``sequence_parallel_encoder(impl="zigzag")``, and ``zigzag_unshard``
    only what leaves the permuted domain. One O(T) gather per RUN instead
    of three gathers + one scatter per CALL. Position-wise computations
    (LN, projections, MLP, per-token losses) are order-agnostic, so entire
    transformer stacks run inside the permuted domain unchanged."""
    n = mesh.shape[axis]
    perm, _ = zigzag_permutation(x.shape[seq_axis], n)
    return jnp.take(x, perm, axis=seq_axis)


def zigzag_unshard(x, mesh, *, seq_axis: int, axis: str = "seq"):
    """Inverse of zigzag_shard (restore natural sequence order)."""
    n = mesh.shape[axis]
    _, inv = zigzag_permutation(x.shape[seq_axis], n)
    return jnp.take(x, inv, axis=seq_axis)


def _zigzag_guard(T, n, head_dim):
    if T % (2 * n):
        raise ValueError(f"zigzag needs T ({T}) divisible by 2*{n} stripes")
    if not _flash_core_ok(head_dim, T // (2 * n)):
        raise ValueError("zigzag ring runs on the flash core: needs "
                         "head_dim % 128 == 0 and stripe length % 8 == 0")


def ring_attention_zigzag(q, k, v, mesh, *, axis: str = "seq",
                          scale: float | None = None,
                          pre_permuted: bool = False):
    """Load-balanced CAUSAL ring attention (zig-zag stripe sharding).

    By default takes/returns NORMAL sequence order ([B, H, T, D]) and
    applies the stripe permutation internally (one gather per operand per
    call). At scale, permute once with ``zigzag_shard`` and pass
    ``pre_permuted=True``: inputs are then consumed — and the output
    returned — in zig-zag order with no per-call permutation at all.
    Requires T % (2 * mesh axis size) == 0 and the flash kernel's alignment
    (head_dim % 128 == 0)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n = mesh.shape[axis]
    T = q.shape[2]
    _zigzag_guard(T, n, q.shape[-1])
    fn = shard_map(
        functools.partial(_ring_zigzag_local, axis=axis, scale=scale),
        mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
        check_vma=False,  # pallas interpret-mode VMA limitation (see above)
    )
    if pre_permuted:
        return fn(q, k, v)
    perm, inv = zigzag_permutation(T, n)
    out = fn(jnp.take(q, perm, axis=2), jnp.take(k, perm, axis=2),
             jnp.take(v, perm, axis=2))
    return jnp.take(out, inv, axis=2)
