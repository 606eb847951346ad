"""Fused GRU recurrence — single-kernel sequence loop, tiled over hidden.

Reference analog: cuDNN's CUDNN_GRU persistent-RNN mode (the same
cudnnRNNForward/Backward family CudnnLSTMHelper drives for LSTM; DL4J's GRU
layer runs the generic libnd4j gruCell loop — this kernel gives the TPU
build the fused tier the reference reserved for LSTM). Design mirrors
ops/pallas/fused_lstm.py exactly: the [B*T, F]x[F,3H] input projection
stays one XLA MXU matmul; the irreducibly-sequential h@R chain runs inside
ONE Pallas kernel with h resident in VMEM scratch (grid (T, H/Hb), hidden
tile innermost, double-buffered h), R pre-laid-out as [nH, H, 3*Hb] bf16
panels (XLA's own default-precision truncation for f32 dots — see the
precision note in fused_lstm.py).

Gate semantics match ops/recurrent.gru_layer (order r, z, n with cuDNN's
linear-before-reset coupling): r = s(xr + hr), z = s(xz + hz),
n = tanh(xn + r * hn), h' = (1-z)*n + z*h — the xg and hg projections must
therefore stay SEPARATE inside the kernel (n mixes them through r).

Backward: reverse-time Pallas kernel with the cuDNN reserve-space strategy:
the training forward saves post-activation r, z, n and the raw recurrent
candidate projection hg_n (each [T, B, H] f32), so the backward never
re-runs h@R. Per reverse step it forms the three pre-activation gate
gradients and the dh carry — z*dh_tot (direct path) plus
[ga_r, ga_z, r*ga_n] @ R^T against pre-transposed panels — and the final
carry IS dh0. Everything non-sequential (dW/dR/db/dx) is assembled outside
as large MXU matmuls, exactly the cudnnRNNBackwardWeights split.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.ops.pallas.fused_lstm import (_pad_gates,
                                                      _pad_to_lanes,
                                                      _panel_dtype)
from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl


def _gru_kernel(xg_ref, r_ref, h0_ref, out_ref, hT_ref, *rest, hb,
                save_residuals):
    if save_residuals:
        rr_ref, rz_ref, rn_ref, rhgn_ref = rest[:4]
        hprev_scr, hnext_scr = rest[4:]
    else:
        hprev_scr, hnext_scr = rest
    # grid (nb, T, nj): batch block outermost (r4) — see fused_lstm.py
    t = pl.program_id(1)
    j = pl.program_id(2)
    nt = pl.num_programs(1)
    nj = pl.num_programs(2)

    @pl.when((t == 0) & (j == 0))
    def _init():
        hprev_scr[:] = h0_ref[:].astype(jnp.float32)

    cols = (slice(None), pl.ds(j * hb, hb))
    # recurrent projection for hidden slice j from the FULL previous h
    hg = jax.lax.dot_general(
        hprev_scr[:].astype(r_ref.dtype), r_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [B, 3*hb]
    xg = xg_ref[0, 0].astype(jnp.float32)              # [B, 3*hb]
    r = jax.nn.sigmoid(xg[:, :hb] + hg[:, :hb])
    z = jax.nn.sigmoid(xg[:, hb:2 * hb] + hg[:, hb:2 * hb])
    hgn = hg[:, 2 * hb:]
    n = jnp.tanh(xg[:, 2 * hb:] + r * hgn)
    h_old = hprev_scr[cols]
    h_new = (1.0 - z) * n + z * h_old
    hnext_scr[cols] = h_new
    out_ref[0] = h_new.astype(out_ref.dtype)
    if save_residuals:
        rr_ref[0] = r
        rz_ref[0] = z
        rn_ref[0] = n
        rhgn_ref[0] = hgn

    @pl.when(j == nj - 1)
    def _advance():
        hprev_scr[:] = hnext_scr[:]

    @pl.when(t == nt - 1)
    def _final():
        hT_ref[:] = h_new.astype(hT_ref.dtype)


def gru_tile(B, H, rdtype_bytes=2, budget=13 << 20, save_residuals=False):
    """Largest hidden tile (multiple of 128, dividing H) for a batch block
    of B rows; None when even Hb=128 does not fit. Same accounting
    discipline as fused_lstm.lstm_tile (grid-varying blocks are
    double-buffered by the pipeline and count twice; batch-block-only
    variation re-fetches at chunk boundaries and counts once)."""
    for hb in (H, 1024, 512, 256, 128):
        if hb > H or H % hb:
            continue
        r_bufs = 1 if hb == H else 2           # grid-invariant panel: once
        est = (r_bufs * H * 3 * hb * rdtype_bytes  # R panel
               + 2 * B * 3 * hb * 4            # xg block (dbl-buffered)
               + 2 * 2 * B * hb * 4            # out/hT tiles (dbl)
               + 2 * B * H * 4                 # h double buffer
               + B * H * 4)                    # h0 (refetch amortized)
        if save_residuals:
            est += 2 * 4 * B * hb * 4          # r/z/n/hgn tiles (dbl)
        if est <= budget:
            return hb
    return None


def gru_bwd_tile(B, H, rdtype_bytes=2, budget=13 << 20):
    for hb in (H, 1024, 512, 256, 128):
        if hb > H or H % hb:
            continue
        r_bufs = 1 if hb == H else 2
        est = (r_bufs * H * 3 * hb * rdtype_bytes  # R^T panel
               + 2 * 6 * B * hb * 4            # r/z/n/hgn/hprev/dout (dbl)
               + 2 * 3 * B * hb * 4            # dgr/dgz/dgn out tiles (dbl)
               + B * H * 4                     # dh0 full-H block
               + 2 * B * H * 4)                # dh carry + dh accumulator
        if est <= budget:
            return hb
    return None


def gru_plan(B, H, rdtype_bytes=2, save_residuals=False):
    from deeplearning4j_tpu.ops.pallas.fused_lstm import _plan

    return _plan(gru_tile, B, H, rdtype_bytes=rdtype_bytes,
                 save_residuals=save_residuals)


def gru_bwd_plan(B, H, rdtype_bytes=2):
    from deeplearning4j_tpu.ops.pallas.fused_lstm import _bwd_plan

    return _bwd_plan(gru_bwd_tile, B, H, rdtype_bytes=rdtype_bytes)


def _fused_gru_recurrence(xg, R, h0, *, interpret, save_residuals=False):
    """xg [T, B, 3H] time-major; returns (out [T, B, H], hT,
    residuals-or-None) where residuals = (r, z, n, hg_n) each [T, B, H] f32
    post-activation — the reserve space for the backward kernel."""
    T, B, G = xg.shape
    H = G // 3
    pdt = _panel_dtype(R.dtype)
    Bc, hb = gru_plan(B, H, rdtype_bytes=jnp.dtype(pdt).itemsize,
                      save_residuals=save_residuals)
    if hb is None:
        raise ValueError(f"no VMEM-feasible GRU tile for B={B}, H={H}")
    nb = B // Bc
    nj = H // hb
    Rl = (R.reshape(H, 3, nj, hb).transpose(2, 0, 1, 3)
          .reshape(nj, H, 3 * hb).astype(pdt))
    xgl = (xg.reshape(T, B, 3, nj, hb).transpose(0, 3, 1, 2, 4)
           .reshape(T, nj, B, 3 * hb))

    tile_tj = pl.BlockSpec((1, Bc, hb), lambda b, t, j: (t, b, j),
                           memory_space=pltpu.VMEM)
    out_shape = [jax.ShapeDtypeStruct((T, B, H), xg.dtype),
                 jax.ShapeDtypeStruct((B, H), xg.dtype)]
    out_specs = [
        tile_tj,
        pl.BlockSpec((Bc, hb), lambda b, t, j: (b, j),
                     memory_space=pltpu.VMEM),
    ]
    if save_residuals:
        for _ in range(4):                     # r, z, n, hg_n
            out_shape.append(jax.ShapeDtypeStruct((T, B, H), jnp.float32))
            out_specs.append(tile_tj)

    res = pl.pallas_call(
        functools.partial(_gru_kernel, hb=hb, save_residuals=save_residuals),
        name="fused_gru_fwd",
        out_shape=tuple(out_shape),
        grid=(nb, T, nj),
        in_specs=[
            pl.BlockSpec((1, 1, Bc, 3 * hb), lambda b, t, j: (t, j, b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, H, 3 * hb), lambda b, t, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Bc, H), lambda b, t, j: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((Bc, H), jnp.float32),
            pltpu.VMEM((Bc, H), jnp.float32),
        ],
        interpret=interpret,
    )(xgl, Rl, h0)
    if save_residuals:
        out, hT = res[:2]
        residuals = res[2:]
    else:
        (out, hT), residuals = res, None
    return out, hT, residuals


def _project_gates(x, W, b, reverse):
    xg = jnp.swapaxes(x @ W + b, 0, 1)         # [T, B, 3H]
    if reverse:
        xg = jnp.flip(xg, axis=0)
    return xg


def _kernel_forward(x, h0, W, R, b, reverse, save_residuals=False):
    xg = _project_gates(x, W, b, reverse)
    out, hT, residuals = _fused_gru_recurrence(
        xg, R, h0, interpret=interpret_mode(), save_residuals=save_residuals)
    if reverse:
        out = jnp.flip(out, axis=0)
    return (jnp.swapaxes(out, 0, 1), hT), residuals


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused(x, h0, W, R, b, reverse):
    out, _ = _kernel_forward(x, h0, W, R, b, reverse)
    return out


def _kernel_bwd_enabled(B, H, rdtype) -> bool:
    return (not env.gru_scan_bwd
            and gru_bwd_plan(
                B, H, rdtype_bytes=jnp.dtype(_panel_dtype(rdtype)).itemsize)[1]
            is not None)


def _fused_fwd(x, h0, W, R, b, reverse):
    save = _kernel_bwd_enabled(x.shape[0], R.shape[0], R.dtype)
    out, residuals = _kernel_forward(x, h0, W, R, b, reverse,
                                     save_residuals=save)
    return out, (x, h0, W, R, b, out[0], residuals)


def _gru_bwd_kernel(r_ref, z_ref, n_ref, hgn_ref, rt_ref, hprev_ref,
                    dout_ref, dgr_ref, dgz_ref, dgn_ref, dh0_ref,
                    dh_scr, dhn_scr, *, hb):
    """One reverse-time step for hidden slice j.

    dh_tot = dout_t + dh carry; then
      dn = dh_tot*(1-z);   ga_n = dn*(1-n^2)       (xg_n gradient)
      dz = dh_tot*(h_prev - n); ga_z = dz*z*(1-z)
      dr = ga_n*hg_n;      ga_r = dr*r*(1-r)
    carry' = z*dh_tot (direct path, per slice)
           + [ga_r, ga_z, r*ga_n] @ R^T (accumulated over slices).
    The final carry is dh0 — emitted on the last step. Grid (nb, T, nj)
    with the batch block outermost (r4), as in the forward.
    """
    t = pl.program_id(1)
    j = pl.program_id(2)
    nt = pl.num_programs(1)
    nj = pl.num_programs(2)

    @pl.when((t == 0) & (j == 0))
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    cols = (slice(None), pl.ds(j * hb, hb))

    r = r_ref[0]
    z = z_ref[0]
    n = n_ref[0]
    hgn = hgn_ref[0]
    h_prev = hprev_ref[0].astype(jnp.float32)

    dh_tot = dout_ref[0].astype(jnp.float32) + dh_scr[cols]
    dn = dh_tot * (1.0 - z)
    ga_n = dn * (1.0 - n * n)
    dz = dh_tot * (h_prev - n)
    ga_z = dz * z * (1.0 - z)
    dr = ga_n * hgn
    ga_r = dr * r * (1.0 - r)
    dgr_ref[0] = ga_r
    dgz_ref[0] = ga_z
    dgn_ref[0] = ga_n

    pdt = rt_ref.dtype
    contrib = jax.lax.dot_general(
        ga_r.astype(pdt), rt_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [B, H]
    contrib = contrib + jax.lax.dot_general(
        ga_z.astype(pdt), rt_ref[0, 1], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    contrib = contrib + jax.lax.dot_general(
        (r * ga_n).astype(pdt), rt_ref[0, 2], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _first():
        dhn_scr[:] = contrib

    @pl.when(j != 0)
    def _acc():
        dhn_scr[:] = dhn_scr[:] + contrib

    # the direct z*dh_tot path lands only in this slice's columns
    dhn_scr[cols] = dhn_scr[cols] + z * dh_tot

    @pl.when(j == nj - 1)
    def _advance():
        dh_scr[:] = dhn_scr[:]

    # dh0 couples across hidden slices (each j adds a full-H matmul
    # contribution), so it can only be emitted once the LAST slice of the
    # final reverse step has accumulated — unlike the LSTM's dc0, which is
    # per-slice and writes tile-by-tile
    @pl.when((t == nt - 1) & (j == nj - 1))
    def _final():
        dh0_ref[:] = dhn_scr[:]


def _bwd_recurrence(residuals, R, hprev_seq, dout, *, plan, interpret):
    """Reverse-time kernel. residuals/hprev_seq/dout in KERNEL time order.
    Returns (ga_r, ga_z, ga_n — each [T, B, H] f32, kernel order — and
    dh0 [B, H]). ``plan`` = (Bc, hb), chosen independently of the
    forward's (see fused_lstm._bwd_recurrence)."""
    rr, rz, rn, rhgn = residuals
    T, B, H = rr.shape
    Bc, hb = plan
    nb = B // Bc
    nj = H // hb
    pdt = _panel_dtype(R.dtype)
    Rt = (R.reshape(H, 3, nj, hb).transpose(2, 1, 3, 0)   # [nj, 3, hb, H]
          .astype(pdt))

    revj = lambda b, t, j: (T - 1 - t, b, j)
    tile = pl.BlockSpec((1, Bc, hb), revj, memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_gru_bwd_kernel, hb=hb),
        name="fused_gru_bwd",
        out_shape=(jax.ShapeDtypeStruct((T, B, H), jnp.float32),) * 3
        + (jax.ShapeDtypeStruct((B, H), jnp.float32),),
        grid=(nb, T, nj),
        in_specs=[
            tile, tile, tile, tile,                    # r, z, n, hg_n
            pl.BlockSpec((1, 3, hb, H), lambda b, t, j: (j, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            tile,                                      # h_prev
            tile,                                      # dout
        ],
        out_specs=(tile,) * 3 + (
            pl.BlockSpec((Bc, H), lambda b, t, j: (b, 0),
                         memory_space=pltpu.VMEM),),
        scratch_shapes=[
            pltpu.VMEM((Bc, H), jnp.float32),  # dh carry (stable per t)
            pltpu.VMEM((Bc, H), jnp.float32),  # dh accumulator
        ],
        interpret=interpret,
    )(rr, rz, rn, rhgn, Rt, hprev_seq, dout)


def _scan_bwd(reverse, res, g):
    from deeplearning4j_tpu.ops.recurrent import gru_layer

    x, h0, W, R, b = res

    def ref(xx, hh, WW, RR, bb):
        return gru_layer(xx, hh, WW, RR, bb, reverse=reverse)

    _, vjp = jax.vjp(ref, x, h0, W, R, b)
    return vjp(g)


def _fused_bwd(reverse, res, g):
    x, h0, W, R, b, out, residuals = res
    B, T, F = x.shape
    H = R.shape[0]
    if residuals is None:
        return _scan_bwd(reverse, (x, h0, W, R, b), g)
    plan = gru_bwd_plan(
        B, H, rdtype_bytes=jnp.dtype(_panel_dtype(R.dtype)).itemsize)

    g_out, g_hT = g
    rr = residuals[0]

    out_k = jnp.swapaxes(out, 0, 1)
    dout_k = jnp.swapaxes(g_out, 0, 1)
    if reverse:
        out_k = jnp.flip(out_k, axis=0)
        dout_k = jnp.flip(dout_k, axis=0)
    dout_k = dout_k.at[T - 1].add(g_hT)
    hprev_k = jnp.concatenate([h0[None].astype(out_k.dtype), out_k[:-1]], 0)

    ga_r, ga_z, ga_n, dh0 = _bwd_recurrence(
        residuals, R, hprev_k, dout_k, plan=plan, interpret=interpret_mode())
    # hg_n's gradient (for dR's n block and the recurrent path already
    # inside the kernel) is r*ga_n; cheap elementwise, XLA fuses it here
    ga_hn = rr * ga_n
    dgs_h = (ga_r, ga_z, ga_hn)                # h-path gate grads (for dR)
    dgs_x = (ga_r, ga_z, ga_n)                 # x-path gate grads (W/b/dx)

    xf = x.astype(jnp.float32)
    hpf = hprev_k.astype(jnp.float32)
    dR = jnp.concatenate(
        [jnp.einsum("tbh,tbg->hg", hpf, dg) for dg in dgs_h], axis=1)
    dgs_x_nat = (tuple(jnp.flip(dg, axis=0) for dg in dgs_x)
                 if reverse else dgs_x)
    dW = jnp.concatenate(
        [jnp.einsum("btf,tbg->fg", xf, dg) for dg in dgs_x_nat], axis=1)
    db = jnp.concatenate([dg.sum((0, 1)) for dg in dgs_x])
    Wf = W.astype(jnp.float32)
    dx_nat = sum(jax.lax.dot_general(
        dg, Wf[:, gi_ * H:(gi_ + 1) * H], (((2,), (1,)), ((), ())))
        for gi_, dg in enumerate(dgs_x_nat))           # [T, B, F]
    dx = jnp.swapaxes(dx_nat, 0, 1)
    return (dx.astype(x.dtype), dh0.astype(h0.dtype), dW.astype(W.dtype),
            dR.astype(R.dtype), db.astype(b.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_gru_layer(x, h0, W, R, b, *, reverse=False):
    """Drop-in accelerated impl of the "gru_layer" op (same signature).

    Unaligned hidden sizes zero-pad to the next lane multiple. Padding is
    exact for GRU even though padded r/z sit at sigmoid(0)=0.5: padded
    lanes have hg_n = 0 and xg_n = 0, so n = tanh(0) = 0 and
    h' = (1-z)*0 + z*h with h0's padded lanes zero — h stays 0 through the
    whole recurrence. Backward: padded-lane output cotangents are zero
    (outputs are sliced), padded gate columns of R/W are zero, so every
    padded gate gradient vanishes (dn ∝ dh_tot = 0 there) and real-lane
    gradients are untouched — the pad/slice is exact, matching the
    fused-LSTM padding contract."""
    H = R.shape[0]
    Hp = _pad_to_lanes(H)
    if Hp == H:
        return _fused(x, h0, W, R, b, bool(reverse))
    padh = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, Hp - H)])
    Wp = _pad_gates(W, H, Hp, 1)
    Rp = _pad_gates(jnp.pad(R, [(0, Hp - H), (0, 0)]), H, Hp, 1)
    bp = _pad_gates(b, H, Hp, 0)
    out, hT = _fused(x, padh(h0), Wp, Rp, bp, bool(reverse))
    return out[..., :H], hT[..., :H]


def _gru_requires(x, h0, W, R, b, **kw):
    Hp = _pad_to_lanes(R.shape[0])
    rb = jnp.dtype(_panel_dtype(R.dtype)).itemsize
    return gru_plan(x.shape[0], Hp, rdtype_bytes=rb,
                    save_residuals=True)[1] is not None


def _gru_applicable(x, h0, W, R, b, **kw):
    """Same measured selection policy as the fused LSTM: the kernel wins
    when R is grid-invariant (one hidden tile spans H, fetched once, the
    recurrence fully VMEM-resident) — which r4's batch-blocked grid now
    achieves at large B too. Verified by the bench `kernels` mode A/B
    rows. Non-f32/bf16 dtypes stay on the XLA scan — the A/B evidence
    and the MXU panel layout cover only those."""
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    Hp = _pad_to_lanes(R.shape[0])
    rb = jnp.dtype(_panel_dtype(R.dtype)).itemsize
    return (x.shape[0] % 8 == 0
            and gru_plan(x.shape[0], Hp, rdtype_bytes=rb,
                         save_residuals=True)[1] == Hp)


register_impl("gru_layer", platform="pallas", predicate=_gru_applicable,
              requires=_gru_requires, priority=1,
              scope="fused_gru")(fused_gru_layer)
