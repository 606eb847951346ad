"""Fused LSTM recurrence — single-kernel sequence loop, tiled over hidden.

Reference analog: CudnnLSTMHelper (deeplearning4j-cuda ::
org.deeplearning4j.nn.layers.recurrent.CudnnLSTMHelper), which replaces the
per-timestep Java loop with one cuDNN persistent-RNN launch — for BOTH the
forward and the backward pass. Same split here: the [B*T, F]x[F,4H] input
projection is left to XLA (it is a single MXU-shaped matmul); the
irreducibly-sequential part — T iterations of h@R + gate elementwise — runs
inside ONE Pallas kernel with h/c resident in VMEM scratch, so the
recurrence never round-trips HBM per step, and the whole T-loop is a single
pipelined program instead of T dispatched step-fusions (the reason cuDNN's
persistent kernels win — per-step launch/fusion overhead is the dominant
cost of the XLA scan at these shapes, not FLOPs).

Tiling: grid (B/Bc, T, H/Hb) — batch block outermost (r4), hidden tile
innermost. Each (t, j) step computes gate columns for hidden slice j from
the FULL previous h (double-buffered in scratch: h_prev is stable while
h_next accumulates tiles, swapped after the last tile of each timestep),
so R never needs to fit VMEM whole — R is pre-laid-out as [nH, H, 4*Hb]
per-tile panels. The (Bc, Hb) plan is chosen by a VMEM budget (lstm_plan):
one hidden tile spanning H keeps the R panel's block index grid-constant,
so Pallas fetches R exactly once for the ENTIRE grid — including across
batch blocks, which is what un-demoted the r3 losing regime (B=256/H=1024
re-streamed R per step at 0.4-0.9x; batch-blocked it measures 1.10x fwd /
1.33x train, BASELINE.md r4). The forward and backward choose their batch
blocks independently (the fwd must stay fully resident and wants the
largest resident block for MXU row fill; the bwd tolerates nj=2 and
prefers batch rows — (64, 512) measured faster than the fully-resident
(32, 1024)); the shared [T, B, H] residual layouts make that free.

Matmul precision: panels are pre-cast to bfloat16 with f32 accumulation —
the SAME truncation XLA applies to f32 dot operands on TPU under the
default matmul precision, so the kernel matches the scan lowering's
numerics while running the MXU at full rate (an earlier all-f32 variant of
these kernels measured 0.75x the scan for exactly this reason). Off-TPU
(interpret mode) the cast is skipped, matching XLA-CPU's full-f32 dots.

Backward: a dedicated reverse-time Pallas kernel (_lstm_bwd_kernel), the
cuDNN-parity counterpart of cudnnRNNBackwardData, with the same reserve-
space strategy cuDNN uses: the training forward saves the POST-activation
gates (i, f, o, z, per-gate [T, B, H] f32 — layouts chosen so no consumer
ever transposes them) and the cell sequence, so the backward never re-runs
the h@R recurrence matmul. The backward walks t in reverse via BlockSpec
index maps, forms the pre-activation gate gradients dg for hidden slice j
from the saved tiles entirely in VMEM, and emits four per-gate dg
sequences. The two recurrent carries (dh_rec, accumulated over j via
dg_j @ R_j^T against pre-transposed bf16 panels, and dc, per-slice in
place) live in VMEM scratch with the forward's double-buffer discipline.
Everything that is NOT sequential — dW = x^T dg, dR = h_prev^T dg, db,
dx = dg W^T, peephole sums — is assembled OUTSIDE the kernel as large MXU
matmuls (the cudnnRNNBackwardWeights split), so the kernel only pays for
the O(T) dependent chain.

GravesLSTM peepholes (i,f from c_{t-1}; o from c_t — DL4J semantics,
matching ops/recurrent.lstm_layer) are fused in the same kernels; gate order
IFOG throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl


def _panel_dtype(dtype):
    """MXU operand dtype for the R panels: bf16 on TPU (XLA's own default-
    precision truncation for f32 dots), operand dtype in interpret mode
    (XLA-CPU does full-f32 dots — the parity target off-TPU)."""
    return dtype if interpret_mode() else jnp.bfloat16


def _lstm_kernel(xg_ref, r_ref, h0_ref, c0_ref, p_ref, out_ref, hT_ref,
                 cT_ref, *rest, hb, has_peephole, save_residuals):
    if save_residuals:
        cseq_ref, gi_ref, gf_ref, go_ref, gz_ref = rest[:5]
        hprev_scr, hnext_scr, c_scr = rest[5:]
    else:
        hprev_scr, hnext_scr, c_scr = rest
    # grid (nb, T, nj): batch-block OUTERMOST (r4) — each block runs the
    # whole T recurrence with its own h/c scratch; R's block index ignores
    # every axis, so when one hidden tile spans H the panel is fetched ONCE
    # for ALL batch blocks (the batch-tiled persistent-RNN regime)
    t = pl.program_id(1)
    j = pl.program_id(2)
    nt = pl.num_programs(1)
    nj = pl.num_programs(2)

    @pl.when((t == 0) & (j == 0))
    def _init():
        hprev_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    cols = (slice(None), pl.ds(j * hb, hb))
    # gates for hidden slice j from the FULL previous h (double buffer)
    g = xg_ref[0, 0].astype(jnp.float32) + jax.lax.dot_general(
        hprev_scr[:].astype(r_ref.dtype), r_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [B, 4*hb]
    gi = g[:, :hb]
    gf = g[:, hb:2 * hb]
    go = g[:, 2 * hb:3 * hb]
    gz = g[:, 3 * hb:]
    c_old = c_scr[cols]
    if has_peephole:
        p = p_ref[0].astype(jnp.float32)               # [3, hb]
        gi = gi + c_old * p[0:1, :]
        gf = gf + c_old * p[1:2, :]
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf)
    z = jnp.tanh(gz)
    c_new = f * c_old + i * z
    if has_peephole:
        go = go + c_new * p[2:3, :]
    o = jax.nn.sigmoid(go)
    h_new = o * jnp.tanh(c_new)
    c_scr[cols] = c_new
    hnext_scr[cols] = h_new
    out_ref[0] = h_new.astype(out_ref.dtype)
    if save_residuals:
        cseq_ref[0] = c_new
        gi_ref[0] = i
        gf_ref[0] = f
        go_ref[0] = o
        gz_ref[0] = z

    @pl.when(j == nj - 1)
    def _advance():
        hprev_scr[:] = hnext_scr[:]

    @pl.when(t == nt - 1)
    def _final():
        hT_ref[:] = h_new.astype(hT_ref.dtype)
        cT_ref[:] = c_new.astype(cT_ref.dtype)


def lstm_tile(B, H, rdtype_bytes=2, budget=13 << 20, save_residuals=False):
    """Largest hidden tile (multiple of 128, dividing H) for a batch block
    of B rows; None when even Hb=128 does not fit (fall back).

    Grid-VARYING blocks (R/xg/peephole panels indexed by t or j, and the
    out/hT/cT[/cseq/gate] tiles) are double-buffered by the Pallas
    pipeline, so they count twice; grid-invariant blocks and the three
    scratch buffers count once. When ONE tile spans H the R panel's block
    index is grid-constant, so it is fetched once and counts ONCE — that
    accounting unlocks full-residency at H=1024/small-B, measured 1.2-1.5x
    the scan on-chip (BASELINE.md r3). Blocks whose index varies only on
    the outermost batch-block axis (h0/c0) count once: Pallas skips the
    DMA while the block index is unchanged, so they re-fetch only at chunk
    boundaries. If the pipeline still allocates a second buffer for them,
    the under-count is bounded by 2*B*H*4 (<= 0.5 MB at every shipped
    chunk size) and is absorbed by the ~3 MB gap between this 13 MB budget
    and the ~16 MB scoped-VMEM limit; `chip_smoke.py` compiles the
    batch-blocked plans on the real chip, so a budget violation surfaces
    there, not in production. R panels are bf16 on TPU
    (rdtype_bytes=2)."""
    for hb in (H, 1024, 512, 256, 128):
        if hb > H or H % hb:
            continue
        r_bufs = 1 if hb == H else 2           # grid-invariant panel: once
        est = (r_bufs * H * 4 * hb * rdtype_bytes  # R panel
               + 2 * B * 4 * hb * 4            # xg block (dbl-buffered)
               + 2 * 3 * B * hb * 4            # out/hT/cT tiles (dbl)
               + 3 * B * H * 4                 # h double buffer + c scratch
               + 2 * B * H * 4)                # h0 + c0 (refetch amortized)
        if save_residuals:
            est += 2 * 5 * B * hb * 4          # cseq + 4 gate tiles (dbl)
        if est <= budget:
            return hb
    return None


def lstm_bwd_tile(B, H, rdtype_bytes=2, budget=13 << 20):
    """Tile selector for the backward kernel. Its working set is smaller
    than the forward's: no xg / h_prev inputs (gates come from the saved
    reserve), one transposed R panel (read only for dg_j @ R_j^T; counted
    once when grid-invariant, i.e. hb == H)."""
    for hb in (H, 1024, 512, 256, 128):
        if hb > H or H % hb:
            continue
        r_bufs = 1 if hb == H else 2
        est = (r_bufs * H * 4 * hb * rdtype_bytes  # R^T panel
               + 2 * 4 * B * hb * 4            # gate tiles (dbl)
               + 3 * 2 * B * hb * 4            # c_prev/c/dout tiles (dbl)
               + 2 * 4 * B * hb * 4            # dg out tiles (dbl)
               + 2 * B * hb * 4                # dc0 out tile (dbl)
               + B * H * 4                     # dcT (refetch amortized)
               + 3 * B * H * 4)                # dh carry + dh accum + dc
        if est <= budget:
            return hb
    return None


def _plan(tile_fn, B, H, **kw):
    """(Bc, hb) for the FORWARD: batch-block size and hidden tile.

    The forward must keep R grid-invariant (hb == H): per step it runs ONE
    dot against the full R, so any panel re-streaming is exposed —
    measured 0.33-0.60x at B=256/H=1024 for every nj > 1 or
    under-resident plan. When the full batch cannot be resident, split it
    into batch blocks (r4) and take the LARGEST resident block (MXU row
    fill: Bc=64 measured 1.10x fwd where Bc=32 measured 0.60x). Falls
    back to hidden tiling at full B (reachable via FORCE_PALLAS only) and
    (None, None) when nothing fits."""
    hb = tile_fn(B, H, **kw)
    if hb == H:
        return B, hb
    for Bc in (128, 64, 32):
        if B % Bc == 0 and Bc < B and tile_fn(Bc, H, **kw) == H:
            return Bc, H
    return (B, hb) if hb else (None, None)


def _bwd_plan(tile_fn, B, H, **kw):
    """(Bc, hb) for the BACKWARD: unlike the forward, nj == 2 is fine —
    each reverse step runs FOUR dots against the R^T panels (one per
    gate), so the alternating-panel traffic hides under compute. Measured
    at B=256/H=1024: (64, 512) runs the bwd in ~1.4 ms where the fully-
    resident (32, 1024) takes ~2.6 ms — batch rows beat residency. Rank:
    largest batch block whose tile keeps nj <= 2."""
    fallback = None
    for Bc in (B, 128, 64, 32):
        if Bc > B or B % Bc:
            continue
        hb = tile_fn(Bc, H, **kw)
        if hb is None:
            continue
        if 2 * hb >= H:
            return Bc, hb
        if fallback is None:
            fallback = (Bc, hb)
    return fallback or (None, None)


def lstm_plan(B, H, rdtype_bytes=2, save_residuals=False):
    return _plan(lstm_tile, B, H, rdtype_bytes=rdtype_bytes,
                 save_residuals=save_residuals)


def lstm_bwd_plan(B, H, rdtype_bytes=2):
    return _bwd_plan(lstm_bwd_tile, B, H, rdtype_bytes=rdtype_bytes)


def _fused_recurrence(xg, R, h0, c0, peephole, *, interpret,
                      save_residuals=False):
    """xg [T, B, 4H] time-major pre-projected gates; returns
    (outputs [T, B, H], hT, cT, residuals-or-None). Residuals are
    (cseq, i, f, o, z), each [T, B, H] f32 post-activation — the reserve
    space for the backward kernel, in layouts no consumer transposes."""
    T, B, G = xg.shape
    H = G // 4
    pdt = _panel_dtype(R.dtype)
    Bc, hb = lstm_plan(B, H, rdtype_bytes=jnp.dtype(pdt).itemsize,
                       save_residuals=save_residuals)
    if hb is None:
        raise ValueError(f"no VMEM-feasible LSTM tile for B={B}, H={H}")
    nb = B // Bc
    nj = H // hb
    # per-tile panels: R [nH, H, 4*Hb]; xg [T, nH, B, 4*Hb]
    Rl = (R.reshape(H, 4, nj, hb).transpose(2, 0, 1, 3)
          .reshape(nj, H, 4 * hb).astype(pdt))
    xgl = (xg.reshape(T, B, 4, nj, hb).transpose(0, 3, 1, 2, 4)
           .reshape(T, nj, B, 4 * hb))
    has_p = peephole is not None
    if has_p:
        pll = peephole.reshape(3, nj, hb).transpose(1, 0, 2)  # [nH, 3, hb]
    else:
        pll = jnp.zeros((nj, 3, hb), xg.dtype)

    tile_tj = pl.BlockSpec((1, Bc, hb), lambda b, t, j: (t, b, j),
                           memory_space=pltpu.VMEM)
    out_shape = [jax.ShapeDtypeStruct((T, B, H), xg.dtype),
                 jax.ShapeDtypeStruct((B, H), xg.dtype),
                 jax.ShapeDtypeStruct((B, H), xg.dtype)]
    out_specs = [
        tile_tj,
        pl.BlockSpec((Bc, hb), lambda b, t, j: (b, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((Bc, hb), lambda b, t, j: (b, j),
                     memory_space=pltpu.VMEM),
    ]
    if save_residuals:
        for _ in range(5):                     # cseq + 4 post-activation gates
            out_shape.append(jax.ShapeDtypeStruct((T, B, H), jnp.float32))
            out_specs.append(tile_tj)

    res = pl.pallas_call(
        functools.partial(_lstm_kernel, hb=hb, has_peephole=has_p,
                          save_residuals=save_residuals),
        name="fused_lstm_fwd",
        out_shape=tuple(out_shape),
        grid=(nb, T, nj),
        in_specs=[
            pl.BlockSpec((1, 1, Bc, 4 * hb), lambda b, t, j: (t, j, b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, H, 4 * hb), lambda b, t, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Bc, H), lambda b, t, j: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Bc, H), lambda b, t, j: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3, hb), lambda b, t, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((Bc, H), jnp.float32),
            pltpu.VMEM((Bc, H), jnp.float32),
            pltpu.VMEM((Bc, H), jnp.float32),
        ],
        interpret=interpret,
    )(xgl, Rl, h0, c0, pll)
    if save_residuals:
        out, hT, cT = res[:3]
        residuals = res[3:]                    # (cseq, i, f, o, z)
    else:
        (out, hT, cT), residuals = res, None
    return out, hT, cT, residuals


def _project_gates(x, W, b, H, forget_gate_bias, reverse):
    """The non-sequential input projection: one [B*T,F]x[F,4H] MXU matmul,
    time-major, kernel domain."""
    xg = x @ W + b
    if forget_gate_bias:
        xg = xg.at[..., H:2 * H].add(forget_gate_bias)
    xg = jnp.swapaxes(xg, 0, 1)  # [T, B, 4H]
    if reverse:
        xg = jnp.flip(xg, axis=0)
    return xg


def _kernel_forward(x, h0, c0, W, R, b, peephole, forget_gate_bias, reverse,
                    save_residuals=False):
    H = R.shape[0]
    xg = _project_gates(x, W, b, H, forget_gate_bias, reverse)
    out, hT, cT, residuals = _fused_recurrence(
        xg, R, h0, c0, peephole, interpret=interpret_mode(),
        save_residuals=save_residuals)
    if reverse:
        out = jnp.flip(out, axis=0)
    return (jnp.swapaxes(out, 0, 1), (hT, cT)), residuals


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _fused(x, h0, c0, W, R, b, peephole, forget_gate_bias, reverse):
    out, _ = _kernel_forward(x, h0, c0, W, R, b, peephole, forget_gate_bias,
                             reverse)
    return out


def _kernel_bwd_enabled(B, H, rdtype) -> bool:
    """Trace-time decision shared by _fused_fwd and _fused_bwd: save (and
    consume) the reserve space only when the backward kernel will run, so
    the scan-backward arm (flag or infeasible tile) pays no reserve cost."""
    return (not env.lstm_scan_bwd
            and lstm_bwd_plan(
                B, H, rdtype_bytes=jnp.dtype(_panel_dtype(rdtype)).itemsize)[1]
            is not None)


def _fused_fwd(x, h0, c0, W, R, b, peephole, forget_gate_bias, reverse):
    save = _kernel_bwd_enabled(x.shape[0], R.shape[0], R.dtype)
    out, residuals = _kernel_forward(x, h0, c0, W, R, b, peephole,
                                     forget_gate_bias, reverse,
                                     save_residuals=save)
    # residuals are kept in KERNEL time order (flipped when reverse=True) —
    # the backward kernel walks the same domain
    return out, (x, h0, c0, W, R, b, peephole, out[0], residuals)


# --------------------------------------------------------------------------
# backward kernel
# --------------------------------------------------------------------------


def _lstm_bwd_kernel(i_ref, f_ref, o_ref, z_ref, rt_ref, cprev_ref, c_ref,
                     dout_ref, dcT_ref, p_ref,
                     dgi_ref, dgf_ref, dgo_ref, dgz_ref, dc0_ref,
                     dh_scr, dhn_scr, dc_scr, *, hb, has_peephole):
    """One reverse-time step for hidden slice j.

    Reads the saved post-activation gates (the reserve space — NO h@R
    recompute), forms the pre-activation gate gradients dg and the two
    carries: dh_rec (accumulated over j via dg_j @ R_j^T against the
    pre-transposed panel) and dc (per-slice, in place). Time reversal is
    done by the BlockSpec index maps, not by flipping arrays in HBM.
    Grid (nb, T, nj) with the batch block outermost (r4), mirroring the
    forward: each batch block replays the reverse recurrence with its own
    carries while the R^T panel stays grid-invariant.
    """
    t = pl.program_id(1)
    j = pl.program_id(2)
    nt = pl.num_programs(1)
    nj = pl.num_programs(2)

    @pl.when((t == 0) & (j == 0))
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = dcT_ref[:].astype(jnp.float32)

    cols = (slice(None), pl.ds(j * hb, hb))

    i = i_ref[0]                                       # [B, hb] f32
    f = f_ref[0]
    o = o_ref[0]
    z = z_ref[0]
    c_old = cprev_ref[0].astype(jnp.float32)
    th = jnp.tanh(c_ref[0].astype(jnp.float32))
    if has_peephole:
        p = p_ref[0].astype(jnp.float32)               # [3, hb]

    # ---- gate gradients
    dh_tot = dout_ref[0].astype(jnp.float32) + dh_scr[cols]
    dgo = (dh_tot * th) * o * (1.0 - o)
    dc = dc_scr[cols] + dh_tot * o * (1.0 - th * th)
    if has_peephole:
        dc = dc + dgo * p[2:3, :]
    dgi = (dc * z) * i * (1.0 - i)
    dgf = (dc * c_old) * f * (1.0 - f)
    dgz = (dc * i) * (1.0 - z * z)
    dc_prev = dc * f
    if has_peephole:
        dc_prev = dc_prev + dgi * p[0:1, :] + dgf * p[1:2, :]
    dc_scr[cols] = dc_prev
    dgi_ref[0] = dgi
    dgf_ref[0] = dgf
    dgo_ref[0] = dgo
    dgz_ref[0] = dgz

    # ---- dh_rec for step t-1: accumulate sum_g dg_g @ R_g^T over slices
    pdt = rt_ref.dtype
    contrib = jax.lax.dot_general(
        dgi.astype(pdt), rt_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [B, H]
    for dgx, gate in ((dgf, 1), (dgo, 2), (dgz, 3)):
        contrib = contrib + jax.lax.dot_general(
            dgx.astype(pdt), rt_ref[0, gate], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _first():
        dhn_scr[:] = contrib

    @pl.when(j != 0)
    def _acc():
        dhn_scr[:] = dhn_scr[:] + contrib

    @pl.when(j == nj - 1)
    def _advance():
        dh_scr[:] = dhn_scr[:]

    @pl.when(t == nt - 1)
    def _final():
        dc0_ref[:] = dc_prev


def _bwd_recurrence(residuals, R, cprev_seq, dout, dcT, peephole, *,
                    plan, interpret):
    """Run the reverse-time kernel. ``residuals`` = (cseq, i, f, o, z) from
    the forward, KERNEL time order. Returns (dgi, dgf, dgo, dgz — each
    [T, B, H] f32 in kernel time order — and dc0). ``plan`` = (Bc, hb):
    the backward's batch block is chosen independently of the forward's
    (measured at B=256/H=1024: the bwd's best plan is (64, 512) — nj=2
    with more batch rows beats the fully-resident (32, 1024), ~1.4 ms vs
    ~2.6 ms — while the fwd must stay resident; the shared [T, B, H]
    layouts make the re-chunk free)."""
    cseq, gi, gf, go, gz = residuals
    T, B, H = cseq.shape
    Bc, hb = plan
    nb = B // Bc
    nj = H // hb
    pdt = _panel_dtype(R.dtype)
    # pre-transposed panels: Rt[j, g] = R[:, g*H + j*hb : ...]^T  [hb, H]
    Rt = (R.reshape(H, 4, nj, hb).transpose(2, 1, 3, 0)   # [nj, 4, hb, H]
          .astype(pdt))
    has_p = peephole is not None
    if has_p:
        pll = peephole.reshape(3, nj, hb).transpose(1, 0, 2)  # [nH, 3, hb]
    else:
        pll = jnp.zeros((nj, 3, hb), R.dtype)

    revj = lambda b, t, j: (T - 1 - t, b, j)       # reverse-time j-tiles
    tile = pl.BlockSpec((1, Bc, hb), revj, memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_lstm_bwd_kernel, hb=hb, has_peephole=has_p),
        name="fused_lstm_bwd",
        out_shape=(jax.ShapeDtypeStruct((T, B, H), jnp.float32),) * 4
        + (jax.ShapeDtypeStruct((B, H), jnp.float32),),
        grid=(nb, T, nj),
        in_specs=[
            tile, tile, tile, tile,                    # i, f, o, z
            pl.BlockSpec((1, 4, hb, H), lambda b, t, j: (j, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            tile,                                      # c_prev
            tile,                                      # c
            tile,                                      # dout
            pl.BlockSpec((Bc, H), lambda b, t, j: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3, hb), lambda b, t, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(tile,) * 4 + (
            pl.BlockSpec((Bc, hb), lambda b, t, j: (b, j),
                         memory_space=pltpu.VMEM),),
        scratch_shapes=[
            pltpu.VMEM((Bc, H), jnp.float32),  # dh_rec carry (stable per t)
            pltpu.VMEM((Bc, H), jnp.float32),  # dh_rec accumulator
            pltpu.VMEM((Bc, H), jnp.float32),  # dc carry (per-slice in place)
        ],
        interpret=interpret,
    )(gi, gf, go, gz, Rt, cprev_seq, cseq, dout, dcT, pll)
    return out                                          # (dgi..dgz, dc0)


def _scan_bwd(forget_gate_bias, reverse, res, g):
    """Fallback backward: autodiff through the XLA scan lowering (used when
    no VMEM-feasible backward tile exists, or when forced via
    DL4J_TPU_LSTM_SCAN_BWD for A/B measurement)."""
    from deeplearning4j_tpu.ops.recurrent import lstm_layer

    x, h0, c0, W, R, b, peephole = res
    diff_args = (x, h0, c0, W, R, b) + (() if peephole is None else (peephole,))

    def ref(*args):
        if peephole is None:
            xx, hh, cc, WW, RR, bb = args
            pp = None
        else:
            xx, hh, cc, WW, RR, bb, pp = args
        return lstm_layer(xx, hh, cc, WW, RR, bb, peephole=pp,
                          forget_gate_bias=forget_gate_bias, reverse=reverse)

    _, vjp = jax.vjp(ref, *diff_args)
    grads = vjp(g)
    if peephole is None:
        grads = grads + (None,)
    return grads


def _fused_bwd(forget_gate_bias, reverse, res, g):
    x, h0, c0, W, R, b, peephole, out, residuals = res
    B, T, F = x.shape
    H = R.shape[0]
    if residuals is None:   # forward already decided: scan backward
        return _scan_bwd(forget_gate_bias, reverse,
                         (x, h0, c0, W, R, b, peephole), g)
    plan = lstm_bwd_plan(
        B, H, rdtype_bytes=jnp.dtype(_panel_dtype(R.dtype)).itemsize)

    g_out, (g_hT, g_cT) = g
    cseq = residuals[0]

    # kernel time domain (flipped when reverse=True), matching residuals
    out_k = jnp.swapaxes(out, 0, 1)
    dout_k = jnp.swapaxes(g_out, 0, 1)
    if reverse:
        out_k = jnp.flip(out_k, axis=0)
        dout_k = jnp.flip(dout_k, axis=0)
    # hT aliases out[T-1]; its cotangent joins the last step's output grad
    dout_k = dout_k.at[T - 1].add(g_hT)
    hprev_k = jnp.concatenate([h0[None].astype(out_k.dtype), out_k[:-1]], 0)
    cprev_k = jnp.concatenate([c0[None].astype(cseq.dtype), cseq[:-1]], 0)

    dgi, dgf, dgo, dgz, dc0 = _bwd_recurrence(
        residuals, R, cprev_k, dout_k, g_cT, peephole, plan=plan,
        interpret=interpret_mode())
    dgs = (dgi, dgf, dgo, dgz)

    # ---- everything non-sequential: big MXU matmuls outside the kernel
    # (the cudnnRNNBackwardWeights split), all on untransposed [T,B,H]
    # operands — dot_general contracts (t,b) directly, no relayouts.
    xf = x.astype(jnp.float32)
    hpf = hprev_k.astype(jnp.float32)
    # h0 feeds only g_0: dh0 = sum_g dg_g[0] @ R_g^T
    dh0 = sum(jax.lax.dot_general(
        dg[0], R.astype(jnp.float32)[:, gi_ * H:(gi_ + 1) * H],
        (((1,), (1,)), ((), ()))) for gi_, dg in enumerate(dgs))
    dR = jnp.concatenate(
        [jnp.einsum("tbh,tbg->hg", hpf, dg) for dg in dgs], axis=1)
    # x-coupled products need NATURAL time order (dgs are kernel order)
    dgs_nat = tuple(jnp.flip(dg, axis=0) for dg in dgs) if reverse else dgs
    dW = jnp.concatenate(
        [jnp.einsum("btf,tbg->fg", xf, dg) for dg in dgs_nat], axis=1)
    db = jnp.concatenate([dg.sum((0, 1)) for dg in dgs])
    # dx = sum_g dg_g @ W_g^T, emitted batch-major
    Wf = W.astype(jnp.float32)
    dx_nat = sum(jax.lax.dot_general(
        dg, Wf[:, gi_ * H:(gi_ + 1) * H], (((2,), (1,)), ((), ())))
        for gi_, dg in enumerate(dgs_nat))             # [T, B, F]
    dx = jnp.swapaxes(dx_nat, 0, 1)
    if peephole is not None:
        cpf = cprev_k.astype(jnp.float32)
        dp = jnp.concatenate([
            (dgi * cpf).sum((0, 1)),
            (dgf * cpf).sum((0, 1)),
            (dgo * cseq).sum((0, 1)),
        ])
        dp = dp.astype(peephole.dtype)
    else:
        dp = None
    return (dx.astype(x.dtype), dh0.astype(h0.dtype), dc0.astype(c0.dtype),
            dW.astype(W.dtype), dR.astype(R.dtype), db.astype(b.dtype), dp)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _pad_to_lanes(H: int) -> int:
    """Next lane multiple: the padded hidden size the kernel entry point
    runs AND the size the selection predicates must evaluate (one shared
    definition so predicate and kernel can never disagree)."""
    return -(-H // 128) * 128


def _pad_gates(a, H, Hp, axis):
    """Zero-pad the per-gate H-blocks of a gate-major [..., G*H] axis to
    [..., G*Hp] (G inferred), keeping IFOG block order."""
    G = a.shape[axis] // H
    shape = list(a.shape)
    shape[axis:axis + 1] = [G, H]
    widths = [(0, 0)] * len(shape)
    widths[axis + 1] = (0, Hp - H)
    out = jnp.pad(a.reshape(shape), widths)
    shape2 = list(a.shape)
    shape2[axis] = G * Hp
    return out.reshape(shape2)


def fused_lstm_layer(x, h0, c0, W, R, b, *, peephole=None,
                     forget_gate_bias=0.0, reverse=False):
    """Drop-in accelerated impl of the "lstm_layer" op (same signature).

    Unaligned hidden sizes (H % 128 != 0 — e.g. the reference's stock
    200-unit GravesLSTM configs, which cuDNN accelerates too) are
    zero-PADDED to the next lane multiple: padded gate columns see zero
    pre-activations, so z = tanh(0) = 0 keeps c = h = 0 in every padded
    lane through the whole recurrence (forget-gate bias and peepholes
    included: they multiply a zero c), and the backward's padded gate
    gradients vanish the same way — slicing after the kernel is exact,
    not approximate. The pad/slice is differentiable, so the
    custom_vjp'd core needs no changes."""
    H = R.shape[0]
    Hp = _pad_to_lanes(H)
    if Hp == H:
        return _fused(x, h0, c0, W, R, b, peephole, float(forget_gate_bias),
                      bool(reverse))
    padh = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, Hp - H)])
    Wp = _pad_gates(W, H, Hp, 1)
    Rp = _pad_gates(jnp.pad(R, [(0, Hp - H), (0, 0)]), H, Hp, 1)
    bp = _pad_gates(b, H, Hp, 0)
    pp = None if peephole is None else _pad_gates(peephole, H, Hp, 0)
    out, (hT, cT) = _fused(x, padh(h0), padh(c0), Wp, Rp, bp, pp,
                           float(forget_gate_bias), bool(reverse))
    return out[..., :H], (hT[..., :H], cT[..., :H])


def _lstm_requires(x, h0, c0, W, R, b, *, peephole=None, **kw):
    # structural: a VMEM-feasible plan must exist (incl. reserve outputs),
    # sized with the SAME panel dtype _fused_recurrence will actually use
    # (f32 in interpret mode, bf16 on TPU) and the PADDED hidden size the
    # kernel will actually run
    Hp = _pad_to_lanes(R.shape[0])
    rb = jnp.dtype(_panel_dtype(R.dtype)).itemsize
    return lstm_plan(x.shape[0], Hp, rdtype_bytes=rb,
                     save_residuals=True)[1] is not None


def _lstm_applicable(x, h0, c0, W, R, b, *, peephole=None, **kw):
    """Perf heuristic (measured on v5e, r3+r4): the kernel wins when R is
    grid-invariant — ONE hidden tile spans H, fetched once, the recurrence
    runs out of VMEM (fwd up to 2.0x, train 1.1-1.6x vs the scan). r4
    extends that regime to LARGE batches by batch-blocking the grid: at
    B=256/H=1024 (the r3 demoted shape) the fwd runs resident batch
    blocks (Bc=64 infer / Bc=32 train) and the bwd runs (64, 512),
    measured 1.10x fwd / 1.33x train — numbers in BASELINE.md. Only
    shapes with no resident plan at all (H too big for any block to keep
    R in VMEM, e.g. H >= 2048) stay on the XLA scan, as do non-f32/bf16
    dtypes — the measured A/B evidence (and the MXU panel layout) covers
    only those."""
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    Hp = _pad_to_lanes(R.shape[0])         # unaligned H runs zero-padded
    rb = jnp.dtype(_panel_dtype(R.dtype)).itemsize
    return (x.shape[0] % 8 == 0
            and lstm_plan(x.shape[0], Hp, rdtype_bytes=rb,
                          save_residuals=True)[1] == Hp)


register_impl("lstm_layer", platform="pallas", predicate=_lstm_applicable,
              requires=_lstm_requires, priority=1,
              scope="fused_lstm")(fused_lstm_layer)
