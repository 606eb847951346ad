"""Local response normalization — Pallas kernel.

Reference analog: deeplearning4j-cuda CudnnLocalResponseNormalizationHelper
(the cuDNN LRN helper swapped into LocalResponseNormalization layers) /
libnd4j's lrn declarable op. TPU-first formulation: the sliding channel
window sum is a banded-matrix product — sq @ B where B[i, j] = 1 iff
|i - j| <= depth//2 — one MXU dot per row-block instead of `depth` shifted
VPU adds, with the [R, C] pixels blocked through VMEM.

The backward (r4) is the same band trick in reverse: with
d = k + alpha*ssum, the chain rule gives
    dx = g * d^-beta - 2*alpha*beta * x * ((g * x * d^(-beta-1)) @ B^T),
so one kernel recomputes d (one band dot) and applies the correction (a
second dot contracting the band's other axis — no transposed copy is
materialized). No residuals are saved: LRN sits between convs where HBM
bandwidth is the scarce resource, and the recompute is 2 MXU dots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl


def _lrn_kernel(x_ref, band_ref, o_ref, *, alpha, beta, k):
    x = x_ref[...].astype(jnp.float32)          # [br, C]
    band = band_ref[...].astype(jnp.float32)    # [C, C]
    sq = x * x
    ssum = jax.lax.dot_general(sq, band, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    o_ref[...] = (x / (k + alpha * ssum) ** beta).astype(o_ref.dtype)


def _band(C, depth):
    # the XLA lowering's window spans offsets [-half, depth-1-half] (exactly
    # `depth` channels — asymmetric when depth is even). Output channel j of
    # sq @ band sums input channels i with band[i, j] = 1, so the condition
    # is on i - j.
    half = depth // 2
    idx = jnp.arange(C)
    off = idx[:, None] - idx[None, :]
    return ((off >= -half) & (off <= depth - 1 - half)).astype(jnp.float32)


def _lrn_forward(x, *, depth, alpha, beta, k, block_rows, interpret):
    orig_shape = x.shape
    C = orig_shape[-1]
    xf = x.reshape(-1, C)
    R = xf.shape[0]
    br = min(_lrn_rows(C, 2, block_rows), R)
    band = _band(C, depth)
    out = pl.pallas_call(
        functools.partial(_lrn_kernel, alpha=alpha, beta=beta, k=k),
        name="lrn_fwd",
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((C, C), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xf, band)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn(x, depth, alpha, beta, k, block_rows):
    return _lrn_forward(x, depth=depth, alpha=alpha, beta=beta, k=k,
                        block_rows=block_rows, interpret=interpret_mode())


def _lrn_fwd(x, depth, alpha, beta, k, block_rows):
    return _lrn(x, depth, alpha, beta, k, block_rows), x


def _lrn_bwd_kernel(x_ref, g_ref, band_ref, dx_ref, *, alpha, beta, k):
    x = x_ref[...].astype(jnp.float32)          # [br, C]
    g = g_ref[...].astype(jnp.float32)          # [br, C]
    band = band_ref[...]                        # [C, C] f32
    ssum = jax.lax.dot_general(x * x, band, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    d = k + alpha * ssum
    dpow = d ** (-beta)
    u = g * x * dpow / d                        # g * x * d^(-beta-1)
    # t_i = sum_j u_j band[i, j]: contract the band's SECOND axis — the
    # transposed-band product without materializing a transpose
    t = jax.lax.dot_general(u, band, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    dx_ref[...] = (g * dpow - 2.0 * alpha * beta * x * t).astype(dx_ref.dtype)


def _lrn_rows(C, n_blocks, block_rows=512, budget=13 << 20):
    """Largest row block whose working set fits the VMEM budget:
    ``n_blocks`` double-buffered [br, C] f32 blocks (fwd: x + out = 2;
    bwd: x + g + dx = 3) plus the grid-invariant [C, C] band. At C=1024
    the bwd's three blocks at br=512 would hit ~16.8 MB — over the ~16M
    scoped limit — so the bwd steps down to br=256 there."""
    br = block_rows
    while br > 8 and 2 * n_blocks * br * C * 4 + C * C * 4 > budget:
        br //= 2
    return br


def _lrn_backward(x, g, *, depth, alpha, beta, k, block_rows, interpret):
    orig_shape = x.shape
    C = orig_shape[-1]
    xf = x.reshape(-1, C)
    gf = g.reshape(-1, C)
    R = xf.shape[0]
    br = min(_lrn_rows(C, 3, block_rows), R)
    band = _band(C, depth)
    dx = pl.pallas_call(
        functools.partial(_lrn_bwd_kernel, alpha=alpha, beta=beta, k=k),
        name="lrn_bwd",
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((C, C), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xf, gf, band)
    return dx.reshape(orig_shape)


def _lrn_bwd(depth, alpha, beta, k, block_rows, x, g):
    return (_lrn_backward(x, g, depth=depth, alpha=alpha, beta=beta, k=k,
                          block_rows=block_rows,
                          interpret=interpret_mode()),)


_lrn.defvjp(_lrn_fwd, _lrn_bwd)


def pallas_lrn(x, *, depth=5, alpha=1e-4, beta=0.75, k=2.0,
               block_rows: int = 512):
    """Public entry: same signature as the XLA lrn lowering."""
    return _lrn(x, depth, float(alpha), float(beta), float(k), block_rows)


def _lrn_requires(x, *, depth=5, **kw):
    # structural: enough pixels to fill row blocks; modest channel count so
    # the [C, C] band plus a row block fit VMEM comfortably
    n = 1
    for d in x.shape[:-1]:
        n *= d
    return n >= 2048 and 32 <= x.shape[-1] <= 1024


def _lrn_applicable(x, *, depth=5, **kw):
    """Default-ON (r4, measured, two-point on-chip A/B at the AlexNet conv2
    shape [64,27,27,256]): fwd 1.26x, train 1.47x. The r3 demotion (train
    0.45x) was caused by the backward recomputing through the XLA lowering
    — the grad path paid kernel-fwd PLUS a full XLA fwd+bwd; the r4 banded
    backward kernel (_lrn_bwd_kernel) removed that tax. Beyond the
    structural requires() bounds (enough rows to fill blocks, band fits
    VMEM), the only gate is dtype: the A/B evidence covers f32/bf16 — the
    MXU-native dtypes the band contraction was tuned for — so anything
    else (f64 emulation, exotic inputs) stays on the measured-safe XLA
    lowering."""
    return x.dtype in (jnp.float32, jnp.bfloat16)


register_impl("lrn", platform="pallas", predicate=_lrn_applicable,
              requires=_lrn_requires, priority=1,
              scope="lrn")(pallas_lrn)
