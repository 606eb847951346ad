"""Attention ops.

Reference analog: libnd4j dot_product_attention / multi_head_dot_product_attention
(libnd4j/include/ops/declarable/generic/nn/attention/**) used by DL4J's
SelfAttentionLayer. TPU-first: the registry's plain lowering is a blockwise-
friendly softmax(QK^T)V that XLA fuses well at small scale; a Pallas flash
-attention kernel registers over it for long sequences (see
ops/pallas/flash_attention.py), selected by predicate on seq length — the
cuDNN-helper pattern.

Layouts: q/k/v [B, N, T, Dh] (batch, heads, time, head_dim).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.registry import op, register_op


@register_op("dot_product_attention")
def dot_product_attention(q, k, v, *, mask=None, bias=None, scale=None,
                          causal=False, window=None):
    """softmax(q k^T / sqrt(d)) v.

    k / v may hold fewer heads than q (a divisor of its count): query head h
    reads key-value head ``h // (N // Nkv)``. ``window`` (with ``causal``):
    query i sees keys j with ``0 <= i - j < window``.

    mask: broadcastable to [B, N, Tq, Tk], 1=keep 0=drop (additive -inf applied).
    bias: broadcastable to [B, N, Tq, Tk], ADDED to the scaled logits before
    the softmax — the exporter-style additive attention mask / relative
    position bias form the import-graph optimizer's fused-attention rewrite
    produces. The Pallas flash kernel structurally rejects bias-carrying
    calls (registry routes them here).
    """
    if window is not None and not causal:
        raise ValueError("dot_product_attention's window is a causal one: pass causal=True")
    if k.shape[1] != q.shape[1]:
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    logits = jnp.einsum("bntd,bnsd->bnts", q, k) * scale
    if bias is not None:
        logits = logits + bias
    neg = jnp.finfo(logits.dtype).min
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            cm &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        logits = jnp.where(cm, logits, neg)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, neg)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnts,bnsd->bntd", w, v)


@register_op("cached_dot_product_attention")
def cached_dot_product_attention(q, k_cache, v_cache, pos, *, scale=None,
                                 k_scale=None, v_scale=None):
    """Single-query decode attention over a KV ring buffer.

    q [B, N, 1, Dh]; k_cache/v_cache [B, N, L, Dh]; pos [B] — the absolute
    position of the query token (its k/v already written at ``pos % L`` by
    the caller). Cache index c is valid when c <= pos (pre-wrap) or always
    once pos >= L (ring full: the L most recent positions). Validity is a
    SET property — with the positional signal added at the embedding, the
    softmax is order-free, so the wrapped window needs no unwrapping.

    This is the generation engine's one-compiled-decode-step workhorse: the
    shapes never change across the serving lifetime, so the surrounding
    step jits exactly once. The Pallas flash kernel never applies here
    (Tq=1 is launch-bound, not memory-bound — the PyGraph lever is replay,
    not tiling), so this op registers only the plain XLA lowering.

    Int8 cache mode: the caches may be int8 with per-(batch, head) absmax
    scales ``k_scale``/``v_scale`` [B, N]. Because the scale is constant
    over both the sequence axis and the head dim, dequantization commutes
    out of the contractions: ``k_scale`` multiplies the logits and
    ``v_scale`` the output — exact w.r.t. the dequantized cache, without
    ever materializing it.
    """
    d = q.shape[-1]
    L = k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    logits = jnp.einsum("bntd,bnsd->bnts", q,
                        k_cache.astype(q.dtype)) * scale  # [B,N,1,L]
    if k_scale is not None:
        logits = logits * k_scale.astype(q.dtype)[:, :, None, None]
    valid = (jnp.arange(L)[None, :] <= pos[:, None]) | (pos[:, None] >= L)
    neg = jnp.finfo(logits.dtype).min
    logits = jnp.where(valid[:, None, None, :], logits, neg)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnts,bnsd->bntd", w, v_cache.astype(q.dtype))
    if v_scale is not None:
        out = out * v_scale.astype(q.dtype)[:, :, None, None]
    return out


@register_op("multi_head_attention")
def multi_head_attention(x_q, x_kv, Wq, Wk, Wv, Wo, *, n_heads, mask=None, causal=False,
                         bq=None, bk=None, bv=None, bo=None):
    """Full MHA: project, attend, merge. x [B, T, F]; W* [F, D]; Wo [D, F_out]."""
    B, Tq, _ = x_q.shape
    Tk = x_kv.shape[1]
    q = x_q @ Wq + (0 if bq is None else bq)
    k = x_kv @ Wk + (0 if bk is None else bk)
    v = x_kv @ Wv + (0 if bv is None else bv)
    Dh = q.shape[-1] // n_heads

    def split(t, T):
        return t.reshape(B, T, n_heads, Dh).transpose(0, 2, 1, 3)

    # through the registry so the Pallas flash kernel is reachable; its
    # `requires` rejects masked/misaligned-causal calls even under FORCE_PALLAS
    o = op("dot_product_attention")(split(q, Tq), split(k, Tk), split(v, Tk),
                                    mask=mask, causal=causal)
    o = o.transpose(0, 2, 1, 3).reshape(B, Tq, n_heads * Dh)
    return o @ Wo + (0 if bo is None else bo)
