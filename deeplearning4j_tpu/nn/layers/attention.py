"""Attention layers.

Reference analog: org.deeplearning4j.nn.conf.layers.{SelfAttentionLayer,
LearnedSelfAttentionLayer, RecurrentAttentionLayer} [UNVERIFIED in snapshot]
built on libnd4j's multi_head_dot_product_attention. Extended net-new with a
full pre-norm TransformerEncoderLayer (the BERT building block the reference
reaches only via TF-import) and a DecoderBlock (rotary positions, RMSNorm
before and after each half, a gated MLP, no biases).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    Layer, register_layer, resolve_activation, scope_name,
)
from deeplearning4j_tpu.ops.registry import op
import deeplearning4j_tpu.ops.attention  # noqa: F401


def _attn_mask(mask, Tq, Tk):
    if mask is None:
        return None
    return mask[:, None, None, :].astype(bool)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over [B,T,F] (org...SelfAttentionLayer)."""

    n_out: int
    n_heads: int = 1
    head_size: Optional[int] = None
    n_in: Optional[int] = None
    project_input: bool = True

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.shape[0])

    def init(self, key, itype):
        nin = self.n_in or itype.shape[1]
        hs = self.head_size or self.n_out // self.n_heads
        D = hs * self.n_heads
        ks = jax.random.split(key, 4)
        return {
            "Wq": self._w(ks[0], (nin, D)),
            "Wk": self._w(ks[1], (nin, D)),
            "Wv": self._w(ks[2], (nin, D)),
            "Wo": self._w(ks[3], (D, self.n_out)),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y = op("multi_head_attention")(
            x, x, params["Wq"], params["Wk"], params["Wv"], params["Wo"],
            n_heads=self.n_heads, mask=_attn_mask(mask, x.shape[1], x.shape[1]),
        )
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class LearnedSelfAttentionLayer(SelfAttentionLayer):
    """Attention with n_queries learned query vectors (org...LearnedSelfAttentionLayer).

    Output is [B, n_queries, n_out] — fixed-size summary of a variable sequence.
    """

    n_queries: int = 1

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, self.n_queries)

    def init(self, key, itype):
        p, s = super().init(key, itype)
        nin = self.n_in or itype.shape[1]
        kq = jax.random.fold_in(key, 7)
        p["Q"] = self._w(kq, (self.n_queries, nin))
        return p, s

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        q = jnp.broadcast_to(params["Q"], (x.shape[0],) + params["Q"].shape)
        y = op("multi_head_attention")(
            q, x, params["Wq"], params["Wk"], params["Wv"], params["Wo"],
            n_heads=self.n_heads, mask=_attn_mask(mask, self.n_queries, x.shape[1]),
        )
        return y, state

    def feed_forward_mask(self, mask, itype):
        return None


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class PositionalEmbeddingLayer(Layer):
    """Adds learned positional embeddings to [B,T,F] — net-new (BERT-style)."""

    max_len: int = 512
    n_out: Optional[int] = None

    def init(self, key, itype):
        d = self.n_out or itype.shape[1]
        return {"P": 0.02 * jax.random.normal(key, (self.max_len, d))}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t = x.shape[1]
        return x + params["P"][:t], state


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class TransformerEncoderLayer(Layer):
    """Pre-norm transformer encoder block — net-new (BERT/GPT building block).

    MHA + residual + LN, then MLP(gelu) + residual + LN.
    """

    d_model: int
    n_heads: int = 8
    d_ff: Optional[int] = None
    activation: str = "gelu"
    dropout_rate: float = 0.0
    causal: bool = False
    pre_norm: bool = True

    def output_type(self, itype):
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        D = self.d_model
        dff = self.d_ff or 4 * D
        ks = jax.random.split(key, 6)
        return {
            "Wq": self._w(ks[0], (D, D)), "Wk": self._w(ks[1], (D, D)),
            "Wv": self._w(ks[2], (D, D)), "Wo": self._w(ks[3], (D, D)),
            "bq": jnp.zeros((D,)), "bk": jnp.zeros((D,)),
            "bv": jnp.zeros((D,)), "bo": jnp.zeros((D,)),
            "W1": self._w(ks[4], (D, dff)), "b1": jnp.zeros((dff,)),
            "W2": self._w(ks[5], (dff, D)), "b2": jnp.zeros((D,)),
            "ln1_g": jnp.ones((D,)), "ln1_b": jnp.zeros((D,)),
            "ln2_g": jnp.ones((D,)), "ln2_b": jnp.zeros((D,)),
        }, {}

    def _ln(self, x, g, b):
        m = x.mean(-1, keepdims=True)
        v = x.var(-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * g + b

    def _drop(self, x, train, rng):
        if not train or self.dropout_rate <= 0 or rng is None:
            return x
        keep = 1.0 - self.dropout_rate
        return jnp.where(jax.random.bernoulli(rng, keep, x.shape), x / keep, 0.0).astype(x.dtype)

    # ---------------------------------------------- decode (KV-cache) path
    def _split_heads(self, t):
        """[B, ..., N*Dh] -> [B, N, ..., Dh] (leading batch, heads axis 1)."""
        B = t.shape[0]
        Dh = self.d_model // self.n_heads
        if t.ndim == 2:                       # single step [B, D]
            return t.reshape(B, self.n_heads, Dh)
        return t.reshape(B, t.shape[1], self.n_heads, Dh).transpose(0, 2, 1, 3)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   kv_dtype=None):
        """Per-sequence KV ring buffers for cached decode: (k, v), each
        [batch, n_heads, max_len, head_dim]. With ``kv_dtype="int8"`` the
        buffers are int8 and the cache is the 4-tuple (k, v, k_scale,
        v_scale) with per-(row, head) running absmax scales."""
        Dh = self.d_model // self.n_heads
        shape = (batch, self.n_heads, max_len, Dh)
        if kv_dtype == "int8":
            return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                    jnp.zeros((batch, self.n_heads), jnp.float32),
                    jnp.zeros((batch, self.n_heads), jnp.float32))
        if kv_dtype is not None:
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    def _mlp_half(self, x, params):
        h = self._ln(x, params["ln2_g"], params["ln2_b"]) if self.pre_norm else x
        m = resolve_activation(self.activation)(h @ params["W1"] + params["b1"])
        x = x + (m @ params["W2"] + params["b2"])
        if not self.pre_norm:
            x = self._ln(x, params["ln2_g"], params["ln2_b"])
        return x

    def apply_step(self, params, x, cache, pos):
        """One decode step from the KV cache: x [B, D] (the current token's
        activations), cache (k, v) [B, N, L, Dh], pos [B] absolute positions
        (write index = pos % L). Returns (y [B, D], new_cache). Numerically
        identical to ``apply`` with ``causal=True`` over the full prefix —
        the witness tests/test_generation.py holds it to 1e-5.

        The cache may also be the int8 4-tuple from ``init_cache(...,
        kv_dtype="int8")``; the ring write then quantizes in place against
        per-(row, head) running absmax scales and the attention op
        dequantizes on its accumulator outputs."""
        int8_mode = len(cache) == 4
        if int8_mode:
            k_cache, v_cache, k_sc, v_sc = cache
        else:
            k_cache, v_cache = cache
        L = k_cache.shape[2]
        B = x.shape[0]
        h = self._ln(x, params["ln1_g"], params["ln1_b"]) if self.pre_norm else x
        q = self._split_heads(h @ params["Wq"] + params["bq"])   # [B, N, Dh]
        k = self._split_heads(h @ params["Wk"] + params["bk"])
        v = self._split_heads(h @ params["Wv"] + params["bv"])
        slot = pos % L
        rows = jnp.arange(B)
        if int8_mode:
            from deeplearning4j_tpu.quantize.kvcache import ring_write_quantized
            k_cache, k_sc = ring_write_quantized(k_cache, k_sc, k, rows, slot)
            v_cache, v_sc = ring_write_quantized(v_cache, v_sc, v, rows, slot)
            o = op("cached_dot_product_attention")(
                q[:, :, None, :], k_cache, v_cache, pos,
                k_scale=k_sc, v_scale=v_sc)                        # [B,N,1,Dh]
            new_cache = (k_cache, v_cache, k_sc, v_sc)
        else:
            k_cache = k_cache.at[rows, :, slot].set(k)
            v_cache = v_cache.at[rows, :, slot].set(v)
            o = op("cached_dot_product_attention")(
                q[:, :, None, :], k_cache, v_cache, pos)           # [B,N,1,Dh]
            new_cache = (k_cache, v_cache)
        o = o[:, :, 0, :].reshape(B, self.n_heads * (self.d_model // self.n_heads))
        x = x + (o @ params["Wo"] + params["bo"])
        if not self.pre_norm:
            x = self._ln(x, params["ln1_g"], params["ln1_b"])
        return self._mlp_half(x, params), new_cache

    def apply_prefill(self, params, x, *, mask=None):
        """Causal forward over the whole prompt that ALSO returns the K/V
        heads ([B, N, T, Dh] each) so the generation engine can seed a
        slot's cache in one pass. Right-padding is safe: under the causal
        mask, position i only ever attends to j <= i, so K/V rows below
        the true length are exact regardless of the padding."""
        am = _attn_mask(mask, x.shape[1], x.shape[1])
        h = self._ln(x, params["ln1_g"], params["ln1_b"]) if self.pre_norm else x
        q = self._split_heads(h @ params["Wq"] + params["bq"])
        k = self._split_heads(h @ params["Wk"] + params["bk"])
        v = self._split_heads(h @ params["Wv"] + params["bv"])
        o = op("dot_product_attention")(q, k, v, mask=am, causal=True)
        B, T = x.shape[0], x.shape[1]
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        x = x + (o @ params["Wo"] + params["bo"])
        if not self.pre_norm:
            x = self._ln(x, params["ln1_g"], params["ln1_b"])
        return self._mlp_half(x, params), (k, v)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        r1, r2 = jax.random.split(rng) if rng is not None else (None, None)
        am = _attn_mask(mask, x.shape[1], x.shape[1])

        h = self._ln(x, params["ln1_g"], params["ln1_b"]) if self.pre_norm else x
        a = op("multi_head_attention")(
            h, h, params["Wq"], params["Wk"], params["Wv"], params["Wo"],
            n_heads=self.n_heads, mask=am, causal=self.causal,
            bq=params["bq"], bk=params["bk"], bv=params["bv"], bo=params["bo"],
        )
        x = x + self._drop(a, train, r1)
        if not self.pre_norm:
            x = self._ln(x, params["ln1_g"], params["ln1_b"])

        h = self._ln(x, params["ln2_g"], params["ln2_b"]) if self.pre_norm else x
        m = resolve_activation(self.activation)(h @ params["W1"] + params["b1"])
        m = m @ params["W2"] + params["b2"]
        x = x + self._drop(m, train, r2)
        if not self.pre_norm:
            x = self._ln(x, params["ln2_g"], params["ln2_b"])
        return x, state


def _inv_freq(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original_positions: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's ``head_dim / 2`` inverse frequencies, as ``transformers`` computes
    them for a static ``rope_type: yarn`` section: the plain ``theta ** (-2 i /
    head_dim)`` for the pairs that turn more than ``beta_fast`` times over
    ``original_positions`` (extrapolated), that over ``factor`` for those that
    turn less than ``beta_slow`` times (interpolated), a linear ramp between."""
    def correction_dim(turns):
        return head_dim * math.log(original_positions / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    plain = _inv_freq(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_tables(positions: int, head_dim: int, theta: float, yarn=None):
    """(cos, sin), each ``[positions, head_dim]`` in float32, of rotary
    position embeddings in the rotate-half pairing: feature ``i`` is paired
    with ``i + head_dim / 2`` and both turn by ``position * theta ** (-2 i /
    head_dim)``. ``yarn``: ``(factor, original_positions, beta_fast, beta_slow,
    attention_factor)`` scales the frequencies by ``yarn_inv_freq`` and
    multiplies cos and sin by the attention factor, at every length."""
    inv_freq = (_inv_freq(head_dim, theta) if yarn is None
                else yarn_inv_freq(head_dim, theta, *yarn[:4]))
    angles = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos, sin) if yarn is None else (cos * yarn[4], sin * yarn[4])


def apply_rotary(t, rope):
    """Rotate ``t`` ``[B, N, T, Dh]`` by its positions; the turn itself in
    float32, the result in ``t``'s type."""
    cos, sin = rope
    tf = t.astype(jnp.float32)
    half = t.shape[-1] // 2
    turned = jnp.concatenate([-tf[..., half:], tf[..., :half]], axis=-1)
    return (tf * cos + turned * sin).astype(t.dtype)


def rms_norm(x, gain, eps: float):
    """``x / rms(x) * gain`` over the last axis; the mean of squares in float32."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (xf * inv * gain.astype(jnp.float32)).astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class DecoderBlock(Layer):
    """Causal decoder block with rotary positions, RMSNorm and a gated MLP, no
    biases — net-new (the block of looped / modern decoders).

    ``norm="sandwich"``: ``a = Attn(N1(h)); h = h + N2(a); m = MLP(N3(h)); h =
    h + N4(m)``, each ``N`` an RMSNorm with its own gain; ``norm="pre"``: the
    same without ``N2`` and ``N4`` (gains ``n1_g``, ``n3_g`` alone). ``Attn``
    projects to ``n_heads`` query heads and ``n_kv_heads`` key and value heads
    (default as many) of ``head_dim``; with ``qk_norm`` an RMSNorm over each
    head's features on q and on k, one gain each; rotates q and k (``rope_yarn``:
    ``rotary_tables``' scaling); and attends causally, within ``window`` keys
    where one is given, through the op registry (so the flash kernel's predicate
    decides as for every caller). ``MLP(u) = (silu(u Wg) * (u Wu)) Wd``, or the
    layer ``mlp`` (``SparseExpertsLayer``), whose parameters sit under ``"mlp"``
    and whose state is the block's.

    ``apply`` takes ``rope``, the ``rotary_tables`` of its positions, from a
    container that applies many blocks in one step (``LoopedStack``) so that
    they are computed once; alone it makes its own.
    """

    d_model: int
    n_heads: int = 8
    head_dim: Optional[int] = None
    d_ff: Optional[int] = None
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    n_kv_heads: Optional[int] = None
    window: Optional[int] = None
    qk_norm: bool = False
    norm: str = "sandwich"              # or "pre"
    rope_yarn: Optional[tuple] = None   # (factor, original positions, beta_fast, beta_slow, attention factor)
    mlp: Optional[Layer] = None         # None: the gated dense MLP of width d_ff

    @property
    def head_size(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def _gains(self) -> tuple:
        if self.norm not in ("sandwich", "pre"):
            raise ValueError(f"DecoderBlock norm {self.norm!r}: 'sandwich' or 'pre'")
        return ("n1_g", "n2_g", "n3_g", "n4_g") if self.norm == "sandwich" else ("n1_g", "n3_g")

    def output_type(self, itype):
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        D, A = self.d_model, self.n_heads * self.head_size
        KV = self.kv_heads * self.head_size
        F = self.d_ff or 4 * D
        ks = jax.random.split(key, 7)
        p = {"Wq": self._w(ks[0], (D, A)), "Wk": self._w(ks[1], (D, KV)),
             "Wv": self._w(ks[2], (D, KV)), "Wo": self._w(ks[3], (A, D))}
        state = {}
        if self.mlp is None:
            p.update(Wg=self._w(ks[4], (D, F)), Wu=self._w(ks[5], (D, F)),
                     Wd=self._w(ks[6], (F, D)))
        else:
            p["mlp"], state = self.mlp.init(ks[4], self.output_type(itype))
        for g in self._gains:
            p[g] = jnp.ones((D,))
        if self.qk_norm:
            p["q_g"], p["k_g"] = jnp.ones((self.head_size,)), jnp.ones((self.head_size,))
        return p, state

    def rope_tables(self, positions: int):
        return rotary_tables(positions, self.head_size, self.rope_theta, self.rope_yarn)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None, rope=None):
        B, T, _ = x.shape
        sandwich = self.norm == "sandwich"
        if rope is None:
            rope = self.rope_tables(T)

        def heads(t, n):
            return t.reshape(B, T, n, self.head_size).transpose(0, 2, 1, 3)

        h = rms_norm(x, params["n1_g"], self.rms_eps)

        def rotated(w, n, gain):
            t = heads(h @ params[w], n)
            if self.qk_norm:
                t = rms_norm(t, params[gain], self.rms_eps)
            return apply_rotary(t, rope)

        q, k = rotated("Wq", self.n_heads, "q_g"), rotated("Wk", self.kv_heads, "k_g")
        o = op("dot_product_attention")(q, k, heads(h @ params["Wv"], self.kv_heads),
                                        mask=_attn_mask(mask, T, T), causal=True,
                                        window=self.window)
        a = o.transpose(0, 2, 1, 3).reshape(B, T, -1) @ params["Wo"]
        x = x + (rms_norm(a, params["n2_g"], self.rms_eps) if sandwich else a)
        h = rms_norm(x, params["n3_g"], self.rms_eps)
        if self.mlp is None:
            m = (jax.nn.silu(h @ params["Wg"]) * (h @ params["Wu"])) @ params["Wd"]
        else:
            with jax.named_scope(scope_name("mlp", self.mlp)):
                m, state = self.mlp.apply(params["mlp"], state, h, train=train, rng=rng,
                                          mask=mask)
        return x + (rms_norm(m, params["n4_g"], self.rms_eps) if sandwich else m), state
