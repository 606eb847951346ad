"""Token mixers a ``DecoderBlock`` takes in place of its rotary softmax
attention (``mixer=``) — net-new (hybrid linear-attention decoders; no DL4J
analog).

``GatedDeltaRuleLayer``: Kimi Linear's KDA mixer (arXiv:2510.26692). With ``h``
the block's normed input, per head of ``head_dim``:

    q~, k~, v = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
    q = q~ / |q~| / sqrt(head_dim);  k = k~ / |k~|
    g = -exp(A_log) * softplus((h Wfa) Wfb + dt_bias)          (float32, per key feature)
    beta = sigmoid(h Wb)                                        (one a head)
    o = op("gated_delta_rule")(q, k, v, g, beta)
    y = (RMSNorm(o; o_g) * sigmoid((h Wga) Wgb)) Wo

``conv`` a causal depthwise convolution of ``conv_size`` taps (``y_t = sum_i
w[c, i] u[t - conv_size + 1 + i]``, zeros before a row's start). Its state is
one float for a monitor, ``delta_log_decay_min``: the most negative log-decay
summed over one chunk of the op in the step. What lies before the op and what
lies after it are each a ``jax.checkpoint`` of their own: the backward keeps the
three projections (by name), the op's operands and its output, and computes the
element-wise chains between them again (the convolutions, SiLU, the norms, the
gates through their low-rank pairs: two dozen arrays of ``[T, n_heads x
head_dim]``, half of them float32, 3 GB a layer at 8,192 tokens and 32 heads of
128, for 0.4 kept).

``LatentAttentionLayer``: latent (compressed key-value) attention without
positions, as trained: ``q = h Wq`` as ``n_heads`` heads of ``nope_dim +
shared_dim``; ``c = h Wkva`` in ``R^{kv_rank + shared_dim}``; ``[k_nope | v] =
RMSNorm(c[:kv_rank]) Wkvb`` a head; every head's key ends in the same
``c[kv_rank:]``; causal softmax attention through the op registry (the flash
kernel takes a value narrower than the key), then ``Wo``.

``SparseAttentionLayer``: grouped-head attention over the keys a learned
indexer chooses (DeepSeek Sparse Attention, ``ops/sparse_attention.py``).
``q = Nq(h Wq)``, ``k = Nk(h Wk)`` (RMSNorm over a head's features), ``v = h
Wv``, ``n_heads`` query heads over ``n_kv_heads`` key-value heads, q and k
turned by M-RoPE (``rotary_tables`` with ``mrope_sections``, from the position
rows given, or on text the plain positions three times). Under the scope
``indexer``, from ``sg(h)`` (the indexer learns from its own loss alone):
``q_i = h Wq_i`` as ``index_heads`` heads of ``index_head_dim``, ``k_i =
LN(h Wk_i)`` (a LayerNorm with gain and bias, eps ``norm_eps``), the first
``index_rope_dim`` features of both turned by plain rotary positions, ``w = h
Ww_i / sqrt(index_heads x index_head_dim)``; the choice
``op("indexer_topk")`` of ``topk`` keys a query, kept under ``remat`` by name
(``SELECTED``). Then ``op("sparse_attention")`` over the choice (the op's own
scope), ``Wo``, and under ``indexer`` again the indexer's loss
``op("indexer_loss")`` against the attention's weights, handed to the score
as ``LOSS_TERM + "_indexer"`` (over the rows, as the score sums a row's
positions), and the share of the causal ``TILE x TILE`` tiles the choice
reaches (``SPARSE_TILES``), a gauge.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import _attn_mask, apply_rotary, rms_norm, rotary_tables
from deeplearning4j_tpu.nn.layers.base import LOSS_TERM, SELECTED, Layer, register_layer
from deeplearning4j_tpu.ops.registry import op
import deeplearning4j_tpu.ops.attention  # noqa: F401
from deeplearning4j_tpu.ops.delta_rule import chunk_log_decay_min      # registers the op too
from deeplearning4j_tpu.ops.sparse_attention import tiles_visited_share   # registers the ops too

#: the KDA layer's gauge, the key of its state
DELTA_STAT = "delta_log_decay_min"
#: ``checkpoint_name`` of the q, k and v projections' results, which the layer's
#: own checkpoint keeps: XLA's products, not worth running a third time
PROJECTED = "delta_mixer_projected"
#: the sparse-attention mixer's state: its term of the score (the indexer's
#: loss) and its gauge (the share of the causal tiles its choice reaches)
INDEXER_LOSS = LOSS_TERM + "_indexer"
SPARSE_TILES = "sparse_tiles_visited"


def causal_depthwise_conv(u, w):
    """``y[..., t, c] = sum_i w[..., c, i] u[..., t - taps + 1 + i, c]``, zeros
    before the row's start: ``u`` ``[..., T, C]``, ``w`` ``[C, taps]`` (or with
    leading axes that broadcast against ``u``'s, a head's own taps)."""
    taps, t = w.shape[-1], u.shape[-2]
    padded = jnp.pad(u, [(0, 0)] * (u.ndim - 2) + [(taps - 1, 0), (0, 0)])
    return sum(padded[..., i:i + t, :] * w[..., None, :, i] for i in range(taps))


def l2_normalize(x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt((xf * xf).sum(-1, keepdims=True) + eps)).astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class GatedDeltaRuleLayer(Layer):
    d_model: int
    n_heads: int = 32
    head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128            # the width of the decay's and the output gate's low-rank pairs
    norm_eps: float = 1e-5

    def output_type(self, itype):
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        D, H, R = self.d_model, self.n_heads, self.gate_rank
        A = H * self.head_dim
        ks = jax.random.split(key, 14)
        p = {"Wq": self._w(ks[0], (D, A)), "Wk": self._w(ks[1], (D, A)),
             "Wv": self._w(ks[2], (D, A)), "Wo": self._w(ks[3], (A, D)),
             "Wfa": self._w(ks[4], (D, R)), "Wfb": self._w(ks[5], (R, A)),
             "Wga": self._w(ks[6], (D, R)), "Wgb": self._w(ks[7], (R, A)),
             "Wb": self._w(ks[8], (D, H)), "o_g": jnp.ones((self.head_dim,))}
        for name, k in zip(("conv_q", "conv_k", "conv_v"), ks[9:12]):
            p[name] = self._w(k, (A, self.conv_size), fan_in=self.conv_size, fan_out=1)
        # a decay rate of 1-16 a head times a step of 0.001-0.1 a feature
        p["A_log"] = jnp.log(jax.random.uniform(ks[12], (H,), minval=1.0, maxval=16.0))
        dt = jnp.exp(jax.random.uniform(ks[13], (A,), minval=math.log(1e-3), maxval=math.log(0.1)))
        p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))        # softplus(dt_bias) = dt
        return p, {DELTA_STAT: jnp.zeros((), jnp.float32)}

    def _by_head(self, w):
        """``[..., n_heads x head_dim]`` -> ``[..., n_heads, head_dim]``: the
        projections write ``[B, n_heads, T, head_dim]`` straight from the product
        (``btd,dhk->bhtk``), the layout the op works in: as ``[B, T, n_heads x
        head_dim]`` turned afterwards, every operand and every gradient of the
        op crossed the memory once more in each direction."""
        return w.reshape(*w.shape[:-1], self.n_heads, self.head_dim)

    def _operands(self, params, x):
        """``q, k, v, g, beta`` of the op, each ``[B, n_heads, T, ...]``."""
        dh = self.head_dim
        with jax.named_scope("conv"):
            q, k, v = (jax.nn.silu(causal_depthwise_conv(
                checkpoint_name(jnp.einsum("btd,dhk->bhtk", x, self._by_head(params["W" + n])),
                                PROJECTED),
                params["conv_" + n].reshape(1, self.n_heads, dh, -1)))
                for n in "qkv")
            q = l2_normalize(q) * dh ** -0.5
            k = l2_normalize(k)
        with jax.named_scope("gates"):
            step = jax.nn.softplus(
                jnp.einsum("btr,rhk->bhtk", x @ params["Wfa"],
                           self._by_head(params["Wfb"])).astype(jnp.float32)
                + self._by_head(params["dt_bias"]).astype(jnp.float32)[:, None, :])
            rate = jnp.exp(params["A_log"].astype(jnp.float32))
            g = -rate[:, None, None] * step                         # [B, H, T, dh] <= 0
            beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bht", x, params["Wb"]).astype(jnp.float32))
        return q, k, v, g, beta

    def _output(self, params, x, o):
        with jax.named_scope("gates"):
            out_gate = jax.nn.sigmoid(jnp.einsum("btr,rhk->bhtk", x @ params["Wga"],
                                                 self._by_head(params["Wgb"])))
        with jax.named_scope("out_norm"):
            o = rms_norm(o, params["o_g"], self.norm_eps) * out_gate
        wo = params["Wo"].reshape(self.n_heads, self.head_dim, -1)
        return jnp.einsum("bhtk,hkd->btd", o, wo)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        q, k, v, g, beta = jax.checkpoint(
            self._operands, policy=jax.checkpoint_policies.save_only_these_names(PROJECTED))(
                params, x)
        with jax.named_scope("gates"):
            decay_min = jax.lax.stop_gradient(chunk_log_decay_min(g))
        o, _ = op("gated_delta_rule")(q, k, v, g, beta)
        return jax.checkpoint(self._output)(params, x, o), {**state, DELTA_STAT: decay_min}


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class LatentAttentionLayer(Layer):
    d_model: int
    n_heads: int = 32
    nope_dim: int = 128             # a head's own key features
    shared_dim: int = 64            # key features every head shares
    v_dim: int = 128
    kv_rank: int = 512
    norm_eps: float = 1e-5

    def output_type(self, itype):
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        D, H = self.d_model, self.n_heads
        ks = jax.random.split(key, 4)
        return {"Wq": self._w(ks[0], (D, H * (self.nope_dim + self.shared_dim))),
                "Wkva": self._w(ks[1], (D, self.kv_rank + self.shared_dim)),
                "kv_g": jnp.ones((self.kv_rank,)),
                "Wkvb": self._w(ks[2], (self.kv_rank, H * (self.nope_dim + self.v_dim))),
                "Wo": self._w(ks[3], (H * self.v_dim, D))}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        B, T, _ = x.shape
        H = self.n_heads

        def heads(t):
            return t.reshape(B, T, H, -1).transpose(0, 2, 1, 3)

        q = heads(x @ params["Wq"])
        c = x @ params["Wkva"]
        kv = heads(rms_norm(c[..., :self.kv_rank], params["kv_g"], self.norm_eps)
                   @ params["Wkvb"])
        shared = jnp.broadcast_to(c[:, None, :, self.kv_rank:], (B, H, T, self.shared_dim))
        k = jnp.concatenate([kv[..., :self.nope_dim], shared], axis=-1)
        o = op("dot_product_attention")(
            q, k, kv[..., self.nope_dim:], mask=_attn_mask(mask, T, T), causal=True)
        return o.transpose(0, 2, 1, 3).reshape(B, T, H * self.v_dim) @ params["Wo"], state


def layer_norm(x, gain, bias, eps: float):
    """``(x - mean) / sqrt(var + eps) * gain + bias`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * gain + bias).astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class SparseAttentionLayer(Layer):
    d_model: int
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_sections: tuple = (16, 24, 24)
    norm_eps: float = 1e-6
    index_heads: int = 16
    index_head_dim: int = 64
    index_rope_dim: int = 32        # the features of q_i and k_i that rotary positions turn
    topk: int = 2048

    def output_type(self, itype):
        return InputType.recurrent(self.d_model, itype.shape[0])

    def init(self, key, itype):
        D, dh, HI, dI = self.d_model, self.head_dim, self.index_heads, self.index_head_dim
        ks = jax.random.split(key, 7)
        p = {"Wq": self._w(ks[0], (D, self.n_heads * dh)),
             "Wk": self._w(ks[1], (D, self.n_kv_heads * dh)),
             "Wv": self._w(ks[2], (D, self.n_kv_heads * dh)),
             "Wo": self._w(ks[3], (self.n_heads * dh, D)),
             "q_g": jnp.ones((dh,)), "k_g": jnp.ones((dh,)),
             "indexer": {"Wq": self._w(ks[4], (D, HI * dI)), "Wk": self._w(ks[5], (D, dI)),
                         "Ww": self._w(ks[6], (D, HI)),
                         "k_norm_g": jnp.ones((dI,)), "k_norm_b": jnp.zeros((dI,))}}
        return p, {INDEXER_LOSS: jnp.zeros((), jnp.float32),
                   SPARSE_TILES: jnp.zeros((), jnp.float32)}

    def text_positions(self, seq: int):
        """``[sections, seq]``: text's one position a token, on every row."""
        return jnp.broadcast_to(jnp.arange(seq), (len(self.mrope_sections), seq))

    def _indexer(self, p, h, positions):
        """``(q_i [B, H_I, T, d_I], k_i [B, T, d_I], w [B, T, H_I] float32)``."""
        B, T, _ = h.shape
        HI, dI, r = self.index_heads, self.index_head_dim, self.index_rope_dim
        rope = rotary_tables(positions[:1], r, self.rope_theta, sections=(r // 2,))

        def turned(t):
            return jnp.concatenate([apply_rotary(t[..., :r], rope), t[..., r:]], axis=-1)

        q_i = turned((h @ p["Wq"]).reshape(B, T, HI, dI).transpose(0, 2, 1, 3))
        k_i = turned(layer_norm(h @ p["Wk"], p["k_norm_g"], p["k_norm_b"], self.norm_eps))
        w = (h @ p["Ww"]).astype(jnp.float32) * (HI * dI) ** -0.5
        return q_i, k_i, w

    def apply(self, params, state, x, *, train=False, rng=None, mask=None, positions=None):
        if mask is not None:
            raise ValueError("SparseAttentionLayer takes no padding mask: its choice is of all keys")
        B, T, _ = x.shape
        H, KV, dh = self.n_heads, self.n_kv_heads, self.head_dim
        positions = self.text_positions(T) if positions is None else positions
        rope = rotary_tables(positions, dh, self.rope_theta, sections=tuple(self.mrope_sections))

        def heads(t, n):
            return t.reshape(B, T, n, dh).transpose(0, 2, 1, 3)

        q = apply_rotary(rms_norm(heads(x @ params["Wq"], H), params["q_g"], self.norm_eps), rope)
        k = apply_rotary(rms_norm(heads(x @ params["Wk"], KV), params["k_g"], self.norm_eps), rope)
        v = heads(x @ params["Wv"], KV)
        with jax.named_scope("indexer"):
            q_i, k_i, w = self._indexer(params["indexer"], jax.lax.stop_gradient(x), positions)
            chosen = checkpoint_name(op("indexer_topk")(q_i, k_i, w, topk=self.topk), SELECTED)
        o, lse = op("sparse_attention")(q, k, v, chosen)
        with jax.named_scope("indexer"):
            loss = op("indexer_loss")(q, k, lse, chosen, q_i, k_i, w)
            tiles = jax.lax.stop_gradient(tiles_visited_share(chosen))
        y = o.transpose(0, 2, 1, 3).reshape(B, T, H * dh) @ params["Wo"]
        return y, {**state, INDEXER_LOSS: loss / B, SPARSE_TILES: tiles}
