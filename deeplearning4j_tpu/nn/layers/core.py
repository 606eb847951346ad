"""Core feed-forward layers.

Reference analog: org.deeplearning4j.nn.conf.layers.{DenseLayer, ActivationLayer,
DropoutLayer, EmbeddingLayer, EmbeddingSequenceLayer, ElementWiseMultiplicationLayer}
and their impls in org.deeplearning4j.nn.layers.feedforward.**. Net-new beside
them: ``BlockDiffusionNoiseLayer``, block diffusion's noising of a token
sequence before its embedding.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import jax.random

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import (
    LOSS_WEIGHTS, Layer, register_layer, resolve_activation,
)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class DenseLayer(Layer):
    """Fully connected layer: act(x @ W + b).

    Reference: org.deeplearning4j.nn.conf.layers.DenseLayer /
    org.deeplearning4j.nn.layers.feedforward.dense.DenseLayer.
    """

    n_out: int
    n_in: Optional[int] = None
    activation: str = "sigmoid"  # DL4J historical default
    has_bias: bool = True

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def init(self, key, itype):
        nin = self.n_in or itype.size
        p = {"W": self._w(key, (nin, self.n_out))}
        if self.has_bias:
            p["b"] = self._b((self.n_out,))
        return p, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return resolve_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class ActivationLayer(Layer):
    """Applies an activation only (org.deeplearning4j.nn.conf.layers.ActivationLayer)."""

    activation: str = "relu"

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return resolve_activation(self.activation)(x), state


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class DropoutLayer(Layer):
    """Standalone inverted dropout (org.deeplearning4j.nn.conf.layers.DropoutLayer).

    ``rate`` is the DROP probability (DL4J's dropOut field is the *keep*
    probability — we use drop probability, the modern convention; serialization
    notes the field name difference).
    """

    rate: float = 0.5

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not train or self.rate <= 0.0:
            return x, state
        if rng is None:
            raise ValueError("DropoutLayer needs rng during training")
        keep = 1.0 - self.rate
        m = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(m, x / keep, 0.0).astype(x.dtype), state


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class EmbeddingLayer(Layer):
    """Index -> vector lookup, one index per example.

    Reference: org.deeplearning4j.nn.conf.layers.EmbeddingLayer (input: [batch, 1]
    integer indices; equivalent to a Dense layer with one-hot input but O(1)).
    """

    n_out: int
    n_in: Optional[int] = None  # vocab size
    activation: str = "identity"
    has_bias: bool = False

    def output_type(self, itype):
        return InputType.feed_forward(self.n_out)

    def init(self, key, itype):
        vocab = self.n_in or itype.size
        p = {"W": self._w(key, (vocab, self.n_out))}
        if self.has_bias:
            p["b"] = self._b((self.n_out,))
        return p, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        y = params["W"][idx]
        if self.has_bias:
            y = y + params["b"]
        return resolve_activation(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class EmbeddingSequenceLayer(Layer):
    """Sequence of indices -> sequence of vectors.

    Reference: org.deeplearning4j.nn.conf.layers.EmbeddingSequenceLayer.
    Output layout is time-major-free [batch, time, features] (TPU/NTF; DL4J
    uses NCW [batch, features, time] — converted at the model boundary).
    """

    n_out: int
    n_in: Optional[int] = None
    activation: str = "identity"
    has_bias: bool = False
    inference_max_len: Optional[int] = None

    def output_type(self, itype):
        t = itype.shape[0] if itype.kind == "rnn" else None
        return InputType.recurrent(self.n_out, t)

    def init(self, key, itype):
        vocab = self.n_in or (itype.size if itype.kind != "rnn" else itype.shape[1])
        p = {"W": self._w(key, (vocab, self.n_out))}
        if self.has_bias:
            p["b"] = self._b((self.n_out,))
        return p, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = params["W"][idx]  # [B, T, n_out]
        if self.has_bias:
            y = y + params["b"]
        return resolve_activation(self.activation)(y), state


def block_diffusion_noise(key, rows: int, seq: int, block: int, t_min: float):
    """(masked ``[rows, seq]`` bool, weights ``[rows, seq]`` float32) of one
    step: a rate ``t ~ U(t_min, 1)`` drawn for each block of ``block``
    positions of each row, each position masked on its own with probability
    ``t`` of its block, and weighted ``1 / t`` where it is masked, 0 elsewhere.
    ``key`` splits into the rates' key and the positions'."""
    k_rate, k_mask = jax.random.split(key)
    rate = jnp.repeat(jax.random.uniform(k_rate, (rows, seq // block), jnp.float32,
                                         t_min, 1.0), block, axis=1)
    masked = jax.random.uniform(k_mask, (rows, seq), jnp.float32) < rate
    return masked, jnp.where(masked, 1.0 / rate, 0.0)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class BlockDiffusionNoiseLayer(EmbeddingSequenceLayer):
    """Block diffusion's noising and embedding of a clean token sequence —
    net-new (masked diffusion over blocks, BD3-LM; no reference analog).

    Takes ids ``x_0`` ``[batch, L]``; draws the step's noise
    (``block_diffusion_noise``: a rate a block of ``block`` positions, at
    least ``t_min``, a mask a position), writes ``mask_id`` (None: the table's
    last row) at the masked positions of a copy ``x_t``, and embeds ``x_t ||
    x_0``: ``[batch, 2L, n_out]``, the noised copy first. The draw's key is
    ``fold_in(key(noise_seed), noise_step)``, both int32 leaves of the layer's
    state: the seed drawn once at ``init`` from the model's seed, the counter
    0 there and one more after every step. So every step draws afresh, two
    seeds draw apart, and a reader that holds the state draws the same
    (replicas that hold one state draw one mask). The masked positions'
    weights ``1 / t`` (0 elsewhere) go to the output layer's score through the
    new state's ``LOSS_WEIGHTS`` (``BlockDiffusionOutputLayer``), which the
    network takes out again. Runs under the scope ``block_diffusion_noise``.
    """

    block: int = 4
    t_min: float = 1e-3             # MDLM's and BD3-LM's clipped schedule
    mask_id: Optional[int] = None

    def output_type(self, itype):
        t = itype.shape[0] if itype.kind == "rnn" else None
        return InputType.recurrent(self.n_out, None if t is None else 2 * t)

    def init(self, key, itype):
        params, _ = super().init(key, itype)
        seed = jax.random.randint(jax.random.fold_in(key, 1), (), 0,
                                  jnp.iinfo(jnp.int32).max, jnp.int32)
        return params, {"noise_seed": seed, "noise_step": jnp.zeros((), jnp.int32)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("block_diffusion_noise"):
            clean = x.astype(jnp.int32)
            if clean.ndim == 3 and clean.shape[-1] == 1:
                clean = clean[..., 0]
            rows, seq = clean.shape
            if mask is not None:
                raise ValueError("BlockDiffusionNoiseLayer takes whole rows: no padding mask")
            if seq % self.block:
                raise ValueError(f"{seq} positions are not whole blocks of {self.block}")
            seed, step = state["noise_seed"], state["noise_step"]
            masked, weights = block_diffusion_noise(
                jax.random.fold_in(jax.random.key(seed), step), rows, seq, self.block,
                self.t_min)
            mask_id = params["W"].shape[0] - 1 if self.mask_id is None else self.mask_id
            noised = jnp.where(masked, mask_id, clean)
            y, _ = super().apply(params, state, jnp.concatenate([noised, clean], axis=1))
        return y, {"noise_seed": seed, "noise_step": step + 1, LOSS_WEIGHTS: weights}


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class ElementWiseMultiplicationLayer(Layer):
    """out = act(x * w + b), learned per-feature scale.

    Reference: org.deeplearning4j.nn.conf.layers.misc.ElementWiseMultiplicationLayer.
    """

    n_out: Optional[int] = None
    activation: str = "identity"

    def init(self, key, itype):
        n = self.n_out or itype.size
        return {"W": jnp.ones((n,)), "b": self._b((n,))}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y = x * params["W"] + params["b"]
        return resolve_activation(self.activation)(y), state
