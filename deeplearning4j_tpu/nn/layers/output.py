"""Output / loss layers.

Reference analog: org.deeplearning4j.nn.conf.layers.{OutputLayer, RnnOutputLayer,
LossLayer, CenterLossOutputLayer} + org.deeplearning4j.nn.layers.BaseOutputLayer.
An output layer = (optional dense transform) + activation + loss; ``score``
returns per-example loss values so masking/weighting compose upstream, exactly
like ILossFunction.computeScoreArray.

Fused numerics: when activation is softmax and loss is MCXENT (or sigmoid+XENT),
``score_from_preout`` uses the logits path (log_softmax / logaddexp) — the
numerically-stable fusion cuDNN/DL4J special-cased, done here in plain XLA.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, resolve_activation
from deeplearning4j_tpu.nn.layers.core import DenseLayer
from deeplearning4j_tpu.ops.losses import get_loss


def _fused(activation: str, loss: str) -> bool:
    a = activation.lower().replace("_", "")
    l = loss.lower().replace("_", "")
    return (a == "softmax" and l in ("mcxent", "negativeloglikelihood",
                                     "sparsemcxent")) or (
        a == "sigmoid" and l == "xent"
    )


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class OutputLayer(DenseLayer):
    """Dense + activation + loss (org.deeplearning4j.nn.conf.layers.OutputLayer)."""

    loss: str = "mcxent"
    activation: str = "softmax"

    def preout(self, params, x):
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        return resolve_activation(self.activation)(self.preout(params, x)), state

    def score_from_preout(self, labels, preout, mask=None):
        """Per-example loss given pre-activation output (stable fused path)."""
        fn = get_loss(self.loss)
        if _fused(self.activation, self.loss):
            return fn(labels, preout, mask, from_logits=True)
        return fn(labels, resolve_activation(self.activation)(preout), mask)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class RnnOutputLayer(OutputLayer):
    """Per-timestep output layer for sequences.

    Reference: org.deeplearning4j.nn.conf.layers.RnnOutputLayer. Input/output
    [batch, time, features]; loss computed per timestep then masked + summed.
    """

    def output_type(self, itype):
        t = itype.shape[0] if itype.kind == "rnn" else None
        return InputType.recurrent(self.n_out, t)

    def preout(self, params, x):
        y = x @ params["W"]  # [B, T, nout]
        if self.has_bias:
            y = y + params["b"]
        return y

    def score_from_preout(self, labels, preout, mask=None):
        fn = get_loss(self.loss)
        b, t = preout.shape[0], preout.shape[1]
        p2 = preout.reshape(b * t, -1)
        l2 = labels.reshape(b * t, -1)
        m2 = mask.reshape(b * t) if mask is not None else None
        if _fused(self.activation, self.loss):
            per = fn(l2, p2, m2, from_logits=True)
        else:
            per = fn(l2, resolve_activation(self.activation)(p2), m2)
        # sum over time -> per-example score (DL4J averages over *present* steps
        # at the score level; we sum here and normalize in the model by mask sum)
        return per.reshape(b, t).sum(axis=1)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class LoopExitOutputLayer(Layer):
    """The exits of a looped stack — net-new (looped decoders with a learned
    exit; no DL4J analog). Input: every pass's state, stacked ``[times, batch,
    time, features]`` (``LoopedStack``); labels: an int32 class index for
    every position, ``[batch, time]``.

    One head ``W`` and one gate ``Wg, bg`` serve every pass: ``logits_t = z_t
    W``, ``g_t = sigmoid(z_t . Wg + bg)``. A position leaves at pass *t* with
    probability ``p_t = g_t prod_{j<t} (1 - g_j)`` (the last pass takes what
    is left), and its loss is ``sum_t p_t CE(logits_t, y) - beta H(p)``, ``H``
    the entropy of ``p``; a row's score is the sum over its positions, as
    ``RnnOutputLayer``'s. The exit distribution is worked out in float32 from
    log-sigmoids.

    Four passes' logits cannot live at once at a language model's vocabulary,
    so the layer scores its *input* (``score_from_features``): the passes are a
    ``lax.map`` whose body, head and cross-entropy of one pass, is recomputed
    on the backward pass; only ``[times, batch, time]`` cross-entropies and
    gate logits leave it. ``output()`` and ``preout`` give the last pass's
    logits. The step's mean exit probability of each pass is kept as the
    layer's state (``exit_share``), for a monitor to read.
    """

    n_out: int
    n_in: Optional[int] = None
    times: int = 1          # the passes handed on: the length of ``exit_share``
    beta: float = 0.05
    activation: str = "identity"

    def output_type(self, itype):
        return InputType.recurrent(self.n_out, itype.shape[0] if itype.kind == "rnn" else None)

    def init(self, key, itype):
        nin = self.n_in or itype.shape[1]
        k_head, k_gate = jax.random.split(key)
        return ({"W": self._w(k_head, (nin, self.n_out)),
                 "Wg": self._w(k_gate, (nin, 1))[:, 0], "bg": jnp.zeros((1,))},
                {"exit_share": jnp.zeros((self.times,), jnp.float32)})

    def preout(self, params, x):
        return x[-1] @ params["W"]

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return resolve_activation(self.activation)(self.preout(params, x)), state

    @staticmethod
    def exit_log_probs(gate_logits):
        """``log p_t`` ``[times, ...]`` from the gates' logits, in float32."""
        gate_logits = gate_logits.astype(jnp.float32)
        stay = jax.nn.log_sigmoid(-gate_logits)                 # log(1 - g_t)
        stayed = jnp.cumsum(stay, axis=0) - stay                # sum over j < t
        leave = jax.nn.log_sigmoid(gate_logits[:-1]) + stayed[:-1]
        return jnp.concatenate([leave, stayed[-1:]], axis=0)    # the last pass takes the rest

    def score_from_features(self, params, state, labels, states, mask=None):
        """(per-row score ``[batch]``, new state) from the passes' states."""
        if states.shape[0] != self.times:
            raise ValueError(f"LoopExitOutputLayer(times={self.times}) is handed "
                             f"{states.shape[0]} passes: give it the looped stack's ``times``")
        labels = labels.astype(jnp.int32)

        @jax.checkpoint
        def one_exit(z):
            with jax.named_scope("exit"):
                logits = z @ params["W"]
                ce = (jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
                      - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
                      .astype(jnp.float32))
                gate = (z.astype(jnp.float32) @ params["Wg"].astype(jnp.float32)
                        + params["bg"].astype(jnp.float32))
                return ce, gate

        ce, gate = jax.lax.map(one_exit, states)                # [times, batch, time] each
        log_p = self.exit_log_probs(gate)
        p = jnp.exp(log_p)
        per = (p * ce).sum(0) + self.beta * (p * log_p).sum(0)  # - beta H(p)
        if mask is not None:
            per = per * mask.reshape(per.shape).astype(per.dtype)
        share = jax.lax.stop_gradient(p.mean(axis=(1, 2)))
        return per.sum(axis=1), {**state, "exit_share": share}


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class LossLayer(Layer):
    """Loss without parameters (org.deeplearning4j.nn.conf.layers.LossLayer)."""

    loss: str = "mcxent"
    activation: str = "identity"

    def preout(self, params, x):
        return x

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return resolve_activation(self.activation)(x), state

    def score_from_preout(self, labels, preout, mask=None):
        fn = get_loss(self.loss)
        if _fused(self.activation, self.loss):
            return fn(labels, preout, mask, from_logits=True)
        return fn(labels, resolve_activation(self.activation)(preout), mask)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class CenterLossOutputLayer(OutputLayer):
    """Softmax + center loss (org.deeplearning4j.nn.conf.layers.CenterLossOutputLayer).

    Maintains per-class feature centers in ``state``; loss = CE + alpha/2 *
    ||f - c_y||^2, centers updated with rate lambda toward class means.
    """

    alpha: float = 0.05
    lambda_: float = 0.5  # DL4J 'lambda'; trailing underscore for Python keyword-safety
    gradient_check: bool = False

    def init(self, key, itype):
        p, _ = super().init(key, itype)
        nin = self.n_in or itype.size
        return p, {"centers": jnp.zeros((self.n_out, nin))}

    def center_score_and_state(self, params, state, features, labels,
                               mask=None):
        """``mask``: optional per-example [B] weights (r5) — a masked-out
        example contributes neither to the center-distance score nor to
        the persisted center update."""
        centers = state["centers"]
        cls = jnp.argmax(labels, axis=-1)
        diff = features - centers[cls]
        score = 0.5 * self.alpha * (diff * diff).sum(axis=-1)
        lw = labels if mask is None else labels * mask[:, None]
        if mask is not None:
            score = score * mask
        # center update: c_j += lambda * mean_{i: y_i=j}(f_i - c_j)
        counts = lw.sum(axis=0)[:, None] + 1.0
        delta = (lw.T @ features - counts * centers + centers) / counts
        new_centers = centers + self.lambda_ * delta
        return score, {"centers": new_centers}


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class CnnLossLayer(Layer):
    """Per-pixel loss over [B, H, W, C] activations
    (org.deeplearning4j.nn.conf.layers.CnnLossLayer — used by UNet-style
    segmentation heads). Loss computed per pixel, summed per example."""

    loss: str = "xent"
    activation: str = "sigmoid"

    def preout(self, params, x):
        return x

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return resolve_activation(self.activation)(x), state

    def score_from_preout(self, labels, preout, mask=None):
        fn = get_loss(self.loss)
        b = preout.shape[0]
        p2 = preout.reshape(-1, preout.shape[-1])
        l2 = labels.reshape(-1, labels.shape[-1])
        m2 = mask.reshape(-1) if mask is not None else None
        if _fused(self.activation, self.loss):
            per = fn(l2, p2, m2, from_logits=True)
        else:
            per = fn(l2, resolve_activation(self.activation)(p2), m2)
        return per.reshape(b, -1).sum(axis=1)
