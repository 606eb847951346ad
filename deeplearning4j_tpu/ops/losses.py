"""Loss-function catalog, name-addressable.

Reference analog: nd4j-api :: org.nd4j.linalg.lossfunctions.LossFunctions
(LossFunction enum: MCXENT, XENT, MSE, L1, L2, NEGATIVELOGLIKELIHOOD, HINGE,
SQUARED_HINGE, KL_DIVERGENCE, POISSON, COSINE_PROXIMITY, MEAN_ABSOLUTE_
PERCENTAGE_ERROR, MEAN_SQUARED_LOGARITHMIC_ERROR) and the ILossFunction
impls. Each takes (labels, preactivations-after-activation, mask) and returns
per-example scores; reduction to scalar happens in the training loop so
masking and per-output weighting compose.

All losses operate on the *activated* output (DL4J computes activation inside
the output layer); numerically-fused paths (softmax+CE, sigmoid+BCE) are used
when the caller passes logits with ``from_logits=True``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

_EPS = 1e-7


def _reduce(per_elem, mask):
    """Sum over output dims -> per-example score; apply mask if given."""
    score = per_elem.reshape(per_elem.shape[0], -1).sum(axis=-1)
    if mask is not None:
        score = score * mask.reshape(mask.shape[0], -1).squeeze()
    return score


def _logp(output, from_logits):
    """Stable log-probability path of mcxent."""
    if from_logits:
        return jax.nn.log_softmax(output, axis=-1)
    return jnp.log(jnp.clip(output, _EPS, 1.0))


def _fold_mask(per, mask):
    """Fold a same-rank mask into the per-element scores; return the
    (possibly consumed) mask for _reduce."""
    if mask is not None and mask.ndim == per.ndim:
        return per * mask, None
    return per, mask


def mcxent(labels, output, mask=None, from_logits=False):
    """Multi-class cross entropy (DL4J MCXENT / NEGATIVELOGLIKELIHOOD)."""
    per, mask = _fold_mask(-(labels * _logp(output, from_logits)), mask)
    return _reduce(per, mask)


def sparse_mcxent(labels, output, mask=None, from_logits=False):
    """Integer-label cross entropy (DL4J LossSparseMCXENT): ``labels`` are
    class INDICES (shape = output.shape minus the class axis), never
    one-hot — a [B, T] int array against a [B, T, V] output, so a 30k-word
    masked-LM head pays O(B*T) label memory instead of O(B*T*V). Same
    masking/reduction semantics as mcxent (r4).

    Out-of-range indices follow take_along_axis's jit semantics (clamped
    to the last class) — size the output layer to the FULL vocabulary."""
    labels = jnp.asarray(labels).astype(jnp.int32)
    if labels.ndim == output.ndim:
        # trailing singleton index dim (the RNN score path reshapes labels
        # to [B*T, 1]); a real one-hot here means the caller wanted mcxent
        if labels.shape[-1] != 1:
            raise ValueError(
                f"sparse_mcxent takes class INDICES (trailing dim 1 or "
                f"absent); got labels {labels.shape} against output "
                f"{output.shape} — one-hot labels belong to loss='mcxent'")
        labels = labels[..., 0]
    picked = jnp.take_along_axis(output, labels[..., None], axis=-1)[..., 0]
    if from_logits:
        # the log-sum-exp over the classes in float32 whatever type the logits
        # were written in (a language model's head in bfloat16), and no
        # [.., classes] array of log-probabilities beside them
        per = (jax.nn.logsumexp(output.astype(jnp.float32), axis=-1)
               - picked.astype(jnp.float32))
    else:
        per = -jnp.log(jnp.clip(picked, _EPS, 1.0))
    per, mask = _fold_mask(per, mask)
    return _reduce(per, mask)


def xent(labels, output, mask=None, from_logits=False):
    """Binary cross entropy (DL4J XENT)."""
    if from_logits:
        per = jnp.maximum(output, 0) - output * labels + jnp.log1p(jnp.exp(-jnp.abs(output)))
    else:
        p = jnp.clip(output, _EPS, 1.0 - _EPS)
        per = -(labels * jnp.log(p) + (1.0 - labels) * jnp.log1p(-p))
    return _reduce(per, mask)


def mse(labels, output, mask=None, **_):
    d = output - labels
    per = d * d
    # DL4J MSE averages over the output dimension (LossMSE = LossL2 / nOut)
    return _reduce(per, mask) / output.shape[-1]


def l2(labels, output, mask=None, **_):
    d = output - labels
    return _reduce(d * d, mask)


def mae(labels, output, mask=None, **_):
    return _reduce(jnp.abs(output - labels), mask) / output.shape[-1]


def l1(labels, output, mask=None, **_):
    return _reduce(jnp.abs(output - labels), mask)


def hinge(labels, output, mask=None, **_):
    # labels in {-1, +1} (DL4J LossHinge)
    return _reduce(jnp.maximum(0.0, 1.0 - labels * output), mask)


def squared_hinge(labels, output, mask=None, **_):
    h = jnp.maximum(0.0, 1.0 - labels * output)
    return _reduce(h * h, mask)


def kld(labels, output, mask=None, **_):
    y = jnp.clip(labels, _EPS, 1.0)
    p = jnp.clip(output, _EPS, 1.0)
    return _reduce(y * (jnp.log(y) - jnp.log(p)), mask)


def poisson(labels, output, mask=None, **_):
    return _reduce(output - labels * jnp.log(jnp.clip(output, _EPS, None)), mask)


def cosine_proximity(labels, output, mask=None, **_):
    yn = labels / (jnp.linalg.norm(labels, axis=-1, keepdims=True) + _EPS)
    pn = output / (jnp.linalg.norm(output, axis=-1, keepdims=True) + _EPS)
    per = -(yn * pn)
    return _reduce(per, mask)


def mape(labels, output, mask=None, **_):
    per = jnp.abs((labels - output) / jnp.clip(jnp.abs(labels), _EPS, None)) * 100.0
    return _reduce(per, mask) / output.shape[-1]


def msle(labels, output, mask=None, **_):
    d = jnp.log1p(jnp.clip(output, _EPS - 1, None)) - jnp.log1p(jnp.clip(labels, _EPS - 1, None))
    return _reduce(d * d, mask) / output.shape[-1]


LOSSES: dict[str, Callable] = {
    "mcxent": mcxent,
    "negativeloglikelihood": mcxent,
    "sparsemcxent": sparse_mcxent,
    "xent": xent,
    "mse": mse,
    "l2": l2,
    "l1": l1,
    "mae": mae,
    "hinge": hinge,
    "squaredhinge": squared_hinge,
    "kldivergence": kld,
    "kld": kld,
    "poisson": poisson,
    "cosineproximity": cosine_proximity,
    "meanabsolutepercentageerror": mape,
    "mape": mape,
    "meansquaredlogarithmicerror": msle,
    "msle": msle,
}


def get_loss(name_or_fn) -> Callable:
    if callable(name_or_fn):
        return name_or_fn
    key = str(name_or_fn).lower().replace("_", "")
    if key not in LOSSES:
        raise ValueError(f"unknown loss '{name_or_fn}'; known: {sorted(LOSSES)}")
    return LOSSES[key]
