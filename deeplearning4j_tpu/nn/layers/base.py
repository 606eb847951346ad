"""Layer base class + registry.

Reference analog: org.deeplearning4j.nn.conf.layers.Layer (config side) and
org.deeplearning4j.nn.api.Layer (impl side). DL4J splits config from impl and
instantiates impls reflectively; TPU-first we unify them — a layer is a frozen
dataclass whose fields are the JSON-serializable hyperparameters and whose
``init``/``apply`` are pure functions, so a stack of layers traces into one
jitted XLA program. (DL4J's workspace memory management has no equivalent
here: XLA's buffer assignment + donation replaces manual arenas.)

Uniform functional contract:
    params, state = layer.init(key, input_type)
    y, new_state  = layer.apply(params, state, x, train=..., rng=..., mask=...)

``params`` are trainable leaves (DL4J param-table keys kept: "W", "b",
"gamma", "beta", "RW", ...); ``state`` holds non-trainable persistent arrays
(batch-norm running stats). Mask propagation mirrors DL4J's
feedForwardMaskArray.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.pallas import delta_rule, sparse_attention
from deeplearning4j_tpu.ops.pallas.flash_attention import SAVED_LSE, SAVED_OUT
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.weights import init_weight

LAYER_REGISTRY: dict[str, type] = {}

#: what a layer's checkpoint keeps besides the layer's input under the
#: configuration's ``remat``: the flash kernel's output and log-sum-exp, T-sized
#: results of T^2 work, and the delta-rule kernels' output and chunk states
#: (a state of K x V a chunk, in k's type), each only where the registry chose
#: the kernel; the sparse-attention kernels' output and log-sum-exp, the
#: indexer loss's gradients (its forward makes them), and the indexer's
#: choice of keys (``SELECTED``, ``nn/layers/mixers.py``'s, whichever
#: lowering made it: the forward, the recomputation and the backward then see
#: one choice); a layer that names nothing keeps its input alone. A wider
#: keep-list (XLA's own products, by name) would go here.
SELECTED = "sparse_attention_selected"
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    SAVED_OUT, SAVED_LSE, delta_rule.SAVED_OUT, delta_rule.SAVED_STATES,
    sparse_attention.SAVED_OUT, sparse_attention.SAVED_LSE, sparse_attention.SAVED_INDEX_GRADS,
    SELECTED)


#: the key of a layer's new state under which its ``apply`` hands back a scalar
#: for the score (a router's load-balancing term): computed from the step's
#: activations, differentiated with the loss, under ``remat`` like the rest. A
#: state that holds two layers' terms (a block's mixer beside its experts)
#: keys the other ``LOSS_TERM + "_<what>"``
LOSS_TERM = "loss_term"


def layer_loss_terms(new_states) -> list:
    """The scalars the layers handed back for the score, from their new states
    (a list by layer or a dict by vertex). None in a network without such a
    layer, whose step is then the program it was."""
    states = new_states.values() if isinstance(new_states, dict) else new_states
    return [term for s in states if isinstance(s, dict) for key, term in s.items()
            if key == LOSS_TERM or key.startswith(LOSS_TERM + "_")]


#: the key of a layer's new state under which its ``apply`` hands the output
#: layer per-position weights for its score (block diffusion's noising layer:
#: 1/t at the positions it masked, 0 elsewhere). They live for one step: the
#: network takes them out of the state it keeps (``take_loss_weights``)
LOSS_WEIGHTS = "loss_weights"


def take_loss_weights(new_states: list):
    """The per-position weights a layer handed on in its new state, taken out
    of it (the list of states is changed in place), or None where no layer
    handed any."""
    found = [i for i, s in enumerate(new_states) if isinstance(s, dict) and LOSS_WEIGHTS in s]
    if not found:
        return None
    if len(found) > 1:
        raise ValueError(f"layers {found} each hand the score weights: one may")
    kept = dict(new_states[found[0]])
    weights = kept.pop(LOSS_WEIGHTS)
    new_states[found[0]] = kept
    return weights


def checkpoint_layer(fn):
    """``jax.checkpoint`` of one layer application, for every network that
    honours ``remat`` (``MultiLayerNetwork``, ``ComputationGraph``,
    ``LoopedStack``)."""
    return jax.checkpoint(fn, policy=REMAT_POLICY)


def scope_name(index, layer) -> str:
    """``<index>.<LayerClass>`` (the layer's own name where it has one): the
    ``jax.named_scope`` a layer runs under, by which a trace's reader tells
    layer kinds apart without a table."""
    return f"{getattr(layer, 'name', None) or index}.{type(layer).__name__}"


def register_layer(cls):
    """Class decorator: make a layer JSON round-trippable by class name."""
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


@dataclasses.dataclass(frozen=True, kw_only=True)
class Layer:
    """Base config+impl for all layers.

    Common hyperparameters mirror org.deeplearning4j.nn.conf.layers.BaseLayer:
    weight init scheme, l1/l2 regularization, per-layer dropout (applied to the
    layer *input*, as in DL4J), and an optional per-layer updater override.
    """

    name: Optional[str] = None
    dropout: float = 0.0  # keep DL4J semantics: dropout applied to layer input
    weight_init: str = "xavier"
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    updater: Optional[Any] = None  # per-layer IUpdater override
    trainable: bool = True  # False => frozen (TransferLearning)

    # ---- to be overridden ----
    def output_type(self, itype: InputType) -> InputType:
        return itype

    def init(self, key, itype: InputType):
        return {}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        raise NotImplementedError

    def feed_forward_mask(self, mask, itype: InputType):
        """How this layer transforms the time/feature mask (DL4J feedForwardMaskArray)."""
        return mask

    # ---- shared helpers ----
    def _maybe_dropout(self, x, train, rng):
        if not train or self.dropout <= 0.0:
            return x
        if rng is None:
            raise ValueError(f"layer {self.name or type(self).__name__}: dropout needs an rng key")
        keep = 1.0 - self.dropout
        m = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(m, x / keep, 0.0).astype(x.dtype)

    def _w(self, key, shape, fan_in=None, fan_out=None):
        return init_weight(key, shape, self.weight_init, fan_in=fan_in, fan_out=fan_out)

    def _b(self, shape):
        return jnp.full(shape, float(self.bias_init), jnp.float32)

    # ---- regularization score (DL4J calcRegularizationScore) ----
    def regularization(self, params) -> jnp.ndarray:
        if (self.l1 == 0.0 and self.l2 == 0.0) or not params:
            return jnp.asarray(0.0)
        s = 0.0
        for k, v in params.items():
            if k in ("b", "beta", "gamma"):  # DL4J: no l1/l2 on bias by default
                continue
            if getattr(v, "is_quantized", False):
                # quantized inference view: frozen weights carry no penalty
                continue
            if isinstance(v, dict):
                s = s + sum(self.l1 * jnp.abs(a).sum() + self.l2 * 0.5 * (a * a).sum()
                            for a in jax.tree_util.tree_leaves(v))
            else:
                s = s + self.l1 * jnp.abs(v).sum() + self.l2 * 0.5 * (v * v).sum()
        return s

    # ---- serde (Jackson-JSON config analog) ----
    def to_dict(self) -> dict:
        d = {"@layer": type(self).__name__}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None or v == f.default:
                continue
            d[f.name] = _ser(v)
        return d

    @staticmethod
    def from_dict(d: dict) -> "Layer":
        d = dict(d)
        cls = LAYER_REGISTRY[d.pop("@layer")]
        kwargs = {}
        for f in dataclasses.fields(cls):
            if f.name in d:
                kwargs[f.name] = _deser(d[f.name], f)
        return cls(**kwargs)


def _ser(v):
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        if isinstance(v, Layer):
            return v.to_dict()
        d = dataclasses.asdict(v)
        d["@type"] = type(v).__name__
        return d
    if hasattr(v, "to_dict"):
        return v.to_dict()
    if isinstance(v, tuple):
        return [_ser(item) for item in v]
    return v


def _deser(v, field):
    if isinstance(v, dict) and "@layer" in v:
        return Layer.from_dict(v)
    if isinstance(v, list):
        return tuple(_deser(item, field) for item in v)
    if isinstance(v, dict) and "@type" in v:
        from deeplearning4j_tpu.optimize.updaters import updater_from_dict

        try:
            return updater_from_dict(v)
        except Exception:
            pass
    return v


def resolve_activation(act) -> Callable:
    return get_activation(act)
