"""A run of layers applied several times to its own output with one set of
weights — net-new (looped / weight-shared decoders; no DL4J analog).

``LoopedStack(layers, times, norm)``: for each of ``times`` passes the held
layers in order, then ``norm`` (one normalisation layer, after every pass);
the normed state feeds the next pass, and every pass's normed state is handed
on, stacked ``[times, batch, time, features]``, for an output layer that scores
every pass's exit (``LoopExitOutputLayer``). The parameter tree holds each
layer once (``{"0": ..., "1": ..., "norm": ...}``), so a leaf's gradient is
the sum over the passes and ``num_params()`` counts it once.

The passes are a ``lax.scan``: one body serves every pass, so the step's
program holds the held layers once however many times they run. Under the
configuration's ``remat`` each *layer application* is its own checkpoint
(``checkpoint_layer``): it keeps the application's input and, where the
block's attention ran in the flash kernel, the kernel's output and log-sum-exp
(``times x len(layers)`` of each), so the backward pass recomputes the block's
products and element-wise passes but not the kernel's forward. The network does
not wrap the container whole, which would save one input and recompute the
whole loop in one piece (``remats_itself``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer, checkpoint_layer, register_layer, scope_name


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class LoopedStack(Layer):
    layers: tuple = ()
    times: int = 1
    norm: Optional[Layer] = None

    #: ``MultiLayerNetwork._forward`` hands ``remat`` to ``apply`` instead of
    #: wrapping the layer in one ``jax.checkpoint``
    remats_itself = True

    @property
    def layer_applications(self) -> int:
        return self.times * len(self.layers)

    def output_type(self, itype):
        for layer in self.layers:
            itype = layer.output_type(itype)
        return itype

    def init(self, key, itype):
        params = {}
        held = list(enumerate(self.layers))
        if self.norm is not None:
            held.append(("norm", self.norm))
        for i, (name, layer) in enumerate(held):
            params[str(name)], state = layer.init(jax.random.fold_in(key, i), itype)
            if state:
                raise ValueError(
                    f"LoopedStack holds stateless layers only: {type(layer).__name__} keeps "
                    f"{sorted(state)}, and one state under {self.times} applications has no meaning")
            itype = layer.output_type(itype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None, remat=False):
        # rotary tables: once a step, shared by every application of every block
        ropes, extras = {}, []
        for layer in self.layers:
            if hasattr(layer, "rope_tables"):
                key = (layer.head_size, layer.rope_theta, layer.rope_yarn)
                if key not in ropes:
                    ropes[key] = layer.rope_tables(x.shape[1])
                extras.append({"rope": ropes[key]})
            else:
                extras.append({})

        def one_pass(z, t):
            for i, layer in enumerate(self.layers):
                k = None if rng is None else jax.random.fold_in(jax.random.fold_in(rng, t), i)

                def run(p, zz, kk, mm, extra, _layer=layer):
                    return _layer.apply(p, {}, zz, train=train, rng=kk, mask=mm, **extra)[0]

                with jax.named_scope(scope_name(i, layer)):
                    z = (checkpoint_layer(run) if remat else run)(
                        params[str(i)], z, k, mask, extras[i])
            if self.norm is not None:
                with jax.named_scope(scope_name("norm", self.norm)):
                    z = self.norm.apply(params["norm"], {}, z)[0]
            return z, z

        _, states = jax.lax.scan(one_pass, x, jnp.arange(self.times))
        return states, state
