"""Jaxpr witness: prove no full-size dequantized weight is materialized.

Weight-only quantization is a bandwidth optimization only if the int8
payload is the sole full-size weight buffer. The failure mode is writing
``q.astype(f32) * scale`` per weight shape — a scaled f32 copy the memory
system must stream — instead of applying the scale to the accumulator
output. The two are distinguishable in the jaxpr: a bare ``convert`` at the
weight's shape is fine (XLA fuses it into the consuming dot's operand
read), but a ``mul`` producing a float array of exactly a quantized
weight's shape is the smoking gun.

Tier-1 tests trace the quantized predict/decode functions and assert this
over the whole jaxpr, mirroring the zero-overhead monitoring guard pattern.
"""

from __future__ import annotations

import jax
from jax.extend import core as jax_core


def _walk(jaxpr):
    """Yield every equation in ``jaxpr`` and all nested sub-jaxprs
    (closed-call, scan, cond branches, pjit, remat, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk(sub)


def _subjaxprs(v):
    if isinstance(v, jax_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jax_core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _subjaxprs(item)


def find_dequantized_weights(fn, *args, weight_shapes=None, **kwargs):
    """Trace ``fn(*args, **kwargs)`` and return the offending equations: any
    ``mul`` whose float output has exactly the shape of a quantized weight.

    weight_shapes: iterable of weight shapes to screen for. Defaults to the
    shapes of every int8 array (ndim >= 2) in ``args`` — i.e. the payloads
    of all QuantizedTensors in the traced params.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    if weight_shapes is None:
        weight_shapes = {
            tuple(leaf.shape)
            for leaf in jax.tree_util.tree_leaves((args, kwargs))
            if getattr(leaf, "dtype", None) == jax.numpy.int8
            and getattr(leaf, "ndim", 0) >= 2
        }
    shapes = {tuple(s) for s in weight_shapes}
    bad = []
    for eqn in _walk(closed.jaxpr):
        if eqn.primitive.name != "mul":
            continue
        for out in eqn.outvars:
            aval = out.aval
            if (tuple(getattr(aval, "shape", ())) in shapes
                    and jax.numpy.issubdtype(aval.dtype, jax.numpy.floating)):
                bad.append(eqn)
                break
    return bad


def assert_no_dequantized_weights(fn, *args, weight_shapes=None, **kwargs):
    bad = find_dequantized_weights(fn, *args, weight_shapes=weight_shapes,
                                   **kwargs)
    if bad:
        lines = "\n  ".join(str(e)[:200] for e in bad[:5])
        raise AssertionError(
            f"quantized path materializes {len(bad)} full-size dequantized "
            f"weight buffer(s):\n  {lines}")
