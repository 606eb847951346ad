"""Row gathers of which only the leading rows count: the moves of an expert
layer's dispatch and combine.

No reference analog. Each op takes the number of rows that are any pair's
(``n_rows``, ``n_held``: a value of the step, like ``grouped_matmul``'s
``group_sizes``) and states the same contract for the rest that
``grouped_matmul`` states for its rows past the groups: what the result holds
there is unspecified, and what an operand holds there (NaN, an index out of
range) reaches no specified result. The plain lowerings below are XLA's gather
over every row with the select, the weight and the sum as passes of their own;
``ops/pallas/row_gather.py`` registers kernels over them that move the counted
rows alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.registry import register_op


def _gather(table, index):
    """``table[index]`` as an operation of its own: fused with the select before
    it and the product after it, XLA's gather of 65,536 rows of 2,304 bfloat16
    took 5.4 ms on the v5e where the bare one takes 2.4-3.5 in the step and
    0.5 alone (PERF.md, PR 34 and 35); the barriers keep producers and
    consumers out of it. An index past the table is clamped, as jax's gather
    does."""
    table, index = jax.lax.optimization_barrier((table, index))
    return jax.lax.optimization_barrier(table[index])


@register_op("gather_rows")
def gather_rows(table, index, n_rows, scale=None):
    """``out[r] = scale[r] * table[index[r]]`` for ``r < n_rows`` (the product
    in float32): ``table`` ``[N, d]``, ``index`` ``[R]`` int32, ``scale``
    ``[R]`` or None. Rows from ``n_rows`` on: unspecified."""
    out = _gather(table, index)
    if scale is None:
        return out
    return (out.astype(jnp.float32) * scale[:, None]).astype(table.dtype)


@register_op("gather_rows_dot")
def gather_rows_dot(table, index, n_rows, scale, other):
    """``gather_rows(table, index, n_rows, scale)`` and, from the same gathered
    rows, ``dots[r] = sum_d other[r, d] * table[index[r], d]`` in float32
    (``other`` ``[R, d]``). Both from ``n_rows`` on: unspecified."""
    picked = _gather(table, index).astype(jnp.float32)
    return ((picked * scale[:, None]).astype(table.dtype),
            (other.astype(jnp.float32) * picked).sum(axis=-1))


@register_op("gather_sum_rows")
def gather_sum_rows(rows, place, n_held, weights=None, more=None):
    """``y[t] = sum over the slots s with place[t, s] < n_held of weights[t, s]
    * rows[place[t, s]]``, summed in float32, in ``rows``' type: ``rows``
    ``[P, d]``, ``place`` ``[T, k]`` int32, ``weights`` ``[T, k]`` or None
    (ones). A slot that is not held adds an exact zero, by a select: what
    ``rows`` holds from ``n_held`` on, NaN included, reaches no sum. ``more``
    ``[P, d]``: the rows summed are ``rows + more`` (two gradients of one
    array, which would else be added over every row first)."""
    if more is not None:
        rows = rows + more
    picked = _gather(rows, place.reshape(-1)).reshape(*place.shape, rows.shape[-1])
    picked = jnp.where((place < n_held)[..., None], picked, jnp.zeros((), rows.dtype))
    picked = picked.astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[..., None]
    return picked.sum(axis=1).astype(rows.dtype)


import deeplearning4j_tpu.ops.pallas.row_gather  # noqa: E402,F401  (registers over the above)
