"""Grouped matrix products: rows sorted by group, one weight matrix a group.

No reference analog (libnd4j has no ragged product). The plain lowering is
``jax.lax.ragged_dot``, which the TPU compiler turns into its own tiled kernel
(tiles no group reaches are not worked) and differentiates into two more
ragged products; ``ops/pallas/grouped_matmul.py`` registers jax's Pallas
kernels over it, chosen by predicate like any other op's.
"""

from __future__ import annotations

import jax

from deeplearning4j_tpu.ops.registry import register_op


@register_op("grouped_matmul")
def grouped_matmul(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g``: ``lhs``
    ``[M, K]`` with its rows sorted by group, ``rhs`` ``[G, K, N]``,
    ``group_sizes`` ``[G]`` int32. Rows past ``group_sizes.sum()`` belong to no
    group: what ``out`` holds there is unspecified."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


import deeplearning4j_tpu.ops.pallas.grouped_matmul  # noqa: E402,F401  (registers over the above)
