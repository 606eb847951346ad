"""Grouped matrix product on the TPU — the Pallas kernels jax ships
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for the rows'
gradient, ``tgmm`` for the weights'), at tiles measured on the v5e.

Registered over ``op("grouped_matmul")``'s plain lowering, ``jax.lax.ragged_dot``,
of which the TPU compiler makes kernel calls of its own at tiles of its own
choosing: at the expert layer's shapes (65,536 rows of which 16,384 are held, 16
groups, 2,304 x 896 and 896 x 2,304, bfloat16) 2.99 and 3.55 ms forward and
backward, 48 TFLOP/s forward, a quarter of the chip's peak; these kernels at
``TILES`` 1.20 and 1.58 ms (PERF.md, PR 34). Both work only the row tiles a
group reaches; what either leaves in the rows past the groups is unspecified.
"""

from __future__ import annotations

import jax.numpy as jnp

from deeplearning4j_tpu.ops.pallas.interpret import interpret_mode
from deeplearning4j_tpu.ops.registry import register_impl

#: (m, k, n) tiles; the forward, the rows' gradient (k and n change places) and
#: the weights' gradient take the same. Fastest or within 4 % of it for both of
#: the layer's shapes, even and skewed groups, among the ten tilings that fit
#: the v5e's 16 MB of scoped VMEM; (512, 2304, 896) and (1024, 896, 1152) do not
TILES = (512, 1152, 896)


def grouped_matmul(lhs, rhs, group_sizes):
    # imported where it is used: no cell without an expert layer pays for it
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, TILES,
                        interpret=interpret_mode())


def _applicable(lhs, rhs, group_sizes):
    # whole row tiles, lane-aligned widths
    return (lhs.shape[0] % TILES[0] == 0
            and rhs.shape[1] % 128 == 0 and rhs.shape[2] % 128 == 0
            and lhs.dtype == rhs.dtype and lhs.dtype in (jnp.bfloat16, jnp.float32))


register_impl("grouped_matmul", platform="pallas", predicate=_applicable,
              scope="grouped_matmul")(grouped_matmul)
