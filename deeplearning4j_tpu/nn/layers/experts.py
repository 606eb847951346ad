"""Sparse experts as a layer — net-new (mixture-of-experts decoders; no DL4J
analog).

``SparseExpertsLayer``: a router over all ``n_experts`` (its product and
softmax in float32), the ``top_k`` largest probabilities a token renormalised
to weights, and for every chosen expert *that this layer holds* the gated MLP
``(silu(u Wg_e) * (u Wu_e)) Wd_e``, weighted and summed. ``experts_held``
(a first index and a count; default all) is the layer's share of an
expert-parallel group: the router stays whole, the layer computes its own
experts' part of the result, and a token none of whose experts is held gets 0.
What the absent experts would add is left out; nothing stands in for them.

No capacity: every (token, expert) pair whose expert is held is served,
whatever the imbalance. The pairs are sorted by expert (those of absent experts
last), the tokens' rows gathered in that order, and each of the three products
is one grouped matrix product over the sorted rows (``op("grouped_matmul")``),
whose groups are the experts' pair counts of this very step. The buffer of
sorted rows holds all ``tokens x top_k`` pairs, the one static bound there is
when no pair may be dropped; rows past the held pairs are worked by no product
and reach no sum. Dispatch and combine are permutations and are differentiated
as such: gathers both ways, never a scatter-add, each a row-gather op of the
registry (``ops/rows.py``: ``gather_rows``, ``gather_sum_rows``,
``gather_rows_dot``) that takes the count of held pairs, a value of the step,
beside its indices. Where the shapes tile, the Pallas kernels of
``ops/pallas/row_gather.py`` move the held pairs' rows alone, with the select,
the weight and the sum over a token's slots inside the move; elsewhere (a CPU,
a ragged shape) XLA gathers all ``tokens x top_k`` rows and runs those as
passes of their own.

The layer hands back the load-balancing term of its router for the score
(``base.LOSS_TERM`` in its new state): ``aux_coef * n_experts * sum_e f_e P_e``,
``f_e`` the share of tokens that chose ``e`` (summing to ``top_k``), ``P_e``
the mean router probability, over all experts; counted once a position of a
row, as the score sums a row's positions. ``moe_stats`` beside it is the step's
load for a monitor: the largest held expert's pairs over the mean, the pairs
held, the tokens with no held expert, the raw term.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import LOSS_TERM, Layer, register_layer
from deeplearning4j_tpu.ops.registry import op
import deeplearning4j_tpu.ops.grouped  # noqa: F401
import deeplearning4j_tpu.ops.rows  # noqa: F401

#: the layer's load gauges, in the order ``moe_stats`` holds them
MOE_STATS = ("load_max_over_mean", "pairs_held", "tokens_unserved", "aux")


def route(probs, top_k: int, first: int, held: int):
    """Top-k routing of ``probs`` ``[tokens, experts]`` for a layer that holds
    the experts ``first .. first + held - 1``. Pair ``p = token * top_k +
    slot``. Returns ``(chosen [tokens, top_k] int32, weights [tokens, top_k]``
    renormalised over the chosen and 0 where the expert is not held, ``order
    [pairs]``: the pair each sorted row takes, held experts' pairs first by
    expert, ``place [pairs]``: the sorted row of each pair, ``group_sizes
    [held]``, ``sorted_weights [pairs]``: each sorted row's pair's weight, a
    constant: the sort carries the weights along, where a gather of 65,536
    scalars by ``order`` afterwards took XLA 0.6 ms on the v5e)."""
    top_p, chosen = jax.lax.top_k(probs, top_k)
    weights = top_p / top_p.sum(axis=-1, keepdims=True)
    local = chosen - first
    is_held = (local >= 0) & (local < held)
    weights = jnp.where(is_held, weights, 0.0)
    key = jnp.where(is_held, local, held).reshape(-1)
    pairs = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, order, sorted_weights = jax.lax.sort(
        (key, pairs, jax.lax.stop_gradient(weights).reshape(-1)), num_keys=1, is_stable=True)
    place = jnp.zeros_like(order).at[order].set(pairs, unique_indices=True)
    group_sizes = (key[:, None] == jnp.arange(held, dtype=key.dtype)).sum(
        axis=0, dtype=jnp.int32)
    return chosen, weights, order, place, group_sizes, sorted_weights


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(xt, order, place, n_held, top_k):
    """The tokens' rows in sorted pair order: ``rows[r] = xt[order[r] //
    top_k]`` for the ``n_held`` rows that are a held pair's (the others:
    unspecified), handed out twice, once to each of the two products that read
    them: their two gradients then come back apart and are added where they are
    gathered, over the held rows, and not by a pass over all ``tokens x
    top_k``. Backward: each token sums the gradients of its ``top_k`` rows
    (those of held pairs), found by ``place``: a gather."""
    return _dispatch_fwd(xt, order, place, n_held, top_k)[0]


def _dispatch_fwd(xt, order, place, n_held, top_k):
    rows = op("gather_rows")(xt, order // top_k, n_held)
    return (rows, rows), (place, n_held)


def _dispatch_bwd(top_k, res, gs):
    place, n_held = res
    d_xt = op("gather_sum_rows")(gs[0], place.reshape(-1, top_k), n_held, None, gs[1])
    return d_xt, None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, sorted_weights, order, place, n_held):
    """``y[token] = sum_slot weights[token, slot] * rows[place[token, slot]]``
    over the held pairs, summed in float32. Backward, in sorted order: a
    row's gradient is its pair's weight (``sorted_weights``, ``route``'s) times
    its token's, found by ``order``, and from the same gathered rows a pair's
    weight gets ``rows[r] . g[token]``: one gather for both, and none of
    ``rows``."""
    return _combine_fwd(rows, weights, sorted_weights, order, place, n_held)[0]


def _combine_fwd(rows, weights, sorted_weights, order, place, n_held):
    y = op("gather_sum_rows")(rows, place.reshape(weights.shape), n_held, weights)
    return y, (rows, weights, sorted_weights, order, place, n_held)


def _combine_bwd(res, g):
    rows, weights, sorted_weights, order, place, n_held = res
    d_rows, d_sorted = op("gather_rows_dot")(g, order // weights.shape[1], n_held,
                                             sorted_weights, rows)
    d_weights = jnp.where(place < n_held, d_sorted[place], 0.0)
    return (d_rows, d_weights.reshape(weights.shape).astype(weights.dtype),
            None, None, None, None)


combine.defvjp(_combine_fwd, _combine_bwd)


@register_layer
@dataclasses.dataclass(frozen=True, kw_only=True)
class SparseExpertsLayer(Layer):
    n_experts: int = 64
    top_k: int = 8
    d_expert: int = 896
    experts_held: Optional[tuple] = None        # (first, count); None: all
    aux_coef: float = 0.001
    n_in: Optional[int] = None

    @property
    def held(self) -> tuple:
        first, count = self.experts_held or (0, self.n_experts)
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} is no run of the "
                             f"{self.n_experts} experts")
        return int(first), int(count)

    def init(self, key, itype):
        d, f, (_, held) = self.n_in or itype.shape[-1], self.d_expert, self.held
        kr, kg, ku, kd = jax.random.split(key, 4)
        params = {"Wr": self._w(kr, (d, self.n_experts)),
                  "Wg": self._w(kg, (held, d, f), fan_in=d, fan_out=f),
                  "Wu": self._w(ku, (held, d, f), fan_in=d, fan_out=f),
                  "Wd": self._w(kd, (held, f, d), fan_in=f, fan_out=d)}
        return params, {LOSS_TERM: jnp.zeros((), jnp.float32),
                        "moe_stats": jnp.zeros((len(MOE_STATS),), jnp.float32)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        first, held = self.held
        xt = x.reshape(-1, x.shape[-1])
        tokens = xt.shape[0]
        with jax.named_scope("router"):
            logits = jnp.dot(xt.astype(jnp.float32), params["Wr"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            probs = jax.nn.softmax(logits, axis=-1)
        with jax.named_scope("route"):
            chosen, weights, order, place, group_sizes, sorted_weights = route(
                probs, self.top_k, first, held)
            n_held = group_sizes.sum()
            # a count by comparison: as a scatter-add of 65,536 scalars it took 0.57 ms
            share = (chosen.reshape(-1, 1) == jnp.arange(self.n_experts)).sum(
                axis=0, dtype=jnp.float32) / tokens
            aux = self.n_experts * (share * probs.mean(axis=0)).sum()
        with jax.named_scope("dispatch"):
            gate_rows, up_rows = dispatch(xt, order, place, n_held, self.top_k)
        with jax.named_scope("expert_matmul"):
            grouped = op("grouped_matmul")
            hidden = (jax.nn.silu(grouped(gate_rows, params["Wg"], group_sizes))
                      * grouped(up_rows, params["Wu"], group_sizes))
            out_rows = grouped(hidden, params["Wd"], group_sizes)
        with jax.named_scope("combine"):
            y = combine(out_rows, weights.astype(jnp.float32), sorted_weights, order, place,
                        n_held)
        # a row's score is the sum over its positions: the term counts once a position
        positions = x.shape[1] if x.ndim == 3 else 1
        largest, pairs = group_sizes.max().astype(jnp.float32), n_held.astype(jnp.float32)
        stats = jnp.stack([largest * held / jnp.maximum(pairs, 1.0), pairs,
                           (weights.sum(axis=-1) == 0).sum().astype(jnp.float32), aux])
        return y.reshape(x.shape), {**state, LOSS_TERM: self.aux_coef * positions * aux,
                                    "moe_stats": jax.lax.stop_gradient(stats)}
