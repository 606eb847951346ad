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
and masked out of every sum. Dispatch and combine are permutations and are
differentiated as such: gathers both ways, never a scatter-add.

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

#: the layer's load gauges, in the order ``moe_stats`` holds them
MOE_STATS = ("load_max_over_mean", "pairs_held", "tokens_unserved", "aux")


def route(probs, top_k: int, first: int, held: int):
    """Top-k routing of ``probs`` ``[tokens, experts]`` for a layer that holds
    the experts ``first .. first + held - 1``. Pair ``p = token * top_k +
    slot``. Returns ``(chosen [tokens, top_k] int32, weights [tokens, top_k]``
    renormalised over the chosen and 0 where the expert is not held, ``order
    [pairs]``: the pair each sorted row takes, held experts' pairs first by
    expert, ``place [pairs]``: the sorted row of each pair, ``group_sizes
    [held]``)."""
    top_p, chosen = jax.lax.top_k(probs, top_k)
    weights = top_p / top_p.sum(axis=-1, keepdims=True)
    local = chosen - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32), unique_indices=True)
    group_sizes = (key[:, None] == jnp.arange(held, dtype=key.dtype)).sum(
        axis=0, dtype=jnp.int32)
    return chosen, jnp.where(is_held, weights, 0.0), order, place, group_sizes


def _gather_rows(table, index):
    """``table[index]`` as an operation of its own: fused with the select before
    it and the product after it, XLA's gather of 65,536 rows of 2,304 bfloat16
    took 5.4 ms on the v5e where the bare one takes 2.4-3.5 (PERF.md, PR 34);
    the barriers keep producers and consumers out of it."""
    table, index = jax.lax.optimization_barrier((table, index))
    return jax.lax.optimization_barrier(table[index])


def _pairs_rows(rows, place, n_held, top_k):
    """``[tokens, top_k, features]``: each pair's sorted row, zeros for a pair
    whose expert is not held (a select on the gathered rows, so that whatever
    a product left in the rows past the held pairs, NaN included, goes)."""
    picked = _gather_rows(rows, place).reshape(-1, top_k, rows.shape[-1])
    held = (place < n_held).reshape(-1, top_k, 1)
    return jnp.where(held, picked, jnp.zeros((), rows.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(xt, order, place, n_held, top_k):
    """The tokens' rows in sorted pair order: ``rows[r] = xt[order[r] //
    top_k]``. Backward: each token sums the gradients of its ``top_k`` rows
    (those of held pairs), found by ``place``: a gather."""
    return _dispatch_fwd(xt, order, place, n_held, top_k)[0]


def _dispatch_fwd(xt, order, place, n_held, top_k):
    return _gather_rows(xt, order // top_k), (place, n_held)


def _dispatch_bwd(top_k, res, g):
    place, n_held = res
    picked = _pairs_rows(g, place, n_held, top_k)
    return picked.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, order, place, n_held):
    """``y[token] = sum_slot weights[token, slot] * rows[place[token, slot]]``
    over the held pairs, summed in float32. Backward: a row's gradient is its
    pair's weight times its token's, found by ``order``: a gather."""
    return _combine_fwd(rows, weights, order, place, n_held)[0]


def _combine_fwd(rows, weights, order, place, n_held):
    y = (_pairs_rows(rows, place, n_held, weights.shape[1]).astype(jnp.float32)
         * weights[..., None]).sum(axis=1).astype(rows.dtype)
    return y, (rows, weights, order, place, n_held)


def _combine_bwd(res, g):
    rows, weights, order, place, n_held = res
    top_k = weights.shape[1]
    d_rows = (_gather_rows(g, order // top_k).astype(jnp.float32)
              * weights.reshape(-1)[order][:, None]).astype(rows.dtype)
    d_weights = (_pairs_rows(rows, place, n_held, top_k).astype(jnp.float32)
                 * g[:, None, :].astype(jnp.float32)).sum(axis=-1).astype(weights.dtype)
    return d_rows, d_weights, None, None, None


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
            chosen, weights, order, place, group_sizes = route(probs, self.top_k, first, held)
            n_held = group_sizes.sum()
            share = jnp.zeros((self.n_experts,), jnp.float32).at[chosen.reshape(-1)].add(
                1.0 / tokens)
            aux = self.n_experts * (share * probs.mean(axis=0)).sum()
        with jax.named_scope("dispatch"):
            rows = dispatch(xt, order, place, n_held, self.top_k)
        with jax.named_scope("expert_matmul"):
            grouped = op("grouped_matmul")
            hidden = (jax.nn.silu(grouped(rows, params["Wg"], group_sizes))
                      * grouped(rows, params["Wu"], group_sizes))
            out_rows = grouped(hidden, params["Wd"], group_sizes)
        with jax.named_scope("combine"):
            y = combine(out_rows, weights.astype(jnp.float32), order, place, n_held)
        # a row's score is the sum over its positions: the term counts once a position
        positions = x.shape[1] if x.ndim == 3 else 1
        largest, pairs = group_sizes.max().astype(jnp.float32), n_held.astype(jnp.float32)
        stats = jnp.stack([largest * held / jnp.maximum(pairs, 1.0), pairs,
                           (weights.sum(axis=-1) == 0).sum().astype(jnp.float32), aux])
        return y.reshape(x.shape), {**state, LOSS_TERM: self.aux_coef * positions * aux,
                                    "moe_stats": jax.lax.stop_gradient(stats)}
