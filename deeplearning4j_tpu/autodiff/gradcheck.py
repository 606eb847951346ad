"""Numeric gradient checking.

Reference analog: org.deeplearning4j.gradientcheck.GradientCheckUtil and
org.nd4j.autodiff.validation.OpValidation — central-difference numeric
gradients vs analytic autodiff gradients, the verification backbone of the
reference's whole test suite (SURVEY.md §4).

The reference runs these in fp64 on CPU; JAX on CPU gives fp64 via
jax.enable_x64 context (tests use float64 inputs directly), and on TPU we
fall back to f32 + loose tolerances.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def grad_check(
    fn: Callable,
    *args,
    eps: float = 1e-4,
    rtol: float = 1e-3,
    atol: float = 1e-5,
    max_checks_per_arg: int = 64,
    argnums=None,
    seed: int = 0,
) -> dict:
    """Compare autodiff grads of scalar-valued ``fn(*args)`` to central differences.

    Runs the whole check in float64 (``jax.enable_x64`` + f64-cast args) —
    the reference runs its gradient checks in fp64 on CPU for the same
    reason: central differences at eps=1e-4 are meaningless at f32/bf16
    resolution. Checks up to ``max_checks_per_arg`` randomly-chosen
    coordinates per argument (GradientCheckUtil samples similarly for big
    params). Returns {"ok": bool, "max_rel_error": float, "failures": [...]}.
    """
    argnums = tuple(range(len(args))) if argnums is None else argnums
    with jax.enable_x64():
        args = tuple(
            jnp.asarray(np.asarray(a, dtype=np.float64))
            if np.issubdtype(np.asarray(a).dtype, np.floating) else jnp.asarray(a)
            for a in args
        )
        fn = jax.jit(fn)  # compile once; every finite-difference eval reuses it
        grads = jax.jit(jax.grad(fn, argnums=argnums))(*args)
        if not isinstance(grads, tuple):
            grads = (grads,)
        rng = np.random.default_rng(seed)
        failures = []
        max_rel = 0.0

        for gi, ai in enumerate(argnums):
            a = np.asarray(args[ai], dtype=np.float64)
            flat_grad = np.asarray(grads[gi]).reshape(-1)
            n = a.size
            idxs = rng.choice(n, size=min(n, max_checks_per_arg), replace=False)
            for idx in idxs:
                pert = a.reshape(-1).copy()
                pert[idx] += eps
                args_p = list(args)
                args_p[ai] = jnp.asarray(pert.reshape(a.shape))
                f_p = float(fn(*args_p))
                pert[idx] -= 2 * eps
                args_p[ai] = jnp.asarray(pert.reshape(a.shape))
                f_m = float(fn(*args_p))
                numeric = (f_p - f_m) / (2 * eps)
                analytic = float(flat_grad[idx])
                denom = max(abs(numeric), abs(analytic))
                rel = abs(numeric - analytic) / denom if denom > atol else 0.0
                max_rel = max(max_rel, rel)
                if rel > rtol and abs(numeric - analytic) > atol:
                    failures.append(
                        {"arg": ai, "index": int(idx), "numeric": numeric,
                         "analytic": analytic, "rel_error": rel}
                    )
    return {"ok": not failures, "max_rel_error": max_rel, "failures": failures}


def grad_check_model(model, x, y, mask=None, **kw) -> dict:
    """Gradient-check a model's full loss wrt every parameter leaf.

    The GradientCheckUtil.checkGradients analog: wraps the model's loss as a
    function of its (flattened) params and runs grad_check per leaf tensor.
    """
    params = model.params
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def loss_of(*args):
        leaf_args, xa, ya = args[:-2], args[-2], args[-1]
        p = jax.tree_util.tree_unflatten(treedef, list(leaf_args))
        loss, _, _ = model._loss_terms(p, model.state, xa, ya, None, mask)
        return loss

    # x/y passed as trailing args so grad_check casts them to f64 too;
    # argnums restricts the checked gradients to the parameter leaves.
    return grad_check(loss_of, *leaves, np.asarray(x), np.asarray(y),
                      argnums=tuple(range(len(leaves))), **kw)


def grad_check_graph(graph, inputs: dict, labels: dict, masks=None, **kw) -> dict:
    """Gradient-check a ComputationGraph's loss wrt every parameter leaf.

    Reference analog: GradientCheckTestsComputationGraph — same central
    checker run over DAG topologies (merge/elementwise vertices, multi-input,
    multi-output)."""
    params = graph.params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    n_in = len(inputs)
    in_names = list(inputs)
    lab_names = list(labels)

    def loss_of(*args):
        leaf_args = args[: len(leaves)]
        xs = args[len(leaves) : len(leaves) + n_in]
        ys = args[len(leaves) + n_in :]
        p = jax.tree_util.tree_unflatten(treedef, list(leaf_args))
        loss, _ = graph._loss(p, graph.state, dict(zip(in_names, xs)),
                              dict(zip(lab_names, ys)), None, masks)
        return loss

    trailing = [np.asarray(inputs[k]) for k in in_names] + \
               [np.asarray(labels[k]) for k in lab_names]
    return grad_check(loss_of, *leaves, *trailing,
                      argnums=tuple(range(len(leaves))), **kw)
