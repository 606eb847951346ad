"""What every training cell's plain reference shares: the optimizers written
out, the three-step follower, the per-leaf norms and the comparison that
decides ``correct``. Imports nothing of the program.

A configuration's module (``configs/<name>.py``) gives ``make_params`` and
``loss_fn``; its JSON gives the ``updater``. The follower drives them through
the cell's first three batches in float32 with the matrix units at
``highest``; the control drives the same code in the nearest precision below
the one the configuration states (``CONTROL_PRECISION``).
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp

CHECK_STEPS = 3
#: the numbers of the first gradient's direction; the gradient itself is kept,
#: on both sides, only in a cell whose ``cells/<cell>.json`` gives one of them
#: a limit
DIRECTION_NUMBERS = ("grad_largest_turn", "grad_median_turn", "grad_whole_turn")
#: leaves whose first reference gradient is under this share of the median
#: leaf's are left out of the parameters' change (a key's bias under softmax:
#: Adam moves it by round-off alone)
DEAD_GRADIENT_SHARE = 1e-3


# ------------------------------------------------------------------ precision
#: the nearest precision below the one a configuration states: what its
#: control is computed in
CONTROL_PRECISION = {"float32": "bfloat16", "bfloat16": "fp8"}


def precision_policy(precision: str) -> tuple:
    """(cast, product, qa) for one of the precisions a reference runs in.

    ``cast`` is applied to the parameters and the floating inputs.
    ``product(f)`` wraps a matrix product or convolution ``f(x, w)``.
    ``qa`` is applied to every activation a layer hands on (what is stored).

    ``float32``: none does anything (the reference proper; its products run
    at ``highest``). ``bfloat16``: everything is cast to bfloat16, as a
    mixed-precision policy with float32 master weights does. ``fp8``: that,
    and every product takes its operands rounded to e4m3 and, in the backward
    pass, its cotangent rounded to e5m2, each with a per-tensor scale (the
    recipe of fp8 matrix units); stored activations are rounded to e4m3 too,
    straight-through. These are the steps below bfloat16 that would tempt a
    later PR: fp8 products, and activations kept in one byte where memory
    bounds the step."""
    same = lambda x: x  # noqa: E731
    if precision == "float32":
        return same, same, same
    if precision not in ("bfloat16", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")

    def cast(tree):
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    if precision == "bfloat16":
        return cast, same, same

    def rounded(x, dtype, top):
        scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / top + 1e-30
        return ((x / scale).astype(dtype).astype(jnp.float32) * scale).astype(x.dtype)

    def e4m3(x):
        return rounded(x, jnp.float8_e4m3fn, 448.0)

    def product(f):
        @jax.custom_vjp
        def g(x, w):
            return f(e4m3(x), e4m3(w))

        def forward(x, w):
            return jax.vjp(f, e4m3(x), e4m3(w))

        def backward(pull, cotangent):
            return pull(rounded(cotangent, jnp.float8_e5m2, 57344.0))

        g.defvjp(forward, backward)
        return g

    def qa(x):
        return x + jax.lax.stop_gradient(e4m3(x) - x)

    return cast, product, qa


# ----------------------------------------------------------------- optimizers
def learning_rate(spec: dict, step):
    """A constant, or linear warm-up to ``peak`` then cosine decay to 0."""
    sched = spec.get("schedule")
    if sched is None:
        return spec["lr"]
    if sched["kind"] != "warmup_cosine":
        raise ValueError(f"unknown schedule {sched['kind']!r}")
    peak, warm, total = spec["lr"], sched["warmup_steps"], sched["total_steps"]
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    return jnp.where(step < warm, peak * step / max(warm, 1),
                     0.5 * peak * (1.0 + jnp.cos(math.pi * frac)))


def clip_global_norm(grads, max_norm: float):
    if not max_norm:
        return grads
    norm = jnp.sqrt(sum((g ** 2).sum() for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def init_opt(spec: dict, params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    if spec["kind"] == "nesterovs":
        return {"v": zeros}
    if spec["kind"] == "adamw":
        return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params)}
    raise ValueError(f"unknown updater {spec['kind']!r}")


def apply_updater(spec: dict, grads, opt, params, step):
    """One update; returns (new params, new optimizer state)."""
    lr = learning_rate(spec, step)
    if spec["kind"] == "nesterovs":
        mu = spec["momentum"]
        v = jax.tree.map(lambda v, g: mu * v - lr * g, opt["v"], grads)
        new = jax.tree.map(lambda p, vn, g: p + (mu * vn - lr * g), params, v, grads)
        return new, {"v": v}
    b1, b2, eps, wd = spec["beta1"], spec["beta2"], spec["eps"], spec["weight_decay"]
    t = step + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    a = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new = jax.tree.map(lambda p, m, v: p - a * m / (jnp.sqrt(v) + eps) - lr * wd * p,
                       params, m, v)
    return new, {"m": m, "v": v}


def first_gradient_from_moment(spec: dict) -> tuple:
    """(key of the optimizer state, factor) such that factor x |state| after
    the very first step is the norm of the gradient the optimizer was given."""
    if spec["kind"] == "nesterovs":
        return "v", 1.0 / spec["lr"]
    if spec["kind"] == "adamw":
        return "m", 1.0 / (1.0 - spec["beta1"])
    raise ValueError(f"unknown updater {spec['kind']!r}")


# ---------------------------------------------------------------------- norms
@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt((x.astype(jnp.float32) ** 2).sum()) for x in jax.tree.leaves(tree)]


@jax.jit
def leaf_norms_of_change(after, before):
    return [jnp.sqrt(((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2).sum())
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))]


def takes_direction(limits: dict) -> bool:
    return any(name in limits for name in DIRECTION_NUMBERS)


@jax.jit
def leaf_turns(got, want):
    """How far each leaf of ``got`` is turned away from the same leaf of
    ``want``, and the whole tree as one vector: the length of the difference
    of the two unit vectors, which is the angle between them in radians while
    it is small, 1.41 at a right angle and 2 at the opposite; NaN where one
    of the two has no length. The size of either side does not enter it."""
    got = [x.astype(jnp.float32) for x in jax.tree.leaves(got)]
    want = [x.astype(jnp.float32) for x in jax.tree.leaves(want)]

    def length(leaves):
        return jnp.sqrt(sum((x ** 2).sum() for x in leaves))

    def turn(pairs, a_length, b_length):
        return jnp.sqrt(sum(((a / a_length - b / b_length) ** 2).sum() for a, b in pairs))

    pairs = list(zip(got, want))
    return ([turn([(a, b)], length([a]), length([b])) for a, b in pairs],
            turn(pairs, length(got), length(want)))


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


# ------------------------------------------------------------------- follower
def follow(module, cfg: dict, key, batches, *, precision: str = "float32",
           keep_fraction: float = 1.0, in_shardings=None, keep_gradient: bool = False) -> dict:
    """Drive the plain reference through ``batches`` (the cell's first
    ``CHECK_STEPS``) from ``make_params(key)``. Returns the readings the
    comparison takes: each step's loss, the norm of every leaf of the first
    gradient as the optimizer gets it, and of every leaf's change after the
    last step; with ``keep_gradient`` also ``grad1``, that first gradient
    itself, on the host.
    ``keep_fraction`` < 1 plants the fault of a step that sees only
    that leading share of each batch, its mean taken over it: every row is
    replaced by one of that share before the step, so the shapes, and with
    them the compiled program, stay the same."""
    spec = cfg["updater"]
    if keep_fraction < 1.0:
        def leading_share(a):
            n = max(1, int(a.shape[0] * keep_fraction))
            # laid out over the chips as the batch was: the step is compiled for that
            return jax.device_put(jnp.concatenate([a[:n]] * (a.shape[0] // n), axis=0), a.sharding)
        batches = [(leading_share(x), leading_share(y)) for x, y in batches]

    def step_fn(params, state, opt, step, x, y):
        (loss, new_state), grads = jax.value_and_grad(
            lambda p: module.loss_fn(p, state, x, y, cfg, precision), has_aux=True)(params)
        grads = clip_global_norm(grads, spec.get("clip_global_norm", 0.0))
        new_params, new_opt = apply_updater(spec, grads, opt, params, step)
        state = {**state, **new_state} if isinstance(state, dict) else new_state
        return new_params, state, new_opt, loss, leaf_norms(grads), grads if keep_gradient else ()

    placed = {} if in_shardings is None else {"in_shardings": in_shardings}
    step_jit = jax.jit(step_fn, donate_argnums=(1, 2), **placed)
    start, state = jax.jit(lambda k: module.make_params(k, cfg))(key)
    if in_shardings is None:
        # committed to the batch's device, as every later step's arguments are:
        # left to the default, the first step lowers (and compiles) apart
        start, state = jax.device_put((start, state), batches[0][0].sharding)
    params, opt = start, init_opt(spec, start)
    record, losses, grad_norms = {}, [], None
    for i, (x, y) in enumerate(batches):
        params, state, opt, loss, gn, grads = step_jit(params, state, opt, jnp.int32(i), x, y)
        losses.append(float(loss))
        if i == 0:
            grad_norms = [float(g) for g in gn]
            if keep_gradient:
                record["grad1"] = jax.device_get(grads)
        del grads
    change = [float(c) for c in leaf_norms_of_change(params, start)]
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "state_norms": [float(n) for n in leaf_norms(state)],
            "leaves": leaf_names(start), "state_leaves": leaf_names(state), **record}


# ----------------------------------------------------------------- comparison
def worst_leaf_gap(got: list, want: list, skip=()) -> tuple:
    """Largest |got - want| over max(want, median want) over the leaves, and
    the index of the leaf that gives it."""
    floor = median_of_positive(want)
    worst, where = 0.0, -1
    for i, (g, w) in enumerate(zip(got, want)):
        if i in skip:
            continue
        gap = abs(g - w) / max(w, floor, 1e-30)
        if not gap <= worst:        # a NaN lands here and is kept
            worst, where = gap, i
    return worst, where


def whole_norm(norms: list, skip=()) -> float:
    return math.sqrt(sum(n * n for i, n in enumerate(norms) if i not in skip))


def median_of_positive(values: list) -> float:
    """The median leaf's norm among the leaves that have one (a residual
    block whose last BatchNorm gain starts at 0 gives its other leaves a
    first gradient of exactly 0)."""
    positive = [v for v in values if v > 0]
    return statistics.median(positive) if positive else 0.0


def median_leaf_gap(got: list, want: list, skip=()) -> float:
    """The median leaf's gap of norms, over the leaves that are compared."""
    floor = median_of_positive(want)
    gaps = [abs(g - w) / max(w, floor, 1e-30)
            for i, (g, w) in enumerate(zip(got, want)) if i not in skip]
    return statistics.median(gaps)


def compare(program: dict, reference: dict, limits: dict) -> dict:
    """The numbers compared, each beside its limit. ``program`` and
    ``reference`` hold ``losses``, ``grad_norms``, ``change_norms``, where
    the model carries state forward (BatchNorm's running statistics)
    ``state_norms`` after the last step, and where the cell's limits name the
    first gradient's direction (``DIRECTION_NUMBERS``) ``grad1``, that
    gradient itself.
    Every number is worked out and printed; one that has no limit in the
    cell's file is not compared (PERF.md says which and why).

    ``grad_*_turn`` is how far the program's first gradient is turned away
    from the reference's (``leaf_turns``): the leaf with the most elements,
    the median leaf (over the leaves whose change is compared) and the whole
    tree as one vector. Rounding is all but orthogonal to the true value, so
    it enters a gap of norms by its power and a turn by its size: where a
    step computed in the precision below slips under the gaps of norms, its
    gradient still points elsewhere. The largest leaf is the steadiest from
    seed to seed, and in a token model it is the embedding table, whose rows
    each hold the gradient of one row of the batch: where the batch's
    residuals all but cancel in the other leaves (PERF.md), they cannot there.

    ``*_leaf_gap`` is the worst leaf's gap of norms, ``*_median_gap`` the
    median leaf's (steady from seed to seed), ``*_norm_gap`` the gap of
    the whole tree's norm (rounding noise, which is all but orthogonal to the
    true value, enters it by its power), both as the contract measures a gap:
    |program's norm - reference's| over the reference's."""
    want_g, want_c = reference["grad_norms"], reference["change_norms"]
    dead = {i for i, g in enumerate(want_g)
            if g < DEAD_GRADIENT_SHARE * median_of_positive(want_g)}
    numbers = {}
    for i, (got, want) in enumerate(zip(program["losses"], reference["losses"])):
        numbers[f"loss{i + 1}_gap"] = abs(got - want) / abs(want)
    numbers["grad_leaf_gap"], g_at = worst_leaf_gap(program["grad_norms"], want_g)
    numbers["change_leaf_gap"], c_at = worst_leaf_gap(program["change_norms"], want_c, dead)
    numbers["grad_median_gap"] = median_leaf_gap(program["grad_norms"], want_g, dead)
    numbers["change_median_gap"] = median_leaf_gap(program["change_norms"], want_c, dead)
    numbers["grad_norm_gap"] = (abs(whole_norm(program["grad_norms"]) - whole_norm(want_g))
                                / whole_norm(want_g))
    numbers["change_norm_gap"] = (abs(whole_norm(program["change_norms"], dead)
                                      - whole_norm(want_c, dead)) / whole_norm(want_c, dead))
    if reference.get("state_norms"):
        numbers["state_leaf_gap"], _ = worst_leaf_gap(program["state_norms"],
                                                      reference["state_norms"])
        numbers["state_median_gap"] = median_leaf_gap(program["state_norms"],
                                                      reference["state_norms"])
    if "grad1" in program and "grad1" in reference:
        turns, whole = leaf_turns(program["grad1"], reference["grad1"])
        sizes = [x.size for x in jax.tree.leaves(reference["grad1"])]
        numbers["grad_largest_turn"] = float(turns[sizes.index(max(sizes))])
        turns = [float(t) for i, t in enumerate(turns) if i not in dead]
        numbers["grad_median_turn"] = (math.nan if any(t != t for t in turns)      # no order
                                       else statistics.median(turns))
        numbers["grad_whole_turn"] = float(whole)
    checks = {name: {"value": value, "limit": limits[name]}
              for name, value in numbers.items() if name in limits}
    ok = (bool(checks) and len(checks) == len(limits)
          and all(c["value"] <= c["limit"] for c in checks.values()))
    if len(program["losses"]) != len(reference["losses"]):
        ok = False
    names = reference.get("leaves", [])
    return {"correct": ok, "checks": checks, "unlimited": {
                k: v for k, v in numbers.items() if k not in limits},
            "worst_leaves": {"grad": names[g_at] if names else g_at,
                             "change": names[c_at] if names else c_at},
            "left_out_of_change": len(dead)}
