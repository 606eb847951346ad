"""Pipeline parallelism — GPipe-style microbatched stage loop.

Reference analog: NONE — the reference has no pipeline parallelism (SURVEY.md
§2.4). Net-new, TPU-first design: the "pipe" mesh axis holds one stage per
device; microbatch activations rotate stage-to-stage with ``lax.ppermute``
over the ICI ring inside a ``lax.fori_loop``. The whole pipeline — all
bubbles, sends, and stage compute — is a single differentiable SPMD program,
so ``jax.grad`` of the pipelined forward IS pipelined backprop (ppermute's
transpose is the reverse rotation); no hand-written 1F1B schedule is needed
for correctness, and XLA overlaps the ppermute with stage compute.

Constraints (documented, enforced): every stage must map activations of one
fixed shape to the same shape (the classic homogeneous-block setting, e.g. a
stack of transformer blocks); stage parameters are passed stacked on a
leading ``n_stages`` axis and sharded over "pipe".
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import DeviceMesh

_pvary = functools.partial(lax.pcast, to="varying")


def stack_stage_params(stage_params_list):
    """Stack per-stage param pytrees along a new leading axis (to be sharded
    over "pipe"). All stages must share a param structure."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_params_list)


def _pipeline_local(params, x, *, stage_fn, n_micro, axis):
    """Per-device body under shard_map. params: leading dim 1 (this stage's
    slice); x: the full batch (replicated over "pipe")."""
    params = jax.tree_util.tree_map(lambda p: p[0], params)
    n_stages = lax.psum(1, axis)
    stage = lax.axis_index(axis)
    micro = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
    mshape = micro.shape[1:]

    carry0 = _pvary(jnp.zeros(mshape, x.dtype), (axis,))
    outs0 = _pvary(jnp.zeros((n_micro,) + mshape, x.dtype), (axis,))
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def body(t, state):
        carry, outs = state
        # stage 0 ingests microbatch t (clipped; out-of-range iterations feed
        # garbage that is never written to outs), others take the carry.
        feed = lax.dynamic_index_in_dim(
            micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        inp = jnp.where(stage == 0, feed, carry)
        out = stage_fn(params, inp)
        # last stage has finished microbatch t - (n_stages - 1) at step t
        widx = t - (n_stages - 1)
        write = jnp.logical_and(stage == n_stages - 1, widx >= 0)
        prev = lax.dynamic_index_in_dim(
            outs, jnp.clip(widx, 0, n_micro - 1), 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(write, out, prev), jnp.clip(widx, 0, n_micro - 1), 0)
        carry = lax.ppermute(out, axis, perm)
        return carry, outs

    total = n_micro + n_stages - 1
    _, outs = lax.fori_loop(0, total, body, (carry0, outs0))
    # outs is only valid on the last stage; broadcast it to every pipe device
    # (psum of a one-hot-masked tensor — GSPMD lowers this to a broadcast).
    outs = lax.psum(jnp.where(stage == n_stages - 1, outs, 0), axis)
    return outs.reshape(x.shape)


class GPipe:
    """Microbatched pipeline over the mesh "pipe" axis.

    ``stage_fn(stage_params, x) -> y`` with ``y.shape == x.shape``;
    ``params`` stacked on a leading n_stages axis (``stack_stage_params``).

        pipe = GPipe(stage_fn, mesh, n_microbatches=4)
        y = pipe(stacked_params, x)            # pipelined forward
        grads = jax.grad(loss_of(pipe))(...)   # pipelined backward for free
    """

    def __init__(self, stage_fn: Callable, mesh: DeviceMesh,
                 n_microbatches: int = 4, axis: str = "pipe"):
        self.stage_fn = stage_fn
        self.mesh = mesh
        self.n_micro = n_microbatches
        self.axis = axis

    def __call__(self, stacked_params, x):
        n_stages = self.mesh.shape[self.axis]
        lead = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        if lead != n_stages:
            raise ValueError(f"params stacked for {lead} stages but mesh "
                             f"'{self.axis}' axis has {n_stages}")
        if x.shape[0] % self.n_micro:
            raise ValueError(f"batch {x.shape[0]} not divisible by "
                             f"{self.n_micro} microbatches")
        fn = shard_map(
            functools.partial(_pipeline_local, stage_fn=self.stage_fn,
                              n_micro=self.n_micro, axis=self.axis),
            mesh=self.mesh.mesh,
            in_specs=(self._param_spec(stacked_params), P()),
            out_specs=P(),
        )
        return fn(stacked_params, x)

    def _param_spec(self, stacked_params):
        return jax.tree_util.tree_map(
            lambda p: P(*([self.axis] + [None] * (np.ndim(p) - 1))), stacked_params)

    def sequential_reference(self, stacked_params, x):
        """Unpipelined equivalent (for parity tests): apply stages in order."""
        n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        for i in range(n_stages):
            p = jax.tree_util.tree_map(lambda q: q[i], stacked_params)
            x = self.stage_fn(p, x)
        return x


def pipeline_train_step(pipe: GPipe, loss_fn: Callable, optimizer,
                        head_fn: Optional[Callable] = None):
    """Build a jitted pipelined train step.

    loss_fn(y_pred, y) -> scalar; head_fn(head_params, activations) -> y_pred
    (e.g. the output projection, run replicated after the pipeline).
    Returns step(params, opt_state, step_i, x, y) -> (params, opt_state, loss)
    where params = {"stages": stacked, "head": head_params or {}}.
    """

    def loss(params, x, y):
        h = pipe(params["stages"], x)
        pred = head_fn(params.get("head", {}), h) if head_fn is not None else h
        return loss_fn(pred, y)

    @jax.jit
    def step(params, opt_state, step_i, x, y):
        lval, grads = jax.value_and_grad(loss)(params, x, y)
        upd, opt_state = optimizer.update(grads, opt_state, params, step_i)
        params = jax.tree_util.tree_map(lambda p, d: p - d, params, upd)
        return params, opt_state, lval

    return step


# --------------------------------------------------------------------- r5
# Heterogeneous-stage pipeline: the conv-net setting (VERDICT r4 #4 — PP
# over ResNet-50's four stage groups, whose activation shapes and param
# structures all differ). GPipe above requires homogeneous stages; here
# activations travel the ppermute ring in ONE fixed-size flat buffer
# (padded to the largest inter-stage activation), and each device holds
# only ITS stage's parameters — packed into one row of a
# [n_stages, max_flat] float32 buffer sharded over "pipe" — unpacking
# them with static shapes inside its lax.switch branch. The schedule,
# differentiability-for-free (grad of ppermute = reverse rotation), and
# single-SPMD-program properties are the same as GPipe's.


def pack_stage_params(stage_params_list):
    """Pack heterogeneous per-stage param pytrees into ([S, Lmax] float32
    buffer, metadata for unpack). Row s holds stage s's raveled leaves
    (jax.flatten_util.ravel_pytree), zero-padded; sharding the buffer
    P("pipe") gives each device only its own stage's parameters."""
    from jax.flatten_util import ravel_pytree

    metas, vecs = [], []
    for p in stage_params_list:
        vec, unravel = ravel_pytree(p)
        metas.append((unravel, vec.dtype, int(vec.shape[0])))
        vecs.append(vec.astype(jnp.float32))
    lmax = max((v.shape[0] for v in vecs), default=0)
    packed = jnp.stack([jnp.pad(v, (0, lmax - v.shape[0])) for v in vecs])
    return packed, metas


def unpack_stage_params(row, meta):
    """Rebuild one stage's pytree from its packed row (static slice)."""
    unravel, dtype, size = meta
    return unravel(row[:size].astype(dtype))


def _hetero_local(packed, x, *, stage_fns, metas, shapes, n_micro, axis):
    """Per-device body. packed: [1, Lmax] (this stage's row); x: the full
    [B, ...] stage-0 input, replicated over "pipe". shapes[s] is the
    PER-MICROBATCH activation shape fed INTO stage s (shapes[S] = the
    pipeline's output shape)."""
    row = packed[0]
    n_stages = len(stage_fns)
    stage = lax.axis_index(axis)
    mb = x.shape[0] // n_micro
    flat = [int(np.prod((mb,) + tuple(s))) for s in shapes]
    bmax = max(flat)

    micro = x.reshape((n_micro, mb) + x.shape[1:])
    micro_buf = jnp.pad(micro.reshape(n_micro, flat[0]).astype(jnp.float32),
                        ((0, 0), (0, bmax - flat[0])))

    def branch(s):
        def f(buf):
            p = unpack_stage_params(row, metas[s])
            xin = buf[:flat[s]].reshape((mb,) + tuple(shapes[s]))
            y = stage_fns[s](p, xin)
            yf = y.reshape(-1).astype(jnp.float32)
            return jnp.pad(yf, (0, bmax - flat[s + 1]))
        return f

    branches = [branch(s) for s in range(n_stages)]

    carry0 = _pvary(jnp.zeros((bmax,), jnp.float32), (axis,))
    outs0 = _pvary(jnp.zeros((n_micro, flat[-1]), jnp.float32), (axis,))
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def body(t, state):
        carry, outs = state
        feed = lax.dynamic_index_in_dim(
            micro_buf, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        inp = jnp.where(stage == 0, feed, carry)
        out = lax.switch(stage, branches, inp)
        widx = t - (n_stages - 1)
        write = jnp.logical_and(stage == n_stages - 1, widx >= 0)
        prev = lax.dynamic_index_in_dim(
            outs, jnp.clip(widx, 0, n_micro - 1), 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(write, out[:flat[-1]], prev),
            jnp.clip(widx, 0, n_micro - 1), 0)
        carry = lax.ppermute(out, axis, perm)
        return carry, outs

    total = n_micro + n_stages - 1
    _, outs = lax.fori_loop(0, total, body, (carry0, outs0))
    outs = lax.psum(jnp.where(stage == n_stages - 1, outs, 0), axis)
    return outs.reshape((n_micro * mb,) + tuple(shapes[-1]))


class HeteroPipe:
    """Microbatched pipeline over "pipe" with HETEROGENEOUS stages.

    stage_fns: list of ``fn(stage_params, x) -> y`` — arbitrary per-stage
    param structure and activation shapes. ``shapes``: per-microbatch-row
    activation shapes, shapes[s] = input of stage s (WITHOUT the batch
    dim), length n_stages + 1 (last = pipeline output). Params come from
    :func:`pack_stage_params`.

        packed, metas = pack_stage_params([p0, p1, p2, p3])
        pipe = HeteroPipe(stage_fns, metas, shapes, mesh, n_microbatches=4)
        y = pipe(packed, x)                   # pipelined forward
        jax.grad(...)                          # pipelined backward for free
    """

    def __init__(self, stage_fns, metas, shapes, mesh: DeviceMesh,
                 n_microbatches: int = 4, axis: str = "pipe"):
        if len(shapes) != len(stage_fns) + 1:
            raise ValueError(f"shapes must list n_stages+1 activation "
                             f"shapes, got {len(shapes)} for "
                             f"{len(stage_fns)} stages")
        self.stage_fns = list(stage_fns)
        self.metas = list(metas)
        self.shapes = [tuple(s) for s in shapes]
        self.mesh = mesh
        self.n_micro = n_microbatches
        self.axis = axis

    def __call__(self, packed, x):
        n_stages = self.mesh.shape[self.axis]
        if len(self.stage_fns) != n_stages:
            raise ValueError(f"{len(self.stage_fns)} stages but mesh "
                             f"'{self.axis}' axis has {n_stages}")
        if x.shape[0] % self.n_micro:
            raise ValueError(f"batch {x.shape[0]} not divisible by "
                             f"{self.n_micro} microbatches")
        fn = shard_map(
            functools.partial(_hetero_local, stage_fns=self.stage_fns,
                              metas=self.metas, shapes=self.shapes,
                              n_micro=self.n_micro, axis=self.axis),
            mesh=self.mesh.mesh,
            in_specs=(P(self.axis, None), P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(packed, x)

    def sequential_reference(self, packed, x):
        """Unpipelined equivalent (for parity tests)."""
        for s, fn in enumerate(self.stage_fns):
            p = unpack_stage_params(packed[s], self.metas[s])
            x = fn(p, x)
        return x


def graph_stage_fn(model, names, entry):
    """``stage_fn(stage_params, x)`` applying a ComputationGraph vertex
    subsequence in topological order (r5 — the ResNet-50 pipeline stages).

    ``names``: a contiguous topological slice whose only external
    dependency is ``entry`` (the previous stage's output vertex / graph
    input); returns the LAST name's activation. Network state (BN running
    stats) is closed over frozen — stage bodies run inference-mode
    normalization, the standard GPipe conv setting.
    """
    conf = model.conf
    state = model.state
    names = list(names)
    name_set = set(names)
    for n in names:
        for dep in conf.vertex_inputs.get(n, []):
            if dep not in name_set and dep != entry:
                raise ValueError(
                    f"stage vertex '{n}' depends on '{dep}' outside the "
                    f"stage (entry is '{entry}') — stages must be "
                    f"contiguous cuts of the graph")

    def stage_fn(stage_params, x):
        acts = {entry: x}
        for n in names:
            v = conf.vertices[n]
            ins = [acts[d] for d in conf.vertex_inputs.get(n, [])]
            if n in conf.preprocessors:
                ins = [conf.preprocessors[n](ins[0])]
            out, _ = v.apply(stage_params.get(n, {}), state.get(n, {}),
                             ins, train=False)
            acts[n] = out
        return acts[names[-1]]

    return stage_fn
