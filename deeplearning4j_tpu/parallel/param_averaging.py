"""Parameter averaging with local steps — the actual semantics of the
reference's ParameterAveragingTrainingMaster (local SGD).

Reference analog: org.deeplearning4j.spark.impl.paramavg.
ParameterAveragingTrainingMaster — each Spark worker fits its replica for
``averagingFrequency`` iterations on its own shard, then parameters are
averaged cluster-wide (RDD reduce) and redistributed. Between averages the
replicas genuinely DIVERGE; that divergence (and the reduced communication
frequency) is the point of the algorithm — it is NOT equivalent to
synchronous data-parallel SGD.

TPU-native: replicas are a leading device axis on the param/optimizer trees,
sharded over the mesh's data axis inside one SPMD program. Local steps touch
no collective at all; every K-th step ends with one pmean of the params
(and a pmean of the optimizer state, matching the reference's
``averageUpdaterState=true`` default). The whole K-step round is a single
``lax.scan`` inside one jitted shard_map call, so the per-step cost is the
same fused train step the single-device path runs.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu import monitoring


class ParameterAveragingTrainer:
    """Local-SGD trainer: K local steps per replica, then average.

    loss_fn(params, x, y) -> scalar loss on the LOCAL shard. ``updater`` is
    any framework updater (stateful ones are fine: the state lives
    per-replica and is averaged with the params, the reference's
    averageUpdaterState behavior).

    ``stateful=True`` (r4) switches the functional contract to
    loss_fn(params, state, rng, x, y) -> (loss, new_state) — the
    MultiLayerNetwork/ComputationGraph ``as_loss_fn`` surface — so models
    with BatchNorm running stats and dropout train on this path: network
    state is carried per-replica across the K local steps, float state
    leaves (running stats) are AVERAGED at sync like the reference master
    averages them with the params, and each local step draws a distinct
    per-replica dropout key (deterministically folded from the round key,
    the step counter, and the replica index, so the round stays one
    replicated SPMD program).
    """

    def __init__(self, loss_fn: Callable, updater, mesh, *,
                 axis: str = "data", averaging_frequency: int = 1,
                 average_updater_state: bool = True, stateful: bool = False,
                 max_grad_norm: float = 0.0, skip_average=None):
        from deeplearning4j_tpu.optimize.updaters import get_updater

        self.loss_fn = loss_fn
        self.updater = get_updater(updater)
        self.mesh = mesh
        self.axis = axis
        # global-norm gradient clipping inside each LOCAL step, mirroring
        # the fit path's conf.max_grad_norm (r5); 0 = off
        self.max_grad_norm = float(max_grad_norm)
        # top-level param entries (MLN layer list / CG vertex dict, bools
        # aligned with the entries) whose averaging collective is SKIPPED
        # (r5): frozen entries never diverge, so averaging them wastes
        # collective bytes — and on the virtual-CPU test mesh XLA's
        # scan+psum rewrite costs 1 ulp even over identical replicas,
        # which would wiggle params that must stay bit-identical
        self.skip_average = skip_average
        if int(averaging_frequency) < 1:
            raise ValueError(f"averaging_frequency must be >= 1, got "
                             f"{averaging_frequency}")
        self.freq = int(averaging_frequency)
        self.average_updater_state = average_updater_state
        self.stateful = stateful
        self._round = None
        self._round_keys = None

    def init(self, params, state=None, rng=None):
        n = self.mesh.shape[self.axis]

        def rep(tree):
            return jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), tree)

        opt = self.updater.init_state(params)
        self._round = None  # re-init invalidates the cached compiled round
        self._round_keys = None
        carry = {"params": rep(params), "opt": rep(opt),
                 "step": jnp.asarray(0, jnp.int32)}
        if self.stateful:
            carry["state"] = rep(state if state is not None else {})
            key = rng if rng is not None else jax.random.key(0)
            carry["rng"] = jax.random.key_data(key)
        return carry

    def _build(self, carry, batch_keys):
        from deeplearning4j_tpu.nn.multilayer import global_norm_clip

        loss_fn, updater = self.loss_fn, self.updater
        axis = self.axis
        avg_opt = self.average_updater_state
        stateful = self.stateful
        max_gn = self.max_grad_norm
        skip = self.skip_average
        has_mask = "mask" in batch_keys
        has_lmask = "label_mask" in batch_keys
        # elastic rounds (an "active" flag in the batch): the average is
        # renormalized over the surviving replicas — a lost worker's local
        # steps are excluded, and because every replica leaves the round
        # holding the (survivor-weighted) average, the lost one re-enters
        # the next round synced to the group: re-admission is the algebra,
        # not a special case
        has_active = "active" in batch_keys

        def round_fn(carry, batch):
            """One averaging round: K purely-local steps, then ONE pmean.
            batch: dict of [K, local_batch, ...] arrays — K microbatches
            for this replica ("x"/"y" always; "mask"/"label_mask" (r5)
            when the stream carries them; "active" is the per-replica
            survival flag and rides OUTSIDE the K-step scan)."""
            batch = dict(batch)
            active = batch.pop("active", None)
            params = jax.tree_util.tree_map(lambda t: t[0], carry["params"])
            opt = jax.tree_util.tree_map(lambda t: t[0], carry["opt"])
            if stateful:
                net_state0 = jax.tree_util.tree_map(lambda t: t[0],
                                                    carry["state"])
                round_key = jax.random.wrap_key_data(carry["rng"])

            def local_step(state, mb):
                x, y = mb["x"], mb["y"]
                if stateful:
                    p, o, s, i = state
                    k = jax.random.fold_in(
                        jax.random.fold_in(round_key, i),
                        lax.axis_index(axis))
                    extra, kw = (), {}
                    if has_mask or has_lmask:
                        extra = (mb.get("mask"), mb.get("label_mask"))
                    if "denom" in mb:
                        kw["denom"] = mb["denom"]
                    (loss, s2), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(p, s, k, x, y, *extra, **kw)
                else:
                    p, o, i = state
                    loss, g = jax.value_and_grad(loss_fn)(p, x, y)
                if max_gn > 0:
                    g = global_norm_clip(g, max_gn)
                upd, o2 = updater.update(g, o, p, i)
                p2 = jax.tree_util.tree_map(lambda a, d: a - d, p, upd)
                if stateful:
                    return (p2, o2, s2, i + 1), loss
                return (p2, o2, i + 1), loss

            if stateful:
                (params, opt, net_state, step), losses = lax.scan(
                    local_step, (params, opt, net_state0, carry["step"]),
                    batch)
            else:
                (params, opt, step), losses = lax.scan(
                    local_step, (params, opt, carry["step"]), batch)
            # the round's single collective: average the diverged replicas
            # (frozen entries pass through untouched — see skip_average).
            # Elastic rounds weight the mean by each replica's active flag
            # and renormalize by the survivor count.
            if has_active:
                w = active[0]                           # this shard's 0/1
                survivors = lax.psum(w, axis)
                pleaf = lambda a: lax.psum(a * w, axis) / survivors
            else:
                pleaf = lambda a: lax.pmean(a, axis)

            def avg_state_leaf(t):
                # running stats (floats) are averaged at sync, like the
                # reference's parameter averaging of the full param
                # vector; integer leaves (counters) advance identically
                # per replica and pass through
                if jnp.issubdtype(t.dtype, jnp.floating):
                    return pleaf(t)
                return t

            def avg_tree(tree):
                pm = lambda t: jax.tree_util.tree_map(pleaf, t)
                if skip is None:
                    return pm(tree)
                if isinstance(tree, dict):
                    return {k: (tree[k] if skip.get(k) else pm(tree[k]))
                            for k in tree}
                return [t if s else pm(t) for t, s in zip(tree, skip)]

            params = avg_tree(params)
            if avg_opt:
                opt = avg_tree(opt)
            out = {"params": jax.tree_util.tree_map(lambda t: t[None], params),
                   "opt": jax.tree_util.tree_map(lambda t: t[None], opt),
                   "step": step}
            if stateful:
                net_state = jax.tree_util.tree_map(avg_state_leaf, net_state)
                out["state"] = jax.tree_util.tree_map(lambda t: t[None],
                                                      net_state)
                out["rng"] = jax.random.key_data(
                    jax.random.fold_in(round_key, step))
            return out, pleaf(losses.mean())

        spec_rep = {
            "params": jax.tree_util.tree_map(lambda _: P(axis),
                                             carry["params"]),
            "opt": jax.tree_util.tree_map(lambda _: P(axis), carry["opt"]),
            "step": P(),
        }
        if stateful:
            spec_rep["state"] = jax.tree_util.tree_map(lambda _: P(axis),
                                                       carry["state"])
            spec_rep["rng"] = P()
        batch_specs = {k: (P(None) if k == "denom"
                           else P(axis) if k == "active"
                           else P(None, axis))
                       for k in batch_keys}
        fn = shard_map(
            round_fn, mesh=self.mesh,
            in_specs=(spec_rep, batch_specs),
            out_specs=(spec_rep, P()),
            # the model loss may route through Pallas kernels (fused
            # LSTM/GRU, flash attention), whose calls don't carry vma
            # metadata — same decision as parallel/sequence.py
            check_vma=False,
        )
        return jax.jit(fn)

    def fit_round(self, carry, x, y, mask=None, label_mask=None, lost=None):
        """One full averaging round over a global batch.

        x/y: [K * global_batch, ...] arrays — or dicts of them (r5: the
        ComputationGraph multi-input/-output shape; every leaf shares the
        batch axis) — split into K sequential microbatches; each replica
        sees K local shards, steps K times locally, then the single
        parameter average runs. ``mask``/``label_mask`` (r5): optional
        [K * global_batch, T] masks riding the same split — the stateful
        as_loss_fn surface normalizes each local step by its shard's
        valid count (single-input/-output only).

        ``lost``: replica indices whose contribution this round is DROPPED
        (crashed/straggling workers): the average renormalizes over the
        survivors, and every replica — including the lost ones — leaves
        the round holding that survivor average, so a recovered worker is
        re-admitted in sync next round. Returns (carry, loss)."""
        import numpy as np

        if (mask is not None or label_mask is not None) and not self.stateful:
            raise ValueError(
                "masked batches need stateful=True (the as_loss_fn surface "
                "that takes (mask, label_mask))")
        K = self.freq
        dp = self.mesh.shape[self.axis]
        denom = None
        if K == 1 and (mask is not None or label_mask is not None):
            # K=1 IS sync DP: each replica normalizes its shard's summed
            # loss by global_valid/dp so the post-step parameter mean
            # equals one global-batch step EXACTLY, padding distribution
            # notwithstanding. K>1 keeps local-valid normalization — each
            # worker's local step is its own fit step (the reference's
            # per-worker minibatch semantics). Computed from the incoming
            # host arrays BEFORE device placement (no device round-trip).
            nm = np.asarray(label_mask if label_mask is not None else mask)
            denom = jnp.asarray(
                np.maximum(nm.reshape(K, -1).sum(axis=1), 1.0) / dp,
                jnp.float32)
        batch = {"x": jax.tree_util.tree_map(jnp.asarray, x),
                 "y": jax.tree_util.tree_map(jnp.asarray, y)}
        if mask is not None:
            batch["mask"] = jnp.asarray(mask)
        if label_mask is not None:
            batch["label_mask"] = jnp.asarray(label_mask)
        n = jax.tree_util.tree_leaves(batch["x"])[0].shape[0]
        for leaf in jax.tree_util.tree_leaves((batch["x"], batch["y"])):
            if leaf.shape[0] != n:
                raise ValueError(
                    f"every x/y slot must share the batch axis: got "
                    f"{leaf.shape[0]} rows vs {n}")
        if n % K:
            raise ValueError(f"batch {n} not divisible into {K} local steps")
        if (n // K) % dp:
            raise ValueError(f"per-step batch {n // K} not "
                             f"divisible by data-parallel degree {dp}")
        batch = jax.tree_util.tree_map(
            lambda v: v.reshape((K, n // K) + v.shape[1:]), batch)
        if denom is not None:
            batch["denom"] = denom
        if lost:
            bad = [i for i in lost if not 0 <= int(i) < dp]
            if bad:
                raise ValueError(f"lost replica indices {bad} outside the "
                                 f"{dp}-replica data axis")
            if len(set(int(i) for i in lost)) >= dp:
                raise ValueError("cannot drop every replica from a round")
            act = np.ones(dp, np.float32)
            act[[int(i) for i in lost]] = 0.0
            batch["active"] = jnp.asarray(act)
        keys = frozenset(batch)
        if self._round is None or self._round_keys != keys:
            self._round = self._build(carry, keys)
            self._round_keys = keys
        mon = monitoring.localsgd_monitor()
        if mon is None:
            return self._round(carry, batch)
        # sync duration = wall time of the whole round (K local steps +
        # the pmean sync), blocked on the loss so the device work is in it
        with monitoring.span("localsgd.round", k=K, dp=dp):
            t0 = time.perf_counter()
            carry, loss = self._round(carry, batch)
            jax.block_until_ready(loss)
            mon.sync_seconds.observe(time.perf_counter() - t0)
        mon.rounds.inc()
        return carry, loss

    def params(self, carry):
        """The (replica-identical) averaged params as a plain tree."""
        return jax.tree_util.tree_map(lambda t: t[0], carry["params"])

    def state(self, carry):
        """The network state tree after the last sync (stateful mode):
        float leaves are replica-identical post-average; integer leaves are
        taken from replica 0 (identical by construction — every replica
        runs the same step count)."""
        if not self.stateful:
            raise ValueError("state() requires stateful=True")
        return jax.tree_util.tree_map(lambda t: t[0], carry["state"])
