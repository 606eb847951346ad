"""Network — the training spine MultiLayerNetwork and ComputationGraph share.

What is the same in the two model classes is written here once: the tail of
the jitted train step (screen, clip, updaters, the guard's select), the
dispatch of a step (tail padding, window, monitor, guard) and the epoch loop
that ``fit`` and ``ParallelWrapper.fit`` drive. What differs by nature, the
forward walk (a list against a DAG) and the loss over the outputs, stays in
the subclasses, which supply ``_step_loss`` and ``_step_inputs``.
"""

from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import faults, guardrails, monitoring
from deeplearning4j_tpu.common.dtypes import BF16, FLOAT32
from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.optimize.async_dispatch import (
    drain_scores, get_window, leading_dim, pad_tail_batch, run_step,
)


def global_norm_clip(grads, max_norm):
    """DL4J GradientNormalization.ClipL2PerParamType analog (global L2 form)."""
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum((g.astype(jnp.float32) ** 2).sum() for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def _entry_keys(tree):
    """The keys of a parameter tree's entries: a list's indices (one a layer),
    a dict's names (one a vertex)."""
    return tree.keys() if isinstance(tree, dict) else range(len(tree))


def _unpack(ds):
    """Accept DataSet/MultiDataSet-like (has .features/.labels), tuple,
    or dict. Returns (features, labels, mask, label_mask).

    ``mask`` is the FORWARD mask (attention/RNN padding; the features
    mask); ``label_mask`` is non-None only when the DataSet carries a
    labels mask DISTINCT from its features mask — the masked-LM shape
    (r4), where the model must attend to all real tokens but the loss
    covers only the selected positions (DL4J's separate featuresMask /
    labelsMask semantics). A single mask keeps its r1-r3 behavior: it
    plays both roles."""
    if hasattr(ds, "features"):
        fm = getattr(ds, "features_mask", None)
        lm = getattr(ds, "labels_mask", None)
        if fm is None:
            # a single labels-mask array keeps its r1-r3 dual role (shared
            # forward + loss mask); a per-output list/dict (r5, MultiDataSet)
            # can only ever be a loss mask
            if isinstance(lm, (list, tuple, dict)):
                return ds.features, ds.labels, None, lm
            return ds.features, ds.labels, lm, None
        return ds.features, ds.labels, fm, lm
    if isinstance(ds, dict):
        return (ds["features"], ds["labels"], ds.get("mask"),
                ds.get("labels_mask"))
    if len(ds) == 4:
        return ds
    if len(ds) == 3:
        x, y, m = ds
        return x, y, m, None
    x, y = ds
    return x, y, None, None


def recorded_init(init):
    """A core's ``init()`` as the span ``setup.init`` of the start-up record
    (with monitoring off too: a model is built before a job switches it on),
    with the leaves and parameters it drew; what it builds names it."""
    @functools.wraps(init)
    def recorded(self, *args, **kwargs):
        with monitoring.startup().measured("setup.init") as span:
            out = init(self, *args, **kwargs)
            span.update(leaves=len(jax.tree.leaves(self.params)), params=self.num_params())
        return out
    return recorded


class Network:
    """A model over a resolved configuration: counters, listeners, the jitted
    train step and the fit loop. A subclass keeps ``params``, ``state``,
    ``opt_state`` and ``_updaters`` as containers of one shape (lists by
    layer, dicts by vertex) and supplies ``_step_loss``, ``_step_inputs``,
    ``_tail_padding_ok`` and ``_exit_state``."""

    def __init__(self, conf):
        self.conf = conf
        self.step_count = 0
        self.epoch_count = 0
        self.score_value = float("nan")
        self.listeners: list = []
        self._policy = BF16 if conf.dtype in ("bf16", "bfloat16") else FLOAT32
        self._rng_key = jax.random.key(conf.seed)
        self._jit_cache: dict = {}
        self._fit_max_batch = 0

    def num_params(self) -> int:
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(self.params))

    def _next_key(self):
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    @property
    def score_value(self) -> float:
        """Latest training score. Under async dispatch
        (optimize/async_dispatch) reading it drains the in-flight window
        first — the value is always that of the newest DISPATCHED step,
        exactly as in sync mode."""
        drain_scores(self)
        return self._score_value

    @score_value.setter
    def score_value(self, value: float) -> None:
        self._score_value = value

    def _layer_stats(self) -> dict:
        """``{entry: state}`` of the layers whose state keeps a gauge's source
        (an expert layer's ``moe_stats``, a gated delta-rule mixer's
        ``delta_log_decay_min``, a sparse-attention mixer's
        ``sparse_tiles_visited``), for the monitor."""
        gauges = ("moe_stats", "delta_log_decay_min", "sparse_tiles_visited")
        return {str(k): self.state[k] for k in _entry_keys(self.state)
                if isinstance(self.state[k], dict) and any(g in self.state[k] for g in gauges)}

    # ------------------------------------------------------- the jitted step
    def _apply_updaters(self, grads, params, opt_state, step):
        with jax.named_scope("clip"):
            if self.conf.max_grad_norm > 0:
                grads = global_norm_clip(grads, self.conf.max_grad_norm)
            cn = float(getattr(self.conf.updater, "clipnorm", 0.0) or 0.0)
            if cn > 0:
                grads = global_norm_clip(grads, cn)
        new_params, new_opt = copy.copy(params), copy.copy(opt_state)
        for k in _entry_keys(params):
            g, u = grads[k], self._updaters[k]
            # per-entry updater override: clip only that layer's subtree
            ucn = float(getattr(u, "clipnorm", 0.0) or 0.0)
            if ucn > 0 and u is not self.conf.updater:
                with jax.named_scope("clip"):
                    g = global_norm_clip(g, ucn)
            with jax.named_scope("updater"):
                upd, new_opt[k] = u.update(g, opt_state[k], params[k], step)
                new_params[k] = jax.tree_util.tree_map(
                    lambda p, d: p - d, params[k], upd)
        return new_params, new_opt

    def _make_train_step(self, guarded: bool = False,
                         clip_active: bool = True):
        if guarded:
            from deeplearning4j_tpu.guardrails import sentinel as _sentinel

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def train_step(params, state, opt_state, step, x, y, key, mask,
                       label_mask=None, ctrl=None):
            (loss, new_state), grads = jax.value_and_grad(
                lambda p: self._step_loss(p, state, x, y, key, mask, label_mask),
                has_aux=True)(params)
            if not guarded:
                new_params, new_opt = self._apply_updaters(grads, params,
                                                           opt_state, step)
                return new_params, new_state, new_opt, loss
            # screen the RAW grads (NaN * clip_scale is still NaN, so the
            # clips below cannot launder a non-finite gradient past the word)
            with jax.named_scope("guard"):
                grads, word = _sentinel.screen(grads, loss, ctrl,
                                               with_clip=clip_active)
            new_params, new_opt = self._apply_updaters(grads, params,
                                                       opt_state, step)
            # a tripped step keeps the old params/opt/state ON DEVICE: the
            # bad update never materializes host-side or in checkpoints
            with jax.named_scope("guard"):
                ok = word[_sentinel.WORD_OK] > 0
                new_params = _sentinel.tree_select(ok, new_params, params)
                new_opt = _sentinel.tree_select(ok, new_opt, opt_state)
                new_state = _sentinel.tree_select(ok, new_state, state)
            return new_params, new_state, new_opt, loss, word

        return train_step

    # ------------------------------------------------------------------- fit
    def fit_batch(self, ds) -> float:
        """One optimization step on a DataSet/(features, labels) pair.

        Sync mode (``DL4J_TPU_ASYNC_STEPS=0`` or an eager-score listener)
        returns the step's loss as a float — the host blocks on the device.
        Async mode (the default) returns a lazy ScoreHandle and keeps up to
        ``DL4J_TPU_ASYNC_STEPS`` steps in flight; any numeric use of the
        handle (or reading ``score()``) drains to a float."""
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "this network is an int8 inference view (quantize()); "
                "train the original f32 network instead")
        x, y, mask, label_mask = _unpack(ds)
        plan = faults.active()
        if plan is not None:
            # input-path injection (nan_grad/loss_spike/data_corrupt): the
            # batch is poisoned BEFORE the replay ring sees it, so retries
            # replay the same poisoned bytes deterministically
            x, y = faults.poison_batch(plan, x, y, step=self.step_count)
        return self._fit_unpacked(x, y, mask, label_mask)

    def _pad_tail(self, x, y, mask, label_mask):
        """Partial epoch tails pad up to a pow2 bucket (loss-exact via
        label-mask zeroing) instead of compiling one program per shape."""
        if env.pad_tail:
            b = leading_dim(x)
            if b > self._fit_max_batch:
                self._fit_max_batch = b
            elif b < self._fit_max_batch and self._tail_padding_ok():
                return pad_tail_batch(x, y, mask, label_mask,
                                      self._fit_max_batch)
        return x, y, mask, label_mask

    def _fit_unpacked(self, x, y, mask, label_mask):
        """The step on an unpacked batch: through the guard where one is
        attached, else the jitted step, its score delivered by
        ``async_dispatch.run_step``."""
        data, masks = self._step_inputs(x, y, mask, label_mask)
        window = get_window(self)
        mon = monitoring.fit_monitor()
        guard = guardrails.get_guard(self)
        if guard is not None:
            result = guard.step(self, data, masks, window, mon)
            self.step_count += 1
            return result
        step_fn = self._jit_cache.get("train")
        if step_fn is None:
            step_fn = self._make_train_step()
            self._jit_cache["train"] = step_fn
        args = (self.params, self.state, self.opt_state,
                jnp.asarray(self.step_count, jnp.int32), *data,
                self._next_key(), *masks)

        def call():
            self.params, self.state, self.opt_state, loss = step_fn(*args)
            if mon is not None:
                mon.hold_exit_share(self._exit_state())
                mon.hold_layer_stats(self._layer_stats())
            return loss

        result = run_step(self, call, window, mon)
        self.step_count += 1
        return result

    def fit(self, data, labels=None, epochs: int = 1):
        """fit(iterator) or fit(features, labels) (the reference's fit
        overloads)."""
        if labels is None:
            return self._fit_epochs(data, epochs, self.fit_batch)
        self._fit_batches(((data, labels) for _ in range(epochs)),
                          self.fit_batch)
        for lst in self.listeners:
            lst.on_fit_end(self)
        return self

    def _fit_batches(self, batches, fit_batch):
        try:
            for ds in batches:
                fit_batch(ds)
        except BaseException:
            # best-effort drain; the batch-loop exception wins
            drain_scores(self, suppress=True)
            raise
        # in-flight scores (and any async step failure) land BEFORE the
        # epoch-end listeners observe the epoch
        drain_scores(self)

    def _fit_epochs(self, data, epochs: int, fit_batch):
        """The epoch loop. ``fit_batch`` is the step: the network's own, or
        ``ParallelWrapper``'s, which shards the batch first."""
        for _ in range(epochs):
            for lst in self.listeners:
                lst.on_epoch_start(self, self.epoch_count)
            # data-wait spans time the iterator pull per batch (host input
            # pipeline vs device step split); None = monitoring off
            mon = monitoring.fit_monitor()
            self._fit_batches(
                data if mon is None else mon.wrap_batches(data, self),
                fit_batch)
            if hasattr(data, "reset"):
                data.reset()
            for lst in self.listeners:
                lst.on_epoch_end(self, self.epoch_count)
            self.epoch_count += 1
        for lst in self.listeners:
            lst.on_fit_end(self)
        return self

    # ------------------------------------------------------------- quantize
    def quantize(self, dtype: str = "int8"):
        """Weight-only int8 inference view of this network (the original
        stays trainable). See deeplearning4j_tpu.quantize."""
        from deeplearning4j_tpu.quantize import quantize_network

        return quantize_network(self, dtype)

    # ----------------------------------------------------------------- serde
    def save(self, path: str, save_updater: bool = True):
        from deeplearning4j_tpu.util.serialization import write_model

        write_model(self, path, save_updater=save_updater)
