"""MultiLayerNetwork — the sequential model class.

Reference analog: org.deeplearning4j.nn.multilayer.MultiLayerNetwork
(fit/output/score/feedForward/evaluate, truncated BPTT, rnnTimeStep) plus the
Solver/StochasticGradientDescent optimize stack (org.deeplearning4j.optimize.
solvers) and BaseMultiLayerUpdater.

TPU-first redesign: where the reference runs one JNI op-dispatch per layer-op
with a Java loop driving it (call stack in SURVEY.md §3.1), here the ENTIRE
training iteration — forward, loss, backward, updater apply — is ONE jitted
XLA program with donated param/optimizer buffers (the "flat params + fused
updater" property of DL4J delivered by the compiler). Listeners observe
results host-side, exactly like the reference's listener bus.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.nn.conf.builders import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.base import (
    checkpoint_layer, layer_loss_terms, scope_name as _scope_name, take_loss_weights,
)
from deeplearning4j_tpu.nn.layers.output import CenterLossOutputLayer
# _unpack and global_norm_clip are imported from here by parallel/ and the
# benchmark's tests
from deeplearning4j_tpu.nn.network import (  # noqa: F401
    Network, _unpack, global_norm_clip, recorded_init,
)
from deeplearning4j_tpu.optimize.async_dispatch import (
    deliver_score, get_window, supports_tail_padding,
)
from deeplearning4j_tpu.optimize.updaters import NoOp, get_updater


def _tree_cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


def _check_carry_batch(carries, batch: int):
    """Stored rnn_time_step state must match the incoming batch; raise a
    clear error instead of an opaque XLA shape failure inside jit."""
    for c in carries.values():
        stored = jax.tree_util.tree_leaves(c)[0].shape[0]
        if stored != batch:
            raise ValueError(
                f"batch size changed between rnn_time_step calls "
                f"({batch} vs stored {stored}); call "
                f"rnn_clear_previous_state() first")


def extract_carry_rows(carries, rows):
    """Per-row view of an rnn carry dict: {layer_idx: carry_tuple} with
    leaves [B, ...] -> same structure with leaves [len(rows), ...].
    ``rows`` is an int or a sequence of row indices. This is the slot-pool
    primitive (generation/): individual sequences move in and out of a
    pooled batch without the whole-batch "batch size changed" rejection
    the plain rnn_time_step API keeps."""
    idx = jnp.atleast_1d(jnp.asarray(rows, jnp.int32))
    return jax.tree_util.tree_map(lambda a: a[idx], carries)


def merge_carry_rows(carries, sub, rows):
    """Inverse of :func:`extract_carry_rows`: write ``sub``'s rows (leaves
    [len(rows), ...]) into ``carries`` at ``rows``; returns the merged
    carry dict (functional — inputs are not mutated)."""
    idx = jnp.atleast_1d(jnp.asarray(rows, jnp.int32))
    return jax.tree_util.tree_map(lambda a, r: a.at[idx].set(r), carries, sub)


class MultiLayerNetwork(Network):
    """Sequential network over a MultiLayerConfiguration."""

    def __init__(self, conf: MultiLayerConfiguration):
        if not conf.layer_input_types:
            conf.resolve()
        super().__init__(conf)
        self.layers = conf.layers
        self.params: list[dict] = []
        self.state: list[dict] = []
        self.opt_state: list[dict] = []
        # frozen wins over any per-layer updater override (TransferLearning)
        self._updaters = [NoOp() if not l.trainable
                          else (get_updater(l.updater) if l.updater is not None
                                else conf.updater)
                          for l in self.layers]

    # ------------------------------------------------------------------ init
    @recorded_init
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        seed = self.conf.seed if seed is None else seed
        key = jax.random.key(seed)
        self._rng_key = jax.random.fold_in(key, 0xD14)
        self.params, self.state = [], []
        for i, layer in enumerate(self.layers):
            k = jax.random.fold_in(key, i)
            p, s = layer.init(k, self.conf.layer_input_types[i])
            self.params.append(p)
            self.state.append(s)
        self.opt_state = [u.init_state(p) for u, p in zip(self._updaters, self.params)]
        return self

    def params_table(self) -> dict:
        """Flat {"0_W": array, ...} naming (MultiLayerNetwork.paramTable)."""
        out = {}
        for i, p in enumerate(self.params):
            for k, v in p.items():
                if isinstance(v, dict):
                    for k2, v2 in v.items():
                        out[f"{i}_{k}_{k2}"] = v2
                else:
                    out[f"{i}_{k}"] = v
        return out

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, x, train, rng, mask):
        """Walk layers; returns (pre-output of final layer, new states, final
        mask, the final layer's input)."""
        new_states = []
        itype_chain = self.conf.layer_input_types
        n = len(self.layers)
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i](x)
            k = jax.random.fold_in(rng, i) if rng is not None else None
            # <index>.<LayerClass>: JAX writes jvp(<scope>) on the forward and
            # transpose(jvp(<scope>)) on the backward operations' op_name
            scope = jax.named_scope(_scope_name(i, layer))
            if i == n - 1 and hasattr(layer, "preout"):
                with scope:
                    x = layer._maybe_dropout(x, train, k) if train else x
                    preout = layer.preout(params[i], x)
                new_states.append(state[i])
                return preout, new_states, mask, x
            with scope:
                if getattr(layer, "remats_itself", False):
                    # a container of layers: each application inside it is its
                    # own jax.checkpoint, never the container as a whole
                    x, s = layer.apply(params[i], state[i], x, train=train, rng=k,
                                       mask=mask, remat=self.conf.remat and train)
                elif self.conf.remat and train:
                    # remat policy (workspace-tuning analog): save only each
                    # layer's input (and the attention kernel's output where it
                    # ran); recompute its other internals during backprop
                    x, s = checkpoint_layer(
                        lambda p, st, xx, kk, mm, _l=layer: _l.apply(
                            p, st, xx, train=True, rng=kk, mask=mm)
                    )(params[i], state[i], x, k, mask)
                else:
                    x, s = layer.apply(params[i], state[i], x, train=train,
                                       rng=k, mask=mask)
            mask = layer.feed_forward_mask(mask, itype_chain[i])
            new_states.append(s)
        return x, new_states, mask, x

    def feed_forward(self, x, train=False):
        """All layer activations (MultiLayerNetwork.feedForward)."""
        x = jnp.asarray(x)
        acts = [x]
        mask = None
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i](x)
            x, _ = layer.apply(self.params[i], self.state[i], x, train=train, mask=mask)
            acts.append(x)
        return acts

    # ---------------------------------------------------------------- output
    def output(self, x, train: bool = False, mask=None):
        """Inference forward pass, jitted once per input shape. ``mask``:
        optional [B, T] padding mask threaded to the layers (attention /
        RNN padding — r4, so masked-LM/padded-batch EVAL attends exactly
        like training does)."""
        x = jnp.asarray(x)
        fn = self._jit_cache.get("output")
        if fn is None:
            @jax.jit
            def fn(params, state, x, mask=None):
                cp = _tree_cast(params, self._policy.compute_dtype)
                cx = x if not jnp.issubdtype(x.dtype, jnp.floating) else x.astype(
                    self._policy.compute_dtype)
                preout, _, _, _ = self._forward(cp, state, cx, False, None,
                                                mask)
                out_layer = self.layers[-1]
                if hasattr(out_layer, "preout"):
                    from deeplearning4j_tpu.nn.layers.base import resolve_activation

                    return resolve_activation(out_layer.activation)(preout).astype(
                        self._policy.output_dtype)
                return preout.astype(self._policy.output_dtype)

            self._jit_cache["output"] = fn
        return fn(self.params, self.state, x,
                  None if mask is None else jnp.asarray(mask))

    # ------------------------------------------------------------------- fit
    def _loss_terms(self, params, state, x, y, rng, mask, carries=None,
                    label_mask=None, train=True, denom=None):
        """Loss + aux from one forward. With ``carries`` (tBPTT) the RNN
        layers start from explicit carried state; returns
        (loss, new_states, new_carries-or-None). ``label_mask``: a loss
        mask DISTINCT from the forward mask (masked LM, r4) — the forward
        sees ``mask`` (padding) while the loss covers ``label_mask``.
        ``denom`` (r5): overrides the masked-sum normalizer (local valid
        count) — the data-parallel trainers pass global_valid/dp so that a
        mean over replicas reproduces the GLOBAL-batch loss exactly even
        when padding is distributed unevenly across shards."""
        out_layer = self.layers[-1]
        # an output layer that scores its input itself (every pass's exit of a
        # looped stack: its pre-outputs cannot all live at once); the pre-output
        # ``_forward`` traces is then read by nothing and XLA drops it
        from_features = hasattr(out_layer, "score_from_features")
        if carries is None:
            preout, new_states, out_mask, features = self._forward(
                params, state, x, train, rng, mask)
            new_carries = None
        else:
            preout, new_states, out_mask, features, new_carries = (
                self._forward_carry(params, state, x, carries, True, rng, mask))
        # per-position weights of the score a layer drew in this step (block
        # diffusion's noising layer): for an output layer that scores its input
        weights = take_loss_weights(new_states)
        if weights is not None and not from_features:
            raise ValueError(f"a layer hands the score per-position weights, which "
                             f"{type(out_layer).__name__} does not take")
        with jax.named_scope("loss"):
            if label_mask is not None:
                out_mask = label_mask
            if from_features:
                per, new_states[-1] = out_layer.score_from_features(
                    params[-1], state[-1], y, features, out_mask,
                    **({} if weights is None else {"weights": weights}))
            else:
                per = out_layer.score_from_preout(y, preout, out_mask)
            if isinstance(out_layer, CenterLossOutputLayer):
                # a per-example loss mask must cover the center term and the
                # persisted center update too (r5)
                cmask = None
                if (out_mask is not None
                        and int(np.prod(out_mask.shape)) == preout.shape[0]):
                    cmask = out_mask.reshape(preout.shape[0])
                cscore, cstate = out_layer.center_score_and_state(
                    params[-1], state[-1], features, y, mask=cmask)
                per = per + cscore
                new_states[-1] = cstate
            if out_mask is not None and per.ndim == 1:
                # masked per-sample sums normalized by valid count — a 1-D [B]
                # per-example mask normalizes exactly like [B, 1]/[B, T] (r5;
                # matches ComputationGraph._loss)
                d = denom if denom is not None else jnp.maximum(out_mask.sum(),
                                                                1.0)
                loss = per.sum() / d
            else:
                loss = per.mean()
            reg = sum(l.regularization(p) for l, p in zip(self.layers, params))
            score = loss + reg
            for term in layer_loss_terms(new_states):
                score = score + term.astype(score.dtype)
        return score, new_states, new_carries

    def _step_loss(self, params, state, x, y, key, mask, label_mask):
        cp = _tree_cast(params, self._policy.compute_dtype)
        cx = x if not jnp.issubdtype(x.dtype, jnp.floating) else x.astype(
            self._policy.compute_dtype)
        loss, new_states, _ = self._loss_terms(
            cp, state, cx, y, key, mask, label_mask=label_mask)
        return loss.astype(jnp.float32), new_states

    # ------------------------------------------------------------- tBPTT
    def _forward_carry(self, params, state, x, carries, train, rng, mask):
        """_forward variant threading explicit RNN carries (tBPTT /
        rnnTimeStep). carries: {layer_idx: carry_tuple}; returns
        (preout, new_states, mask, features, new_carries)."""
        new_states, new_carries = [], {}
        itype_chain = self.conf.layer_input_types
        n = len(self.layers)
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i](x)
            k = jax.random.fold_in(rng, i) if rng is not None else None
            scope = jax.named_scope(_scope_name(i, layer))
            if i == n - 1 and hasattr(layer, "preout"):
                with scope:
                    x = layer._maybe_dropout(x, train, k) if train else x
                    preout = layer.preout(params[i], x)
                new_states.append(state[i])
                return preout, new_states, mask, x, new_carries
            if i in carries and hasattr(layer, "apply_with_carry"):
                with scope:
                    x = layer._maybe_dropout(x, train, k) if train else x
                    x, new_carries[i] = layer.apply_with_carry(
                        params[i], x, carries[i], mask=mask)
                new_states.append(state[i])
            else:
                with scope:
                    x, s = layer.apply(params[i], state[i], x, train=train,
                                       rng=k, mask=mask)
                new_states.append(s)
            mask = layer.feed_forward_mask(mask, itype_chain[i])
        return x, new_states, mask, x, new_carries

    def _rnn_layer_indices(self):
        return [i for i, l in enumerate(self.layers)
                if hasattr(l, "apply_with_carry")]

    def _init_carries(self, batch: int):
        return {i: self.layers[i].initial_carry(batch)
                for i in self._rnn_layer_indices()}

    def _make_tbptt_step(self):
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(params, state, opt_state, step_i, x, y, key, mask, carries,
                 label_mask=None):
            def loss_fn(p):
                cp = _tree_cast(p, self._policy.compute_dtype)
                cx = x if not jnp.issubdtype(x.dtype, jnp.floating) else x.astype(
                    self._policy.compute_dtype)
                loss, new_states, new_carries = self._loss_terms(
                    cp, state, cx, y, key, mask, carries=carries,
                    label_mask=label_mask)
                return loss.astype(jnp.float32), (new_states, new_carries)

            (loss, (new_states, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updaters(grads, params,
                                                       opt_state, step_i)
            # gradients do NOT flow across chunk boundaries (truncated BPTT)
            new_carries = jax.lax.stop_gradient(new_carries)
            return new_params, new_states, new_opt, loss, new_carries

        return step

    def _fit_tbptt(self, x, y, mask, label_mask=None) -> float:
        L = self.conf.tbptt_fwd_length
        x, y = jnp.asarray(x), jnp.asarray(y)
        T = x.shape[1]
        step_fn = self._jit_cache.get("tbptt")
        if step_fn is None:
            step_fn = self._make_tbptt_step()
            self._jit_cache["tbptt"] = step_fn
        carries = self._init_carries(x.shape[0])
        total, n_chunks = None, 0
        # full chunks, then the trailing partial chunk (its different shape
        # compiles once and is cached like any other jit specialization)
        starts = list(range(0, (T // L) * L, L))
        if T % L:
            starts.append((T // L) * L)
        for s in starts:
            xc, yc = x[:, s:s + L], y[:, s:s + L]
            mc = None if mask is None else jnp.asarray(mask)[:, s:s + L]
            lc = (None if label_mask is None
                  else jnp.asarray(label_mask)[:, s:s + L])
            key = self._next_key()
            self.params, self.state, self.opt_state, loss, carries = step_fn(
                self.params, self.state, self.opt_state,
                jnp.asarray(self.step_count, jnp.int32), xc, yc, key, mc,
                carries, lc)
            # accumulate ON DEVICE: all chunks stay dispatched back-to-back;
            # the one host fetch per call happens at score delivery below
            total = loss if total is None else total + loss
            n_chunks += 1
        mean = total / max(n_chunks, 1)
        result = deliver_score(self, mean, get_window(self),
                               monitoring.fit_monitor())
        self.step_count += 1
        return result

    # ---------------------------------------------------- stored-state RNN
    def rnn_time_step(self, x):
        """Streaming inference with persisted RNN state
        (MultiLayerNetwork.rnnTimeStep). x [B, T, F] or [B, F] (single step).
        Output activations for the new timesteps; state persists across calls
        until rnn_clear_previous_state()."""
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        carries = getattr(self, "_rnn_carries", None)
        if carries is not None:
            _check_carry_batch(carries, x.shape[0])
        else:
            carries = self._init_carries(x.shape[0])
        fn = self._jit_cache.get("rnn_time_step")
        if fn is None:
            @jax.jit
            def fn(params, state, x, carries):
                cp = _tree_cast(params, self._policy.compute_dtype)
                preout, _, _, _, new_carries = self._forward_carry(
                    cp, state, x, carries, False, None, None)
                out_layer = self.layers[-1]
                if hasattr(out_layer, "preout"):
                    from deeplearning4j_tpu.nn.layers.base import resolve_activation

                    out = resolve_activation(out_layer.activation)(preout)
                else:
                    out = preout
                return out.astype(self._policy.output_dtype), new_carries

            self._jit_cache["rnn_time_step"] = fn
        out, new_carries = fn(self.params, self.state, x, carries)
        # layers without an entry in new_carries keep their previous carry
        merged = dict(carries)
        merged.update(new_carries)
        self._rnn_carries = merged
        # a LastTimeStep tail collapses the time axis; only squeeze 3D output
        return out[:, 0] if single and out.ndim == 3 else out

    def rnn_clear_previous_state(self):
        """MultiLayerNetwork.rnnClearPreviousState analog."""
        self._rnn_carries = None

    def rnn_get_carry_rows(self, rows):
        """Extract the stored rnn_time_step state for individual batch rows
        (int or sequence) as a carry dict with leaves [len(rows), ...].
        Raises if no state is stored yet."""
        carries = getattr(self, "_rnn_carries", None)
        if carries is None:
            raise ValueError("no stored rnn state; call rnn_time_step first")
        return extract_carry_rows(carries, rows)

    def rnn_set_carry_rows(self, rows, sub, batch: Optional[int] = None):
        """Merge per-row carries into the stored rnn_time_step state — the
        admit/evict half of the row API: a retiring sequence's rows can be
        overwritten by a newcomer's without clearing the rest of the batch.
        With no stored state, ``batch`` sizes a fresh zero carry to merge
        into. The PLAIN rnn_time_step API keeps its whole-batch rejection;
        this is the explicit opt-in."""
        carries = getattr(self, "_rnn_carries", None)
        if carries is None:
            if batch is None:
                raise ValueError(
                    "no stored rnn state; pass batch= to size a fresh carry")
            carries = self._init_carries(batch)
        self._rnn_carries = merge_carry_rows(carries, sub, rows)
        return self._rnn_carries

    def _fit_unpacked(self, x, y, mask, label_mask):
        label_mask = _single_mask(label_mask)
        if (self.conf.tbptt_fwd_length > 0 and np.ndim(x) == 3
                and np.shape(x)[1] > self.conf.tbptt_fwd_length):
            return self._fit_tbptt(x, y, mask, label_mask)
        return super()._fit_unpacked(x, y, mask, label_mask)

    def _step_inputs(self, x, y, mask, label_mask):
        x, y, mask, label_mask = self._pad_tail(x, y, mask, label_mask)
        return ((jnp.asarray(x), jnp.asarray(y)),
                (None if mask is None else jnp.asarray(mask),
                 None if label_mask is None else jnp.asarray(label_mask)))

    def _exit_state(self):
        return self.state[-1]

    # -------------------------------------------------------------- pretrain
    def pretrain(self, data, epochs: int = 1):
        """Layerwise unsupervised pretraining (MultiLayerNetwork.pretrain).

        Each layer exposing ``pretrain_loss`` (AutoEncoderLayer,
        VariationalAutoencoderLayer) is trained greedily on the activations
        of the (frozen) layers below it; supervised fit afterwards fine-tunes
        everything."""
        for i, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_loss"):
                continue
            self.pretrain_layer(i, data, epochs=epochs)
        return self

    def pretrain_layer(self, layer_index: int, data, epochs: int = 1):
        """Pretrain one layer (MultiLayerNetwork.pretrainLayer)."""
        layer = self.layers[layer_index]
        if not hasattr(layer, "pretrain_loss"):
            raise ValueError(f"layer {layer_index} has no pretrain objective")
        updater = self._updaters[layer_index]

        key = ("pretrain", layer_index)
        if key not in self._jit_cache:
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def step(lparams, opt, step_i, below_params, below_state, x, rng):
                # forward through frozen layers below
                h = x
                for j in range(layer_index):
                    if j in self.conf.preprocessors:
                        h = self.conf.preprocessors[j](h)
                    h, _ = self.layers[j].apply(below_params[j], below_state[j],
                                                h, train=False)
                if layer_index in self.conf.preprocessors:
                    h = self.conf.preprocessors[layer_index](h)

                def loss_fn(p):
                    return layer.pretrain_loss(p, h, rng)

                loss, grads = jax.value_and_grad(loss_fn)(lparams)
                upd, opt = updater.update(grads, opt, lparams, step_i)
                lparams = jax.tree_util.tree_map(lambda p, d: p - d,
                                                 lparams, upd)
                return lparams, opt, loss

            self._jit_cache[key] = step
        step_fn = self._jit_cache[key]

        lparams = self.params[layer_index]
        opt = updater.init_state(lparams)
        below_p = self.params[:layer_index]
        below_s = self.state[:layer_index]
        loss = float("nan")
        i = 0
        if hasattr(data, "shape"):  # numpy/jax array of features
            for _ in range(epochs):
                lparams, opt, loss = step_fn(
                    lparams, opt, jnp.asarray(i, jnp.int32), below_p, below_s,
                    jnp.asarray(data), self._next_key())
                i += 1
        else:  # DataSet iterator / list of batches
            for _ in range(epochs):
                for ds in data:
                    x = ds if hasattr(ds, "shape") else _unpack(ds)[0]
                    lparams, opt, loss = step_fn(
                        lparams, opt, jnp.asarray(i, jnp.int32), below_p,
                        below_s, jnp.asarray(x), self._next_key())
                    i += 1
                if hasattr(data, "reset"):
                    data.reset()
        self.params[layer_index] = lparams
        return float(loss)

    def as_loss_fn(self, train: bool = False):
        """(loss_fn(params, state, rng, x, y, mask=None, label_mask=None)
        -> (loss, new_state), (initial params, initial state)) — the
        functional surface the parallel trainers consume
        (ParameterAveragingTrainer / EncodedGradientTrainer take a loss
        over plain TREES, not a model object).

        r4: network state (BN running stats) and the dropout rng are
        THREADED through the surface instead of frozen at export time, so
        the functional trainers can train BN/dropout models — the
        reference's ParameterAveragingTrainingMaster averages any model,
        running stats included. l1/l2 regularization terms are included,
        matching the fit path. train=True runs train-mode forward (batch
        statistics in BN, dropout when ``rng`` is not None); rng=None
        disables dropout.

        r5: optional trailing (mask, label_mask) — the fit path's mask
        routing on the functional surface: the forward sees ``mask``
        (padding), the loss covers ``label_mask`` (or ``mask`` when no
        distinct labels mask), normalized by the valid-step count. This is
        _loss_terms itself, so padded-sequence models train identically
        here and under fit_batch."""

        def loss_fn(params, state, rng, x, y, mask=None, label_mask=None,
                    denom=None):
            loss, new_states, _ = self._loss_terms(
                params, state, x, y, rng, mask, label_mask=label_mask,
                train=train, denom=denom)
            return loss, new_states

        return loss_fn, (self.params, self.state)

    # ----------------------------------------------------------------- score
    def _tail_padding_ok(self) -> bool:
        ok = getattr(self, "_pad_ok", None)
        if ok is None:
            ok = self._pad_ok = supports_tail_padding(self.layers)
        return ok

    def score(self, ds=None) -> float:
        """Loss on a dataset without updating (MultiLayerNetwork.score(DataSet))."""
        if ds is None:
            return self.score_value
        x, y, mask, label_mask = _unpack(ds)
        label_mask = _single_mask(label_mask)
        fn = self._jit_cache.get("score")
        if fn is None:
            @jax.jit
            def fn(params, state, x, y, mask, label_mask=None):
                # the SAME loss (mask normalization, center term,
                # regularization) the fit path reports, minus the update —
                # score and fit must not disagree on masked batches (r5)
                loss, _, _ = self._loss_terms(
                    params, state, x, y, None, mask,
                    label_mask=label_mask, train=False)
                return loss

            self._jit_cache["score"] = fn
        return float(fn(self.params, self.state, jnp.asarray(x), jnp.asarray(y),
                        None if mask is None else jnp.asarray(mask),
                        None if label_mask is None else jnp.asarray(label_mask)))

    # ------------------------------------------------------------------ eval
    def evaluate(self, iterator, evaluation=None) -> Evaluation:
        ev = evaluation or Evaluation()
        for ds in iterator:
            x, y, mask, label_mask = _unpack(ds)
            label_mask = _single_mask(label_mask)
            out = self.output(x, mask=mask)   # forward sees the padding mask
            ev.eval(np.asarray(y), np.asarray(out),
                    mask=label_mask if label_mask is not None else mask)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.util.serialization import restore_multi_layer_network

        return restore_multi_layer_network(path, load_updater=load_updater)


def _single_mask(lm):
    """MultiLayerNetwork has ONE output: a per-output list/dict labels mask
    (the r5 MultiDataSet/ComputationGraph shape) must fail loud here rather
    than be jnp.asarray-stacked into a bogus [n, B, T] loss mask."""
    if isinstance(lm, (list, tuple, dict)):
        raise ValueError(
            "per-output labels masks (list/dict) are a ComputationGraph/"
            "MultiDataSet shape; MultiLayerNetwork takes a single labels "
            "mask array")
    return lm
