"""ComputationGraph — the DAG model class.

Reference analog: org.deeplearning4j.nn.graph.ComputationGraph — topological
forward/backward over GraphVertex[], multiple inputs/outputs, MergeVertex /
ElementWiseVertex residual topologies (the ResNet-50 shape).

TPU-first: topological order is computed once at config-resolve; the whole
DAG traces into a single jitted XLA program per step, multi-output losses
summed. Params/state/opt-state are name-keyed dicts over vertices.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.nn.conf.builders import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.graph import LayerVertex
from deeplearning4j_tpu.nn.layers.base import checkpoint_layer, layer_loss_terms
from deeplearning4j_tpu.nn.multilayer import _check_carry_batch, _tree_cast
from deeplearning4j_tpu.nn.network import Network, _unpack, recorded_init
from deeplearning4j_tpu.optimize.updaters import NoOp, get_updater


def _scope_name(name: str, vertex) -> str:
    """``<vertex>.<LayerClass>`` (the vertex's own class where it holds no
    layer): a trace's reader tells layer kinds apart without a table."""
    kind = vertex.layer if isinstance(vertex, LayerVertex) else vertex
    return f"{name}.{type(kind).__name__}"


class ComputationGraph(Network):
    def __init__(self, conf: ComputationGraphConfiguration):
        if not conf.topological_order:
            conf.resolve()
        super().__init__(conf)
        self.params: dict = {}
        self.state: dict = {}
        self.opt_state: dict = {}
        self._updaters = {}
        for name, v in conf.vertices.items():
            if isinstance(v, LayerVertex):
                l = v.layer
                # frozen wins over any per-layer updater override
                self._updaters[name] = (NoOp() if not l.trainable
                                        else (get_updater(l.updater)
                                              if l.updater is not None
                                              else conf.updater))
            else:
                self._updaters[name] = conf.updater

    # ------------------------------------------------------------------ init
    @recorded_init
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        seed = self.conf.seed if seed is None else seed
        key = jax.random.key(seed)
        self._rng_key = jax.random.fold_in(key, 0xD14)
        self.params, self.state = {}, {}
        for i, name in enumerate(self.conf.topological_order):
            v = self.conf.vertices[name]
            in_types = self._vertex_input_types(name)
            p, s = v.init(jax.random.fold_in(key, i), in_types)
            if p:
                self.params[name] = p
            if s:
                self.state[name] = s
        self.opt_state = {n: self._updaters[n].init_state(p) for n, p in self.params.items()}
        return self

    def _vertex_input_types(self, name):
        types = self.conf.vertex_output_types
        ins = []
        for dep in self.conf.vertex_inputs.get(name, []):
            t = types[dep]
            if name in self.conf.preprocessors:
                t = self.conf.preprocessors[name].output_type(t)
            ins.append(t)
        return ins

    @property
    def _output_vertices(self):
        return self.conf.network_outputs

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, inputs: dict, train, rng, masks=None,
                 want_preout=False):
        """Walk topological order. Returns (dict name->activation, new_state,
        dict of output preouts if want_preout, dict of the (preprocessed)
        features fed to each output vertex)."""
        acts = dict(inputs)
        new_state = {}
        preouts = {}
        out_feats = {}
        for i, name in enumerate(self.conf.topological_order):
            v = self.conf.vertices[name]
            ins = [acts[d] for d in self.conf.vertex_inputs.get(name, [])]
            if name in self.conf.preprocessors:
                ins = [self.conf.preprocessors[name](ins[0])]
            k = jax.random.fold_in(rng, i) if rng is not None else None
            p = params.get(name, {})
            s = state.get(name, {})
            # <vertex>.<LayerClass>: JAX writes jvp(<scope>) on the forward and
            # transpose(jvp(<scope>)) on the backward operations' op_name
            scope = jax.named_scope(_scope_name(name, v))
            if want_preout and name in self._output_vertices and isinstance(v, LayerVertex) \
                    and hasattr(v.layer, "preout"):
                out_feats[name] = ins[0]
                with scope:
                    preouts[name] = v.layer.preout(p, ins[0])
                acts[name] = preouts[name]
                if s:
                    new_state[name] = s
                continue
            with scope:
                if self.conf.remat and train:
                    out, s2 = checkpoint_layer(
                        lambda pp, ss, ii, kk, _v=v: _v.apply(
                            pp, ss, ii, train=True, rng=kk, masks=masks)
                    )(p, s, ins, k)
                else:
                    out, s2 = v.apply(p, s, ins, train=train, rng=k, masks=masks)
            acts[name] = out
            if s2:
                new_state[name] = s2
        return acts, new_state, preouts, out_feats

    def _as_input_dict(self, xs):
        names = self.conf.network_inputs
        if isinstance(xs, dict):
            return {k: jnp.asarray(v) for k, v in xs.items()}
        if not isinstance(xs, (list, tuple)):
            xs = [xs]
        return {n: jnp.asarray(x) for n, x in zip(names, xs)}

    def _cast_in(self, params, inputs):
        """Mixed-precision cast shared by the train/score traces."""
        cp = _tree_cast(params, self._policy.compute_dtype)
        ci = {k: (v.astype(self._policy.compute_dtype)
                  if jnp.issubdtype(v.dtype, jnp.floating) else v)
              for k, v in inputs.items()}
        return cp, ci

    # ---------------------------------------------------------------- output
    def output(self, *xs, mask=None):
        """Inference forward. ``mask``: optional [B, T] features/padding
        mask threaded to every vertex (attention/RNNs must see padding at
        inference exactly as in training)."""
        inputs = self._as_input_dict(xs[0] if len(xs) == 1 else list(xs))
        fn = self._jit_cache.get("output")
        if fn is None:
            @jax.jit
            def fn(params, state, inputs, masks=None):
                cp = _tree_cast(params, self._policy.compute_dtype)
                acts, _, _, _ = self._forward(cp, state, inputs, False, None,
                                              masks=masks)
                outs = [acts[n].astype(self._policy.output_dtype)
                        for n in self.conf.network_outputs]
                return outs

            self._jit_cache["output"] = fn
        outs = fn(self.params, self.state, inputs,
                  None if mask is None else [jnp.asarray(mask)])
        return outs[0] if len(outs) == 1 else outs

    # --------------------------------------------------------- rnnTimeStep
    def _rnn_vertices(self):
        return [name for name, v in self.conf.vertices.items()
                if isinstance(v, LayerVertex)
                and hasattr(v.layer, "apply_with_carry")]

    def _init_carries(self, batch: int):
        return {name: self.conf.vertices[name].layer.initial_carry(batch)
                for name in self._rnn_vertices()}

    def _forward_carries(self, params, state, inputs, carries):
        """Topological forward threading explicit RNN carries (the
        ComputationGraph.rnnTimeStep walk)."""
        acts = dict(inputs)
        new_carries = {}
        for name in self.conf.topological_order:
            v = self.conf.vertices[name]
            ins = [acts[d] for d in self.conf.vertex_inputs.get(name, [])]
            if name in self.conf.preprocessors:
                ins = [self.conf.preprocessors[name](ins[0])]
            p = params.get(name, {})
            if name in carries:
                out, c = v.layer.apply_with_carry(p, ins[0], carries[name])
                acts[name] = out
                new_carries[name] = c
            else:
                out, _ = v.apply(p, state.get(name, {}), ins, train=False)
                acts[name] = out
        return [acts[n] for n in self.conf.network_outputs], new_carries

    def rnn_time_step(self, *xs):
        """Streaming inference with persisted RNN state
        (ComputationGraph.rnnTimeStep). Inputs [B, T, F] or [B, F] (single
        step); state persists across calls until rnn_clear_previous_state()."""
        inputs = self._as_input_dict(xs[0] if len(xs) == 1 else list(xs))
        single = all(v.ndim == 2 for v in inputs.values())
        if single:
            inputs = {k: v[:, None, :] for k, v in inputs.items()}
        batch = next(iter(inputs.values())).shape[0]
        carries = getattr(self, "_rnn_carries", None)
        if carries is not None:
            _check_carry_batch(carries, batch)
        else:
            carries = self._init_carries(batch)
        fn = self._jit_cache.get("rnn_time_step")
        if fn is None:
            @jax.jit
            def fn(params, state, inputs, carries):
                cp = _tree_cast(params, self._policy.compute_dtype)
                outs, new_carries = self._forward_carries(cp, state, inputs,
                                                          carries)
                outs = [o.astype(self._policy.output_dtype) for o in outs]
                return outs, new_carries

            self._jit_cache["rnn_time_step"] = fn
        outs, new_carries = fn(self.params, self.state, inputs, carries)
        # _forward_carries visits every rnn vertex, so new_carries is complete
        self._rnn_carries = new_carries
        if single:
            # a LastTimeStep/feed-forward path may have collapsed the time
            # axis already; only squeeze genuinely 3D outputs
            outs = [o[:, 0] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        """ComputationGraph.rnnClearPreviousState analog."""
        self._rnn_carries = None

    def as_loss_fn(self, train: bool = False):
        """(loss_fn(params, state, rng, x, y, mask=None, label_mask=None)
        -> (loss, new_state), (initial params, initial state)) — the
        functional surface the parallel trainers consume (the
        ComputationGraph counterpart of MultiLayerNetwork.as_loss_fn).

        x: one array for single-input graphs or a {input_name: array}
        dict; y likewise for the graph's outputs. r4: network state (BN
        running stats) and the dropout rng are threaded through instead
        of frozen at export time, and l1/l2 regularization terms are
        included — matching the fit path. r5: routes through _loss itself,
        so the fit path's mask semantics (forward sees ``mask``, each
        output's loss covers ``label_mask``, valid-count normalization)
        hold on the functional surface too."""
        conf = self.conf

        def loss_fn(params, state, rng, x, y, mask=None, label_mask=None,
                    denom=None):
            inputs = self._as_input_dict(x)
            labels = y if isinstance(y, dict) else \
                {conf.network_outputs[0]: y}
            masks = None if mask is None else [mask]
            # trace-safe: no host-side mask-equality fast path here — the
            # caller passes label_mask only when it is genuinely distinct
            lms = (None if label_mask is None
                   else {n: label_mask for n in conf.network_outputs})
            loss, new_state = self._loss(params, state, inputs, labels,
                                         rng, masks, labels_masks=lms,
                                         train=train, denom=denom)
            # vertices with no state entry keep their old (empty) state so
            # the returned tree matches the input's structure
            merged = {k: new_state.get(k, s) for k, s in state.items()}
            return loss, merged

        return loss_fn, (self.params, self.state)

    # ------------------------------------------------------------------- fit
    def _loss(self, params, state, inputs, labels: dict, rng, masks,
              labels_masks=None, train=True, denom=None):
        """``masks``: the FORWARD (features/padding) mask list the vertices
        consume. ``labels_masks``: optional dict {output_name: [B, T] mask}
        of loss masks DISTINCT from the forward mask — the masked-LM shape
        (r5), mirroring MultiLayerNetwork._loss_terms' label_mask routing:
        attention/RNNs see the padding mask while each output's loss covers
        only its labels mask (DL4J ComputationGraph featuresMask/labelsMask
        semantics)."""
        acts, new_state, preouts, out_feats = self._forward(
            params, state, inputs, train, rng, masks=masks, want_preout=True)
        with jax.named_scope("loss"):
            loss = self._loss_terms(params, state, acts, new_state, preouts,
                                    out_feats, labels, masks, labels_masks,
                                    denom)
        return loss, new_state

    def _loss_terms(self, params, state, acts, new_state, preouts, out_feats,
                    labels, masks, labels_masks, denom):
        """The outputs' losses plus the regularization terms, from one
        forward's activations. A center-loss head writes its persisted
        centers into ``new_state``."""
        from deeplearning4j_tpu.nn.layers.output import CenterLossOutputLayer

        # the shared [B, T] sequence mask (the same list contract the
        # vertices consume) is the default loss mask; a per-output entry in
        # labels_masks overrides it. Losses apply it exactly like
        # MultiLayerNetwork._loss_terms — masked per-sample sums
        # normalized by that output's valid-step count
        shared_mask = masks[0] if masks else None
        loss = 0.0
        for name in self.conf.network_outputs:
            v = self.conf.vertices[name]
            explicit = (labels_masks is not None
                        and labels_masks.get(name) is not None)
            out_mask = labels_masks[name] if explicit else shared_mask
            ref = preouts[name] if name in preouts else acts[name]
            if explicit:
                # validate/canonicalize the explicit mask ONCE, before
                # branching on output kind: a 3D sequence head takes a
                # [B, T] mask; every other rank (collapsed 2D heads, 4D
                # conv heads) takes a per-example [B]/[B, 1] mask,
                # canonicalized to [B]. Anything else fails loud here
                # rather than as an opaque broadcast error inside the loss.
                B = ref.shape[0]
                if ref.ndim == 3:
                    if out_mask.shape != (B, ref.shape[1]):
                        raise ValueError(
                            f"labels mask for output '{name}' has shape "
                            f"{tuple(out_mask.shape)}; expected "
                            f"({B}, {ref.shape[1]}) for output shape "
                            f"{tuple(ref.shape)}")
                else:
                    if int(np.prod(out_mask.shape)) != B:
                        raise ValueError(
                            f"labels mask for output '{name}' has shape "
                            f"{tuple(out_mask.shape)}, not per-example for "
                            f"output shape {tuple(ref.shape)}")
                    out_mask = out_mask.reshape(B)
            elif (out_mask is not None and ref.ndim == 2
                    and out_mask.ndim == 2 and out_mask.shape[1] != 1):
                # time axis collapsed upstream (LastTimeStep): the shared
                # [B, T] forward mask no longer applies to the per-example
                # output head — drop it, as MLN does via feed_forward_mask
                out_mask = None
            per_example = explicit and ref.ndim != 3
            if name in preouts and hasattr(v.layer, "score_from_preout"):
                per = v.layer.score_from_preout(
                    labels[name], ref, None if per_example else out_mask)
                if per_example:
                    # canonical [B] weights apply AFTER the head's own
                    # reduction, uniformly across head ranks
                    per = per * out_mask
                if isinstance(v.layer, CenterLossOutputLayer):
                    # any per-example-compatible mask (explicit OR a shared
                    # [B, 1] features mask) covers the center term and the
                    # persisted center update — mirrors MLN._loss_terms
                    cmask = None
                    if (out_mask is not None
                            and int(np.prod(out_mask.shape)) == ref.shape[0]):
                        cmask = out_mask.reshape(ref.shape[0])
                    cscore, cstate = v.layer.center_score_and_state(
                        params.get(name, {}), state.get(name, {}),
                        out_feats[name], labels[name], mask=cmask)
                    per = per + cscore
                    new_state[name] = cstate
                if out_mask is not None and per.ndim == 1:
                    # masked per-sample sums normalized by valid count —
                    # for a [B, T] sequence mask AND a per-example [B]/[B,1]
                    # mask alike (the two must not normalize differently).
                    # ``denom`` (r5): trainer-supplied global_valid/dp
                    # override, see MultiLayerNetwork._loss_terms
                    d = (denom if denom is not None
                         else jnp.maximum(out_mask.sum(), 1.0))
                    loss = loss + per.sum() / d
                else:
                    loss = loss + per.mean()
            else:
                d = acts[name] - labels[name]
                if out_mask is not None and d.ndim == 3:
                    # [B, T] mask (shared or explicit — explicit is
                    # validated to this shape) over a sequence output
                    w = out_mask[..., None]
                    nv = w.sum() if denom is None else denom
                    loss = loss + ((d * d) * w).sum() / jnp.maximum(
                        nv * float(d.shape[-1]), 1.0)
                elif explicit:
                    # canonical [B] per-example mask, any other rank
                    w = out_mask.reshape(d.shape[0], *([1] * (d.ndim - 1)))
                    nv = w.sum() if denom is None else denom
                    loss = loss + ((d * d) * w).sum() / jnp.maximum(
                        nv * float(np.prod(d.shape[1:])), 1.0)
                else:
                    loss = loss + (d * d).mean()
        for name, v in self.conf.vertices.items():
            if isinstance(v, LayerVertex) and name in params:
                loss = loss + v.layer.regularization(params[name])
        for term in layer_loss_terms(new_state):
            loss = loss + term.astype(jnp.float32)
        return loss

    def _step_loss(self, params, state, inputs, labels, key, masks,
                   labels_masks):
        cp, ci = self._cast_in(params, inputs)
        loss, new_state = self._loss(cp, state, ci, labels, key, masks,
                                     labels_masks=labels_masks)
        # carry forward unchanged state entries
        for k, v in state.items():
            new_state.setdefault(k, v)
        return loss.astype(jnp.float32), new_state

    def _as_label_dict(self, y):
        if isinstance(y, dict):
            return {k: jnp.asarray(v) for k, v in y.items()}
        ys = y if isinstance(y, (list, tuple)) else [y]
        return {n: jnp.asarray(v)
                for n, v in zip(self.conf.network_outputs, ys)}

    def _labels_masks_for(self, mask, label_mask):
        """Normalize a DataSet/MultiDataSet labels mask to the per-output
        dict `_loss` consumes, or None when it adds nothing beyond the
        shared forward mask (the ordinary RNN case — keeps the r1-r4
        single-mask trace). Accepts a single [B, T] array (applied to
        every output), or a per-output list/dict."""
        if label_mask is None:
            return None
        outs = self.conf.network_outputs
        if isinstance(label_mask, dict):
            unknown = set(label_mask) - set(outs)
            if unknown:
                raise ValueError(
                    f"labels_mask keys {sorted(unknown)} are not network "
                    f"outputs {list(outs)}")
            d = {k: jnp.asarray(v) for k, v in label_mask.items()
                 if v is not None}
        elif isinstance(label_mask, (list, tuple)):
            if len(label_mask) != len(outs):
                raise ValueError(
                    f"labels_mask list has {len(label_mask)} entries for "
                    f"{len(outs)} network outputs {list(outs)}")
            d = {n: jnp.asarray(v) for n, v in zip(outs, label_mask)
                 if v is not None}
        else:
            if label_mask is mask or (
                    mask is not None
                    and np.shape(mask) == np.shape(label_mask)
                    and np.array_equal(np.asarray(mask),
                                       np.asarray(label_mask))):
                # identical to the forward mask: the shared path already
                # covers it
                return None
            d = {n: jnp.asarray(label_mask) for n in outs}
        return d or None

    def _tail_padding_ok(self) -> bool:
        """Tail padding is loss-exact for a DAG iff no vertex computes
        cross-example batch statistics and every network output is a
        standard per-example-loss head (mirrors multilayer's
        supports_tail_padding over the vertex set)."""
        ok = getattr(self, "_pad_ok", None)
        if ok is None:
            from deeplearning4j_tpu.nn.layers.norm import BatchNormalizationLayer
            from deeplearning4j_tpu.nn.layers.output import LossLayer, OutputLayer

            ok = all(not (isinstance(v, LayerVertex)
                          and isinstance(v.layer, BatchNormalizationLayer)
                          and not v.layer.use_mean_var_from_state)
                     for v in self.conf.vertices.values())
            if ok:
                for name in self.conf.network_outputs:
                    v = self.conf.vertices[name]
                    if not (isinstance(v, LayerVertex)
                            and isinstance(v.layer, (OutputLayer, LossLayer))):
                        ok = False
                        break
            self._pad_ok = ok
        return ok

    def _step_inputs(self, x, y, mask, label_mask):
        if not isinstance(y, (list, tuple, dict)):
            # multi-input x pads per entry, but a per-output labels LIST/DICT
            # keeps its raw shape (a loss mask cannot be synthesized for it
            # shape-safely)
            x, y, mask, label_mask = self._pad_tail(x, y, mask, label_mask)
        # vertices consume masks as a LIST (one shared [B, T] sequence
        # mask threaded to every vertex; LayerVertex reads masks[0]) — a
        # bare array would hit `if masks` truthiness inside the trace
        return ((self._as_input_dict(x), self._as_label_dict(y)),
                (None if mask is None else [jnp.asarray(mask)],
                 self._labels_masks_for(mask, label_mask)))

    def _exit_state(self):
        return self.state.get(self.conf.network_outputs[0])

    # ------------------------------------------------------------------ eval
    def evaluate(self, iterator, evaluation=None) -> Evaluation:
        ev = evaluation or Evaluation()
        for ds in iterator:
            x, y, mask, label_mask = _unpack(ds)
            out = self.output(x, mask=mask)  # forward sees the padding mask
            if isinstance(out, list):
                out = out[0]
                y = y[0] if isinstance(y, (list, tuple)) else y
            # only the FIRST output is evaluated; validate the per-output
            # list/dict exactly like fit_batch, then pick that output's mask
            lms = self._labels_masks_for(mask, label_mask)
            lm = None if lms is None else lms.get(self.conf.network_outputs[0])
            ev.eval(np.asarray(y), np.asarray(out),
                    mask=lm if lm is not None else mask)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    def score(self, ds=None) -> float:
        """Loss on a batch without updating (ComputationGraph.score(DataSet));
        with no argument, the last fit score. Routes masks exactly like
        fit_batch: forward sees the features mask, each output's loss its
        labels mask."""
        if ds is None:
            return self.score_value
        x, y, mask, label_mask = _unpack(ds)
        inputs = self._as_input_dict(x)
        labels = self._as_label_dict(y)
        labels_masks = self._labels_masks_for(mask, label_mask)
        fn = self._jit_cache.get("score")
        if fn is None:
            @jax.jit
            def fn(params, state, inputs, labels, masks, labels_masks=None):
                cp, ci = self._cast_in(params, inputs)
                loss, _ = self._loss(cp, state, ci, labels, None, masks,
                                     labels_masks=labels_masks, train=False)
                return loss.astype(jnp.float32)

            self._jit_cache["score"] = fn
        return float(fn(self.params, self.state, inputs, labels,
                        None if mask is None else [jnp.asarray(mask)],
                        labels_masks))

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "ComputationGraph":
        from deeplearning4j_tpu.util.serialization import restore_computation_graph

        return restore_computation_graph(path, load_updater=load_updater)
