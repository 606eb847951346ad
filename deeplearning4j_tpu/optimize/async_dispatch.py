"""Async training dispatch: lazy scores, bounded in-flight windows, tail
padding.

Reference analog: DL4J's AsyncDataSetIterator/workspace-prefetch tier kept
the GPU fed on the *input* side, but its fit loop still synchronized on every
iteration's score. Here the other half: JAX dispatches a jitted train step
asynchronously and returns device arrays immediately — the ONLY thing that
blocks the host is fetching a scalar (``float(loss)``). The per-step
``float(loss)`` in ``fit_batch`` therefore forfeits async dispatch: the
accelerator drains its queue while Python runs listeners and pulls the next
batch. This is the dispatch-gap problem PyGraph (arxiv 2503.19779) attacks
with CUDA Graphs — keep the device queue full, never block the host on a
scalar you don't need yet.

Four pieces:

- **run_step / deliver_score** — the one place a dispatched step's score
  reaches the host and the listeners (sync or async, monitored or not);
  ``nn/network.py``'s ``fit_batch`` hands it the jitted call.
- **ScoreHandle / AsyncScoreWindow** — ``fit_batch`` keeps the loss on
  device and returns a lazy handle; a bounded window of in-flight steps
  (``DL4J_TPU_ASYNC_STEPS``, default 2, ``=0`` restores sync behavior)
  drains oldest-first when it fills, at epoch end, or when someone actually
  reads a score. Listener callbacks are deferred to drain time with the
  ORIGINAL (iteration, epoch, score) attribution; listeners that act on
  model state per iteration declare ``needs_eager_score = True`` and force
  the eager (sync) path.
- **pad_tail_batch** — partial tail batches are padded up to the smallest
  ``pow2_bucket`` of the largest batch seen, with label-mask zeroing so the
  loss and gradients are those of the unpadded batch; epoch tails then stop
  compiling one XLA program per ragged shape.
- **_fetch_scalar** — the single chokepoint through which every host←device
  score fetch in the fit path flows, so tests can spy on it and assert the
  hot path introduces no new host syncs.
"""

from __future__ import annotations

import collections
from typing import Optional

import numpy as np

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.monitoring import context as trace_context


def _fetch_scalar(arr) -> float:
    """The host←device sync. Every score fetch on the fit path funnels
    through here (spy point for the zero-new-host-syncs guard)."""
    return float(arr)


class AsyncStepError(RuntimeError):
    """An in-flight train step failed; raised at drain time with the step
    it belongs to (not the step the host had reached when it surfaced).
    ``trace_id`` names the request trace that DISPATCHED the step (ambient
    :func:`monitoring.context.bind` at submit time), so a deferred failure
    is still attributable to the window that caused it. Guarded steps
    (deeplearning4j_tpu.guardrails) additionally carry ``sentinel`` — the
    tripping step's [ok, gnorm, loss, z] health word."""

    def __init__(self, step: int, epoch: int, cause: BaseException,
                 trace_id: Optional[str] = None, sentinel=None):
        sentinel = (None if sentinel is None
                    else [float(v) for v in sentinel])
        msg = f"async train step {step} (epoch {epoch}) failed: {cause}"
        if sentinel is not None:
            msg += f" [sentinel {[round(v, 4) for v in sentinel]}]"
        if trace_id:
            msg += f" [trace {trace_id}]"
        super().__init__(msg)
        self.step = step
        self.epoch = epoch
        self.trace_id = trace_id
        self.sentinel = sentinel
        self.__cause__ = cause


class ScoreHandle:
    """Lazy score of one dispatched train step.

    Holds nothing device-side itself — the window owns the in-flight loss
    array until drain. Any numeric use (``float()``, comparison, numpy
    coercion, formatting) forces a drain through this step, so code written
    against the old eager ``fit_batch -> float`` contract keeps working and
    simply opts back into the sync point it was already paying for.
    """

    __slots__ = ("_window", "step", "epoch", "trace_id", "_value", "_error")

    def __init__(self, window: "AsyncScoreWindow", step: int, epoch: int):
        self._window = window
        self.step = step
        self.epoch = epoch
        # the ambient request trace at DISPATCH time (None untraced) —
        # stamped now so a deferred drain error still names its origin
        self.trace_id = trace_context.current_trace_id()
        self._value: Optional[float] = None
        self._error: Optional[AsyncStepError] = None

    def ready(self) -> bool:
        return self._value is not None or self._error is not None

    def value(self) -> float:
        if not self.ready():
            self._window.drain_through(self)
        if self._error is not None:
            raise self._error
        return self._value

    # ---- float-like surface (the old contract was `fit_batch -> float`)
    def __float__(self):
        return float(self.value())

    def __int__(self):
        return int(self.value())

    def __bool__(self):
        return bool(self.value())

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value(), dtype=dtype)

    def __format__(self, spec):
        return format(self.value(), spec)

    def __repr__(self):
        if self._error is not None:
            return f"ScoreHandle(step={self.step}, error={self._error!r})"
        if self._value is None:
            return f"ScoreHandle(step={self.step}, in-flight)"
        return f"ScoreHandle(step={self.step}, {self._value!r})"

    def __eq__(self, other):
        return self.value() == other

    def __ne__(self, other):
        return self.value() != other

    def __lt__(self, other):
        return self.value() < other

    def __le__(self, other):
        return self.value() <= other

    def __gt__(self, other):
        return self.value() > other

    def __ge__(self, other):
        return self.value() >= other

    def __hash__(self):
        return hash(self.value())

    def __add__(self, other):
        return self.value() + other

    __radd__ = __add__

    def __sub__(self, other):
        return self.value() - other

    def __rsub__(self, other):
        return other - self.value()

    def __mul__(self, other):
        return self.value() * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self.value() / other

    def __rtruediv__(self, other):
        return other / self.value()

    def __neg__(self):
        return -self.value()

    def __abs__(self):
        return abs(self.value())

    def __round__(self, n=None):
        return round(self.value(), n)


class AsyncScoreWindow:
    """Bounded window of in-flight (step, loss, deferred-listeners) entries.

    ``submit`` appends and drains oldest-first once more than
    ``max_in_flight`` steps are outstanding — the host stays at most that
    many steps ahead of the device, so loss arrays (and the programs that
    produce them) can't pile up unboundedly. Drain order is FIFO: deferred
    listeners observe every (iteration, epoch, score) triple exactly once,
    in step order, identical to the sync trace.
    """

    def __init__(self, model, max_in_flight: int):
        self.model = model
        self.max_in_flight = max(1, int(max_in_flight))
        self._pending: collections.deque = collections.deque()

    def __len__(self) -> int:
        return len(self._pending)

    def submit(self, loss, word=None, guard=None) -> ScoreHandle:
        """Register one dispatched step's on-device loss; returns its lazy
        handle. Called with the model's PRE-increment step/epoch counters.
        Guarded steps (deeplearning4j_tpu.guardrails) also carry their
        on-device sentinel ``word`` and the ``guard`` that screens it at
        drain — the word's loss lane replaces the bare loss fetch, so the
        screen costs no extra host sync."""
        m = self.model
        handle = ScoreHandle(self, m.step_count, m.epoch_count)
        # snapshot: set_listeners() between dispatch and drain must not
        # retroactively change who observes this iteration
        self._pending.append((handle, loss, tuple(m.listeners), word, guard))
        while len(self._pending) > self.max_in_flight:
            self._drain_one()
        return handle

    def take_pending(self):
        """Remove and return every in-flight entry (guardrails rollback:
        a checkpoint restore erases the device-side effects of in-flight
        steps, so the guard re-resolves their handles host-side from the
        replayed window and re-queues them for FIFO delivery)."""
        out = list(self._pending)
        self._pending.clear()
        return out

    def requeue(self, handle, listeners, word, guard) -> None:
        """Re-queue a taken entry with a host-side resolution in place of
        its (now stale) device arrays; delivered by the normal FIFO drain."""
        self._pending.append((handle, None, listeners, word, guard))

    def _drain_one(self) -> None:
        handle, loss, listeners, word, guard = self._pending.popleft()
        mon = monitoring.fit_monitor()
        try:
            if guard is not None:
                from deeplearning4j_tpu import guardrails

                if isinstance(word, guardrails._Resolved):
                    # a rollback already re-resolved this step host-side
                    value = word.value
                elif mon is None:
                    value = guard.deliver(self.model, handle.step,
                                          handle.epoch,
                                          guardrails._fetch_word(word), self)
                else:
                    with mon.phase("drain", step=handle.step):
                        value = guard.deliver(self.model, handle.step,
                                              handle.epoch,
                                              guardrails._fetch_word(word),
                                              self)
            elif mon is None:
                value = _fetch_scalar(loss)
            else:
                with mon.phase("drain", step=handle.step):
                    value = _fetch_scalar(loss)
        except Exception as e:  # surfaced with the step it belongs to
            handle._error = AsyncStepError(handle.step, handle.epoch, e,
                                           trace_id=handle.trace_id,
                                           sentinel=getattr(e, "word", None))
            raise handle._error
        handle._value = value
        _deliver(self.model, listeners, handle.step, handle.epoch, value, mon)

    def drain(self) -> None:
        """Retire every in-flight step (epoch end / fit end / score read)."""
        while self._pending:
            self._drain_one()

    def drain_through(self, handle: ScoreHandle) -> None:
        while self._pending and not handle.ready():
            self._drain_one()


def get_window(model) -> Optional[AsyncScoreWindow]:
    """The model's async window per the CURRENT env/listener state, or None
    for the sync path. ``DL4J_TPU_ASYNC_STEPS=0`` and eager-score listeners
    both force sync; a mode flip drains whatever is still in flight first so
    no score or listener callback is lost across the switch."""
    steps = env.async_steps
    eager = steps <= 0 or any(getattr(l, "needs_eager_score", False)
                              for l in model.listeners)
    window = getattr(model, "_score_window", None)
    if eager:
        if window is not None and len(window):
            window.drain()
        return None
    if window is None:
        window = AsyncScoreWindow(model, steps)
        model._score_window = window
    else:
        window.max_in_flight = max(1, steps)
    return window


def drain_scores(model, suppress: bool = False) -> None:
    """Drain a model's window if one exists. ``suppress=True`` is the
    already-unwinding cleanup form (the original exception wins; in-flight
    scores are still delivered best-effort)."""
    window = getattr(model, "_score_window", None)
    if window is None or not len(window):
        return
    if not suppress:
        window.drain()
        return
    try:
        window.drain()
    except Exception:
        pass


def _deliver(model, listeners, step: int, epoch: int, value: float,
             mon) -> float:
    """A fetched score reaches the model and ``listeners`` (timed when
    ``mon`` is active) under the (step, epoch) it was dispatched with."""
    model._score_value = value
    if mon is None:
        for lst in listeners:
            lst.iteration_done(model, step, epoch, value)
    else:
        with mon.phase("listeners", step=step):
            for lst in listeners:
                lst.iteration_done(model, step, epoch, value)
        mon.iteration_done(value)
    return value


def deliver_score(model, loss, window: Optional[AsyncScoreWindow],
                  mon) -> "float | ScoreHandle":
    """Shared sync-path score delivery + async submit. Sync: fetch, set
    ``_score_value``, run listeners (timed when ``mon`` is active). Async:
    submit to the window. Caller increments ``step_count`` afterwards."""
    if window is not None:
        try:
            return window.submit(loss)  # drains oldest once over capacity
        except BaseException:
            # the handle is queued before the window drains, so an error
            # surfacing here belongs to an OLDER step — the current step is
            # dispatched and queued and must still consume its id, or the
            # next fit_batch would re-dispatch under the same step number
            model.step_count += 1
            raise
    return _deliver(model, model.listeners, model.step_count,
                    model.epoch_count, _fetch_scalar(loss), mon)


def run_step(model, call, window: Optional[AsyncScoreWindow],
             mon) -> "float | ScoreHandle":
    """One train step from dispatch to delivery. ``call()`` enqueues the
    jitted step, keeps its new params/state on the model and returns the
    on-device loss; the score is then delivered as ``deliver_score`` does.
    With ``mon`` active the call is the ``dispatch`` phase (async) or, with
    the fetch, the ``device_step`` phase (sync)."""
    if mon is None:
        # hot path: monitoring off means NO registry/tracer calls here
        return deliver_score(model, call(), window, None)
    if window is not None:
        with mon.phase("dispatch", step=model.step_count):
            loss = call()
        return deliver_score(model, loss, window, mon)
    with mon.phase("device_step", step=model.step_count):
        # the host fetch is the device sync: step time includes it
        value = _fetch_scalar(call())
    return _deliver(model, model.listeners, model.step_count,
                    model.epoch_count, value, mon)


# ---- tail-batch padding --------------------------------------------------
def _pow2_bucket(n: int, limit: int) -> int:
    """Smallest power-of-two >= n, clamped to ``limit`` (the serving tier's
    pow2_buckets/bucket_for, inlined to keep nn free of serving imports)."""
    b = 1
    while b < n and b < limit:
        b <<= 1
    return min(b, limit)


def _pad0(arr, pad: int, ones: bool = False):
    """Pad ``pad`` rows onto dim 0 (zeros, or ones for forward masks —
    all-zero mask rows would feed softmax-attention a fully-masked row and
    poison the batch with NaNs). jnp ops: prefetched device batches must not
    round-trip through the host here. Multi-input lists/dicts (the
    ComputationGraph shape) are padded per entry."""
    import jax.numpy as jnp

    if isinstance(arr, dict):
        return {k: _pad0(v, pad, ones) for k, v in arr.items()}
    if isinstance(arr, (list, tuple)):
        return [_pad0(v, pad, ones) for v in arr]
    a = jnp.asarray(arr)
    fill = jnp.ones if ones else jnp.zeros
    return jnp.concatenate([a, fill((pad,) + a.shape[1:], a.dtype)], axis=0)


def leading_dim(x) -> int:
    """Batch size of a features entry (array, or CG multi-input list/dict)."""
    if isinstance(x, dict):
        x = next(iter(x.values()))
    if isinstance(x, (list, tuple)):
        x = x[0]
    return int(np.shape(x)[0])


def pad_tail_batch(x, y, mask, label_mask, max_batch: int):
    """Pad a partial tail batch up to its pow2 bucket of ``max_batch``.

    Returns (x, y, mask, label_mask), padded or passed through. The padded
    rows are zero features/labels excluded from the loss by a zeroed labels
    mask, so the masked-sum/valid-count normalization reproduces the
    unpadded batch's loss and gradients exactly; only the XLA program shape
    changes. Pass-through cases: full batches, batches already at a bucket
    size, and single-mask batches (their mask plays the forward AND loss
    role through shape-changing feed_forward_mask chains — rewriting it
    into a distinct labels mask is not shape-safe in general).
    """
    b = leading_dim(x)
    if b >= max_batch:
        return x, y, mask, label_mask
    if mask is not None and label_mask is None:
        return x, y, mask, label_mask
    bucket = _pow2_bucket(b, max_batch)
    if bucket <= b:
        return x, y, mask, label_mask
    pad = bucket - b
    if label_mask is None:
        # synthesize the loss mask that excludes the padding: per-timestep
        # [B, T] for sequence labels, per-example [B] otherwise
        shape = (np.shape(y)[:2] if np.ndim(y) == 3 else (b,))
        import jax.numpy as jnp

        label_mask = jnp.ones(shape, jnp.float32)
    x = _pad0(x, pad)
    y = _pad0(y, pad)
    if mask is not None:
        mask = _pad0(mask, pad, ones=True)
    label_mask = _pad0(label_mask, pad)
    return x, y, mask, label_mask


def supports_tail_padding(layers) -> bool:
    """Padding is loss-exact only when no layer computes cross-example
    batch statistics (BatchNorm's mean/var would see the zero rows) and the
    output head reduces to per-example scores under a labels mask."""
    from deeplearning4j_tpu.nn.layers.norm import BatchNormalizationLayer
    from deeplearning4j_tpu.nn.layers.output import LossLayer, OutputLayer

    layers = list(layers)
    if not layers:
        return False
    for l in layers:
        if isinstance(l, BatchNormalizationLayer) and not l.use_mean_var_from_state:
            return False
    out = layers[-1]
    return isinstance(out, (OutputLayer, LossLayer))
