"""Training-guardrail tests: the device-side sentinel word, the
skip → clip-retry → rollback policy ladder, bad-batch bisection blame,
quarantine sidecars, the first-class ``clipnorm`` updater option, and the
zero-overhead spy guard when unarmed.

Reference analog (SURVEY.md §5): the reference's closest facility is
OpProfiler's NaN panic — a host-side post-hoc check that aborts. Here
health is judged ON DEVICE inside the jitted step, the bad update is
discarded before it exists host-side, and recovery is policy, not abort.
"""

import json
import math
import os

import numpy as np
import pytest

from deeplearning4j_tpu import faults, guardrails, monitoring
from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.guardrails import (
    Guardrail, GuardrailPolicy, GuardrailTripped, bisect_culprit,
)
from deeplearning4j_tpu.guardrails import sentinel
from deeplearning4j_tpu.guardrails.sentinel import (
    CTRL_LANES, SentinelState, WORD_GNORM, WORD_LOSS, WORD_OK, WORD_Z,
)
from deeplearning4j_tpu.nn import (
    InputType, MultiLayerNetwork, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize import Sgd
from deeplearning4j_tpu.optimize.async_dispatch import (
    AsyncStepError, drain_scores,
)
from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
from deeplearning4j_tpu.optimize.updaters import (
    Adam, Nesterovs, updater_from_dict,
)


#: every flag a test of this file sets. DL4J_TPU_MONITORING among them: left
#: out, the teardown's reload baked it into the singleton and every later
#: file on the worker started with monitoring armed
_ENV_VARS = ("DL4J_TPU_ASYNC_STEPS", "DL4J_TPU_PAD_TAIL", "DL4J_TPU_GUARDRAILS",
             "DL4J_TPU_GUARDRAILS_DIR", "DL4J_TPU_MONITORING")


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Fresh env/faults/metrics around every test; async default."""
    for var in _ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    env.reload()
    faults.configure("")
    monitoring.reset()
    yield
    faults.configure("")
    # monkeypatch undoes setenv AFTER this teardown runs, so reloading
    # here would bake a test's env vars into the singleton and leak them
    # into whatever suite runs next — clear them first
    for var in _ENV_VARS:
        os.environ.pop(var, None)
    env.reload()
    monitoring.reset()      # enablement back to the (now cleared) env flag


def _async(monkeypatch, steps):
    monkeypatch.setenv("DL4J_TPU_ASYNC_STEPS", str(steps))
    env.reload()


def _model(seed=5, updater=None):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(updater or Sgd(lr=0.1)).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(lr=0.1)).graph_builder()
            .add_inputs("in")
            .set_input_types(**{"in": InputType.feed_forward(4)})
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("o", OutputLayer(n_out=3, activation="softmax",
                                        loss="mcxent"), "d")
            .set_outputs("o").build())
    return ComputationGraph(conf).init()


def _data(n=16, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _leaves(model):
    import jax

    return [np.asarray(l) for l in jax.tree_util.tree_leaves(model.params)]


# --------------------------------------------------------------- sentinel
class TestSentinelScreen:
    """Unit tests of the jitted health word against manual math."""

    def _grads(self):
        return [{"W": np.full((3, 2), 0.5, np.float32),
                 "b": np.ones((2,), np.float32)}]

    def _ctrl(self, clip=0.0, gmax=0.0, zmax=0.0, mean=0.0, var=-1.0):
        import jax.numpy as jnp

        return jnp.asarray([clip, gmax, zmax, mean, var], jnp.float32)

    def _run(self, grads, loss, ctrl):
        import jax

        out_g, word = jax.jit(sentinel.screen)(grads, np.float32(loss), ctrl)
        return jax.device_get(out_g), np.asarray(word)

    def test_clean_step_word_and_gnorm_math(self):
        grads = self._grads()
        _, w = self._run(grads, 1.25, self._ctrl())
        manual = math.sqrt(6 * 0.5 ** 2 + 2 * 1.0 ** 2)
        assert w[WORD_OK] == 1.0
        assert w[WORD_GNORM] == pytest.approx(manual, rel=1e-6)
        assert w[WORD_LOSS] == pytest.approx(1.25)
        assert len(w) == sentinel.WORD_LANES
        assert CTRL_LANES == 5

    def test_nan_loss_trips(self):
        _, w = self._run(self._grads(), float("nan"), self._ctrl())
        assert w[WORD_OK] == 0.0

    def test_nonfinite_grads_trip(self):
        grads = [{"W": np.array([[np.inf, 1.0]], np.float32)}]
        _, w = self._run(grads, 0.5, self._ctrl())
        assert w[WORD_OK] == 0.0
        assert not np.isfinite(w[WORD_GNORM])

    def test_gnorm_limit_trips_and_clip_rescues(self):
        grads = self._grads()
        _, w = self._run(grads, 0.5, self._ctrl(gmax=1.0))
        assert w[WORD_OK] == 0.0          # gnorm ~1.58 > 1.0
        # clip scales below the limit: same batch passes on retry
        _, w2 = self._run(grads, 0.5, self._ctrl(clip=0.5, gmax=1.0))
        assert w2[WORD_OK] == 1.0

    def test_clip_scales_gradients_to_target_norm(self):
        grads = self._grads()
        out, w = self._run(grads, 0.5, self._ctrl(clip=0.5))
        gnorm = float(w[WORD_GNORM])
        scaled = np.sqrt(sum(float((np.asarray(g) ** 2).sum())
                             for g in [out[0]["W"], out[0]["b"]]))
        assert scaled == pytest.approx(0.5, rel=1e-5)
        # word reports the PRE-clip norm
        assert gnorm == pytest.approx(math.sqrt(6 * 0.25 + 2), rel=1e-6)

    def test_noclip_is_bit_exact_identity(self):
        grads = self._grads()
        out, _ = self._run(grads, 0.5, self._ctrl())
        np.testing.assert_array_equal(out[0]["W"], grads[0]["W"])
        np.testing.assert_array_equal(out[0]["b"], grads[0]["b"])

    def test_z_screen_math_and_warmup_gate(self):
        grads = self._grads()
        # var = 0.01, mean = 1: loss 2 -> z ~ 10 > 6 -> trip
        _, w = self._run(grads, 2.0, self._ctrl(zmax=6.0, mean=1.0, var=0.01))
        assert w[WORD_OK] == 0.0
        assert w[WORD_Z] == pytest.approx((2.0 - 1.0) / math.sqrt(0.01 + 1e-12),
                                          rel=1e-4)
        # var < 0 == warmup: identical loss passes, z screen off
        _, w2 = self._run(grads, 2.0, self._ctrl(zmax=6.0, mean=1.0, var=-1.0))
        assert w2[WORD_OK] == 1.0


class TestSentinelState:
    def test_ewma_matches_manual_recurrence(self):
        s = SentinelState(alpha=0.5, warmup=2)
        mean, var = 0.0, 0.0
        for i, loss in enumerate([1.0, 2.0, 1.5, 3.0]):
            s.update(loss)
            if i == 0:
                mean, var = loss, 0.0
            else:
                d = loss - mean
                mean = 0.5 * mean + 0.5 * loss
                var = 0.5 * var + 0.5 * d * d
        assert s.mean == pytest.approx(mean)
        assert s.var == pytest.approx(var)

    def test_warmup_baseline_disables_z(self):
        s = SentinelState(warmup=3)
        s.update(1.0)
        s.update(1.1)
        assert s.baseline() == (0.0, -1.0)
        assert s.zscore(100.0) == 0.0
        s.update(1.2)
        mean, var = s.baseline()
        assert var >= 0 and mean == pytest.approx(s.mean)

    def test_variance_floor_blocks_jitter_trips(self):
        s = SentinelState(warmup=2)
        for _ in range(10):
            s.update(2.0)             # constant loss: raw var == 0
        _, var = s.baseline()
        assert var >= (0.05 * 2.0) ** 2 * 0.999
        assert s.zscore(2.02) < 1.0

    def test_nonfinite_losses_ignored(self):
        s = SentinelState()
        s.update(1.0)
        s.update(float("nan"))
        s.update(float("inf"))
        assert s.n == 1 and s.mean == 1.0


# --------------------------------------------------------------- bisection
class TestBisectCulprit:
    @pytest.mark.parametrize("n", [1, 4, 7])
    def test_names_exact_culprit_at_every_position(self, n):
        for culprit in range(n):
            applied = []

            def snapshot():
                return list(applied)

            def restore(s):
                applied[:] = s

            def run_range(i, j):
                trip = any(k == culprit for k in range(i, j))
                applied.extend(range(i, j))
                return trip

            idx, rounds = bisect_culprit(n, run_range, snapshot, restore)
            assert idx == culprit
            assert rounds <= max(0, math.ceil(math.log2(max(n, 1))))

    @pytest.mark.parametrize("n", [2, 5, 8])
    def test_state_corrupting_culprit_via_ref_probe_predicate(self, n):
        """The guardrail's sneaky-culprit predicate: nothing trips
        in-range; badness is only visible when the culprit's effect is IN
        the applied state (the trailing ref probe)."""
        for culprit in range(n):
            applied = []

            def snapshot():
                return list(applied)

            def restore(s):
                applied[:] = s

            def run_range(i, j):
                applied.extend(range(i, j))
                return culprit in applied   # ref probe after the range

            idx, _ = bisect_culprit(n, run_range, snapshot, restore)
            assert idx == culprit

    def test_empty_window_rejected(self):
        with pytest.raises(ValueError):
            bisect_culprit(0, lambda i, j: True, list, lambda s: None)

    def test_single_entry_needs_zero_rounds(self):
        idx, rounds = bisect_culprit(1, lambda i, j: True, list,
                                     lambda s: None)
        assert (idx, rounds) == (0, 0)


# ---------------------------------------------------------- zero overhead
class TestZeroOverheadUnarmed:
    def test_unarmed_fit_touches_no_guardrail_code(self, monkeypatch):
        """The spy guard: with guardrails unarmed, fit_batch must not call
        Guardrail.step or sentinel.screen, and must not compile the
        guarded train-step variant."""
        calls = []
        monkeypatch.setattr(
            Guardrail, "step",
            lambda self, *a, **k: calls.append("step"))
        monkeypatch.setattr(
            sentinel, "screen",
            lambda *a, **k: calls.append("screen"))
        net = _model()
        x, y = _data()
        for _ in range(3):
            net.fit_batch((x, y))
        drain_scores(net)
        assert calls == []
        assert "train_guarded" not in net._jit_cache
        assert net._guardrail is None     # env arming resolved once, to off


# ------------------------------------------------------------ armed clean
class TestArmedCleanRun:
    def test_armed_untripped_params_bit_identical(self, monkeypatch):
        """Arming the sentinel on a healthy run must not change a single
        bit of the trajectory (clip lane 0 -> exact identity scaling)."""
        _async(monkeypatch, 0)
        x, y = _data(32)

        plain, pl = _model(), CollectScoresListener()
        plain.set_listeners(pl)
        plain.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=3)

        armed, al = _model(), CollectScoresListener()
        armed.set_listeners(al)
        guard = guardrails.arm(armed)
        armed.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=3)

        assert al.scores == pl.scores
        for a, b in zip(_leaves(armed), _leaves(plain)):
            np.testing.assert_array_equal(a, b)
        assert guard.trips == 0
        assert "train_guarded" in armed._jit_cache

    def test_graph_armed_untripped_bit_identical(self, monkeypatch):
        _async(monkeypatch, 2)
        x, y = _data(32, rng_seed=7)

        plain = _graph()
        plain.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2)

        armed = _graph()
        guard = guardrails.arm(armed)
        armed.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2)

        for a, b in zip(_leaves(armed), _leaves(plain)):
            np.testing.assert_array_equal(a, b)
        assert guard.trips == 0


# ------------------------------------------------------------- the ladder
class TestSkipRung:
    def test_skip_discards_update_and_quarantines(self, monkeypatch, tmp_path):
        _async(monkeypatch, 0)
        net = _model()
        qp = str(tmp_path / "q.ndjson")
        guard = guardrails.arm(net, GuardrailPolicy(skip_budget=3),
                               quarantine_path=qp)
        x, y = _data()
        faults.configure("nan_grad:1@step==2")
        scores = [net.fit_batch((x, y)) for _ in range(5)]
        # the trip delivered its truthful NaN loss, then training moved on
        assert math.isnan(scores[2])
        assert all(math.isfinite(s) for s in scores[3:])
        assert guard.trips == 1 and guard.steps_lost == 1
        assert guard.rollbacks == 0
        assert guard.quarantined == [2]
        rec = [json.loads(l) for l in open(qp)]
        assert rec[0]["step"] == 2 and rec[0]["method"] == "direct"
        assert rec[0]["word"]["ok"] == 0.0
        assert any(t["tensor"] == "features" and t["finite_fraction"] < 1.0
                   for t in rec[0]["batch"])

    def test_skipped_step_leaves_params_untouched(self, monkeypatch):
        _async(monkeypatch, 0)
        net = _model()
        guardrails.arm(net, GuardrailPolicy(skip_budget=3))
        x, y = _data()
        faults.configure("nan_grad:1@step==1")
        net.fit_batch((x, y))
        before = _leaves(net)
        net.fit_batch((x, y))        # poisoned: device discards the update
        after = _leaves(net)
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, b)


class TestClipRetryRung:
    def test_gnorm_trip_rescued_by_clip(self, monkeypatch):
        _async(monkeypatch, 0)
        net = _model()
        guard = guardrails.arm(net, GuardrailPolicy(
            skip_budget=0, clip_retry=True, clipnorm=0.5, gnorm_limit=1.0,
            warmup_steps=10_000))
        x, y = _data()
        faults.configure("loss_spike:1@step==3")
        scores = [net.fit_batch((x, y)) for _ in range(6)]
        assert guard.trips == 1
        assert guard.rollbacks == 0 and guard.steps_lost == 0
        assert all(math.isfinite(s) for s in scores)
        assert all(np.isfinite(l).all() for l in _leaves(net))

    def test_nan_is_not_laundered_by_clip(self, monkeypatch):
        """A NaN gradient fails the clip retry too (NaN * scale == NaN) —
        the ladder must not let clipping mask a non-finite step."""
        _async(monkeypatch, 0)
        net = _model()
        guard = guardrails.arm(net, GuardrailPolicy(
            skip_budget=1, clip_retry=True, clipnorm=1.0))
        x, y = _data()
        faults.configure("nan_grad:2@step>0")
        net.fit_batch((x, y))
        net.fit_batch((x, y))        # trip 1: skip (budget 1)
        with pytest.raises(GuardrailTripped) as exc_info:
            net.fit_batch((x, y))    # trip 2: clip fails, no checkpointer
        assert exc_info.value.step == 2
        assert exc_info.value.word[WORD_OK] == 0.0
        assert guard.trips == 2


class TestRollbackRung:
    def test_rollback_restores_last_good_bit_exact(self, monkeypatch,
                                                   tmp_path):
        _async(monkeypatch, 0)
        net = _model()
        guard = guardrails.arm(net, GuardrailPolicy(
            skip_budget=0, clip_retry=False, checkpoint_every=2,
            warmup_steps=10_000), checkpoint_dir=str(tmp_path))
        x, y = _data()
        faults.configure("nan_grad:1@step==2")
        net.fit_batch((x, y))
        net.fit_batch((x, y))        # cadence: key 2 == state after 2 steps
        good = _leaves(net)
        score = net.fit_batch((x, y))   # trip at step 2 -> rollback
        assert math.isnan(score)
        assert guard.rollbacks == 1
        assert guard.quarantined == [2]
        # nothing to replay (window of one, all blamed): params are the
        # checkpoint's, bit for bit
        for a, b in zip(_leaves(net), good):
            np.testing.assert_array_equal(a, b)
        # training resumes cleanly from the restored state
        assert math.isfinite(float(net.fit_batch((x, y))))
        assert net.step_count == 4
        assert os.path.exists(str(tmp_path / "quarantine.ndjson"))

    def test_rollback_never_checkpoints_nonfinite_params(self, monkeypatch,
                                                         tmp_path):
        """Every checkpoint the guardrail writes must validate + restore to
        fully finite params — the core acceptance invariant."""
        _async(monkeypatch, 2)
        net = _model()
        guard = guardrails.arm(net, GuardrailPolicy(
            skip_budget=0, checkpoint_every=4, warmup_steps=4),
            checkpoint_dir=str(tmp_path))
        x, y = _data()
        faults.configure("nan_grad:1@step==6")
        for _ in range(12):
            net.fit_batch((x, y))
        drain_scores(net)
        assert guard.rollbacks == 1
        probe = _model(seed=99)
        for step in guard.checkpointer.all_steps():
            guard.checkpointer.restore(step, probe)
            assert all(np.isfinite(l).all() for l in _leaves(probe)), step


class TestAsyncBisection:
    def test_culprit_named_mid_window_under_async(self, monkeypatch,
                                                  tmp_path):
        """The trip surfaces steps late under async dispatch; the
        bisection must still blame exactly the poisoned batch."""
        _async(monkeypatch, 2)
        net, lst = _model(), CollectScoresListener()
        net.set_listeners(lst)
        guard = guardrails.arm(net, GuardrailPolicy(
            skip_budget=0, checkpoint_every=5, warmup_steps=4),
            checkpoint_dir=str(tmp_path))
        x, y = _data()
        faults.configure("nan_grad:1@step==7")
        for _ in range(20):
            net.fit_batch((x, y))
        drain_scores(net)
        assert guard.trips == 1 and guard.rollbacks == 1
        assert guard.quarantined == [7]
        assert guard.last_bisect_probes >= 1
        assert all(np.isfinite(l).all() for l in _leaves(net))
        # ordered, exactly-once delivery: every iteration 0..19 observed in
        # order, the culprit's score the honest NaN
        its = [i for i, _ in lst.scores]
        assert its == list(range(20))
        by_it = dict(lst.scores)
        assert math.isnan(by_it[7])
        assert all(math.isfinite(v) for i, v in by_it.items() if i != 7)
        rec = [json.loads(l)
               for l in open(str(tmp_path / "quarantine.ndjson"))]
        assert [r["step"] for r in rec] == [7]
        assert rec[0]["method"] == "bisect"

    def test_exhausted_ladder_surfaces_as_async_step_error(self, monkeypatch):
        """Satellite (b): a GuardrailTripped at drain becomes an
        AsyncStepError with the ORIGINAL step and the sentinel word —
        and later healthy steps still reach listeners, in order."""
        _async(monkeypatch, 2)
        net, lst = _model(), CollectScoresListener()
        net.set_listeners(lst)
        guardrails.arm(net, GuardrailPolicy(skip_budget=0, clip_retry=False))
        x, y = _data()
        faults.configure("nan_grad:1@step==3")
        errors = []
        for _ in range(10):
            try:
                net.fit_batch((x, y))
            except AsyncStepError as e:
                errors.append(e)
        drain_scores(net)
        assert len(errors) == 1
        err = errors[0]
        assert err.step == 3
        assert isinstance(err.__cause__, GuardrailTripped)
        assert err.sentinel is not None and err.sentinel[WORD_OK] == 0.0
        assert "sentinel" in str(err)
        # the failed step never fires listeners; every other step does,
        # in order — the regression half of satellite (b)
        its = [i for i, _ in lst.scores]
        assert its == [i for i in range(10) if i != 3]
        assert all(math.isfinite(v) for _, v in lst.scores)


# ------------------------------------------------------- clipnorm updater
class TestClipnormUpdater:
    def test_clipnorm_matches_manual_global_norm_math(self, monkeypatch):
        """Satellite (c): Sgd(clipnorm=c) must produce exactly the manual
        min(1, c/||g||)-scaled update of the unclipped run."""
        _async(monkeypatch, 0)
        x, y = _data()
        c = 0.05

        ref = _model(updater=Sgd(lr=0.1))
        p0 = _leaves(ref)
        ref.fit_batch((x, y))
        raw_delta = [a - b for a, b in zip(_leaves(ref), p0)]
        # Sgd: delta == -lr * g, so ||g|| == ||delta|| / lr
        gnorm = math.sqrt(sum(float((d.astype(np.float64) ** 2).sum())
                              for d in raw_delta)) / 0.1
        scale = min(1.0, c / gnorm)
        assert scale < 1.0               # the clip actually engages

        clipped = _model(updater=Sgd(lr=0.1, clipnorm=c))
        q0 = _leaves(clipped)
        clipped.fit_batch((x, y))
        clip_delta = [a - b for a, b in zip(_leaves(clipped), q0)]
        # atol covers f32 round-trip noise: raw_delta is the f32-quantized
        # lr*g, while the clipped run scales the pre-quantization gradient
        for d_raw, d_clip in zip(raw_delta, clip_delta):
            np.testing.assert_allclose(d_clip, d_raw * scale, rtol=2e-5,
                                       atol=1e-7)

    def test_clipnorm_serializes_and_keeps_positional_args(self):
        u = Nesterovs(0.1, 0.9, clipnorm=2.5)    # lr/momentum positional
        assert (u.lr, u.momentum, u.clipnorm) == (0.1, 0.9, 2.5)
        r = updater_from_dict(u.to_dict())
        assert r == u and r.clipnorm == 2.5
        assert Adam(1e-3).clipnorm == 0.0

    def test_guardrail_clip_retry_reuses_global_norm_clip(self, monkeypatch):
        """The ladder's clip rung and the updater option share one
        definition: a clip-retried step equals a clipnorm-armed step."""
        _async(monkeypatch, 0)
        x, y = _data()
        c = 0.05

        # gnorm_limit == clipnorm: the raw step (||g|| ~0.7) trips the
        # limit, and the clipped replay lands exactly ON it, so the retry
        # passes its own screen (limits below clipnorm can never rescue)
        viaguard = _model(updater=Sgd(lr=0.1))
        guardrails.arm(viaguard, GuardrailPolicy(
            skip_budget=0, clip_retry=True, clipnorm=c, gnorm_limit=c,
            warmup_steps=10_000))
        viaguard.fit_batch((x, y))       # gnorm_limit trips; clip rescues

        viaopt = _model(updater=Sgd(lr=0.1, clipnorm=c))
        viaopt.fit_batch((x, y))

        for a, b in zip(_leaves(viaguard), _leaves(viaopt)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


# ------------------------------------------------------- arming / metrics
class TestArmingAndMetrics:
    def test_env_arming(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_GUARDRAILS", "1")
        monkeypatch.setenv("DL4J_TPU_GUARDRAILS_DIR", str(tmp_path))
        env.reload()
        net = _model()
        guard = guardrails.get_guard(net)
        assert isinstance(guard, Guardrail)
        assert guard.checkpointer is not None
        assert guardrails.get_guard(net) is guard     # cached on the model
        guardrails.disarm(net)
        assert guardrails.get_guard(net) is None

    def test_checkpoint_cadence(self, monkeypatch, tmp_path):
        _async(monkeypatch, 0)
        net = _model()
        guard = guardrails.arm(net, GuardrailPolicy(checkpoint_every=3),
                               checkpoint_dir=str(tmp_path))
        x, y = _data()
        for _ in range(9):
            net.fit_batch((x, y))
        steps = guard.checkpointer.all_steps()
        assert steps[-1] == 9
        assert set(steps) <= {0, 3, 6, 9}
        assert len(steps) <= guard.policy.keep_last
        guardrails.disarm(net)

    def test_recovery_metric_and_flight_incident(self, monkeypatch):
        """Tier-1 smoke of satellite (f): an injected nan_grad must show up
        as dl4j_recovery_total{component="guardrails"} plus the guardrail
        tier, and cut a numeric_trip flight incident."""
        monkeypatch.setenv("DL4J_TPU_MONITORING", "1")
        env.reload()
        monitoring.reset()
        rec = monitoring.flight.configure(enabled=True)
        _async(monkeypatch, 0)
        net = _model()
        guardrails.arm(net, GuardrailPolicy(skip_budget=3))
        x, y = _data()
        faults.configure("nan_grad:1@step==1")
        for _ in range(4):
            net.fit_batch((x, y))
        text = monitoring.metrics_text()
        assert ('dl4j_recovery_total{component="guardrails",outcome="skip"} 1'
                in text)
        assert 'dl4j_guardrail_trips_total{kind="nonfinite"} 1' in text
        assert 'dl4j_guardrail_steps_lost_total 1' in text
        trips = [e for e in rec.tail() if e["kind"] == "numeric_trip"]
        assert len(trips) == 1
        assert trips[0]["action"] == "skip" and trips[0]["step"] == 1
        assert trips[0]["word"][WORD_OK] == 0.0
        assert trips[0]["sentinel_trace"][-1]["step"] == 1


# --------------------------------------------------------------- e2e chaos
@pytest.mark.slow
class TestEndToEndChaos:
    def test_injected_nan_converges_like_fault_free_twin(self, monkeypatch,
                                                         tmp_path):
        """The acceptance witness: DL4J_TPU_FAULTS="nan_grad:1@step>20" over
        a real fit; training completes, no checkpoint ever holds a
        non-finite param, the culprit is named, and the final loss lands
        within tolerance of the fault-free twin."""
        x, y = _data(64, rng_seed=3)

        def run(spec, ckpt_dir):
            _async(monkeypatch, 2)
            net = _model(seed=21)
            guard = guardrails.arm(net, GuardrailPolicy(
                skip_budget=0, checkpoint_every=8, warmup_steps=6),
                checkpoint_dir=ckpt_dir)
            faults.configure(spec)
            it = ArrayDataSetIterator(x, y, batch_size=16)
            net.fit(it, epochs=15)            # 60 steps
            faults.configure("")
            loss = float(net.score((x, y)))
            return net, guard, loss

        faulty, guard, loss = run("nan_grad:1@step>20",
                                  str(tmp_path / "faulty"))
        clean, _, clean_loss = run("", str(tmp_path / "clean"))

        assert guard.trips == 1 and guard.rollbacks == 1
        assert guard.quarantined == [21]
        rec = [json.loads(l)
               for l in open(str(tmp_path / "faulty" / "quarantine.ndjson"))]
        assert [r["step"] for r in rec] == [21]
        # zero non-finite params ever checkpointed
        probe = _model(seed=99)
        for step in guard.checkpointer.all_steps():
            guard.checkpointer.restore(step, probe)
            assert all(np.isfinite(l).all() for l in _leaves(probe))
        # one lost batch out of 60 steps: the documented tolerance is 15%
        # relative on the final full-set loss
        assert math.isfinite(loss)
        assert loss == pytest.approx(clean_loss, rel=0.15)
