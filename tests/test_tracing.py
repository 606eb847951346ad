"""Request-scoped tracing + flight recorder tests (PR 12).

Covers the ISSUE-12 witness list: the SpanTracer ring cap (memory stays
flat under a million spans, drops counted), exposition hardening against
hostile label/help text, RequestTrace/RequestTracer semantics (header
adoption, completed ring, Chrome-trace shape), the FlightRecorder ring +
trigger-dump bundles, the ``/debug/requests`` / ``/debug/trace/<id>`` /
``/debug/flight`` surfaces on a traced gateway (one traced generate
request end to end), OpenMetrics exemplars behind ``?exemplars=1``, and
chaos trace propagation — an armed worker crash dumps a postmortem bundle
naming the trace that rode the crashed worker.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import faults, monitoring
from deeplearning4j_tpu.common.env import env
from deeplearning4j_tpu.monitoring import flight
from deeplearning4j_tpu.monitoring.context import (
    RequestTrace, RequestTracer, bind, current, current_trace_id,
)
from deeplearning4j_tpu.monitoring.flight import FlightRecorder
from deeplearning4j_tpu.monitoring.tracing import SpanTracer, validate_nesting
from deeplearning4j_tpu.serving import ServingGateway


@pytest.fixture(autouse=True)
def _fresh_monitoring():
    """Fresh registry/tracer/recorder and env-default enablement per test."""
    monitoring.reset()
    yield
    monitoring.reset()


class StubModel:
    def __init__(self, scale=1.0, delay=0.0):
        self.scale = scale
        self.delay = delay

    def output(self, x):
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(x) * self.scale


def _post(base, path, payload, timeout=30, headers=None):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(base, path, timeout=10):
    try:
        r = urllib.request.urlopen(base + path, timeout=timeout)
        return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


# --------------------------------------------------------------- span ring
class TestSpanTracerRing:
    def test_cap_drops_oldest_and_counts(self):
        monitoring.enable()
        tr = SpanTracer(max_events=8)
        for i in range(20):
            tr.instant(f"e{i}")
        evs = [e for e in tr.events() if e["ph"] not in ("M",)]
        assert len(evs) == 8
        # oldest evicted, newest kept
        assert evs[0]["name"] == "e12" and evs[-1]["name"] == "e19"
        assert tr.dropped == 12
        fam = monitoring.registry().get("dl4j_trace_events_dropped_total")
        assert fam is not None and fam.value == 12

    def test_metadata_survives_eviction(self):
        tr = SpanTracer(max_events=4)
        with tr.span("keepalive"):
            pass
        for i in range(50):
            tr.instant(f"e{i}")
        metas = [e for e in tr.events() if e["ph"] == "M"]
        names = {e["name"] for e in metas}
        # process_name + this thread's thread_name still present after the
        # span events themselves were evicted
        assert {"process_name", "thread_name"} <= names

    def test_memory_flat_under_a_million_spans(self):
        """The long-running-gateway regression: a million span events must
        not grow the tracer past its ring (the pre-ring SpanTracer kept
        every event in an unbounded list)."""
        tr = SpanTracer(max_events=1000)
        for i in range(1_000_000):
            tr.instant("tick")
        assert len(tr._events) == 1000
        assert tr.dropped == 999_000
        validate_nesting(tr.events())

    def test_env_tunable_cap(self, monkeypatch):
        monkeypatch.setattr(env, "trace_max_events", 16)
        tr = SpanTracer()
        assert tr._cap == 16

    def test_complete_emits_x_event(self):
        tr = SpanTracer()
        tr.complete("queue_wait", 0.25, trace_id="abc")
        (ev,) = [e for e in tr.events() if e["ph"] == "X"]
        assert ev["name"] == "queue_wait"
        assert ev["dur"] == pytest.approx(0.25e6)
        assert ev["args"]["trace_id"] == "abc"
        assert ev["ts"] >= 0


# ------------------------------------------------------ hostile exposition
class TestExpositionHardening:
    def test_hostile_label_and_help_text(self):
        reg = monitoring.MetricsRegistry()
        c = reg.counter("dl4j_evil_total",
                        'help with "quotes", \\backslash\\ and\nnewline',
                        labels=("who",))
        c.labels(who='injector"} 1\nfake_metric 99').inc()
        text = reg.exposition()
        lines = text.strip().split("\n")
        # every line is a comment or starts with the metric name — the
        # hostile value could not fabricate an extra sample line
        assert all(l.startswith("#") or l.startswith("dl4j_evil_total")
                   for l in lines)
        assert "fake_metric 99" not in [l.strip() for l in lines]
        help_line = [l for l in lines if l.startswith("# HELP")][0]
        assert "\\n" in help_line and "\\\\" in help_line
        sample = [l for l in lines if not l.startswith("#")][0]
        assert '\\"' in sample and "\\n" in sample

    def test_exemplar_rendering_only_when_asked(self):
        monitoring.enable()
        h = monitoring.registry().histogram("dl4j_exm_seconds", "t",
                                            buckets=(0.1, 1.0))
        h.observe(0.05, exemplar={"trace_id": "tr01"})
        plain = monitoring.metrics_text()
        assert "# {" not in plain
        om = monitoring.metrics_text(exemplars=True)
        (ex_line,) = [l for l in om.splitlines() if "# {" in l]
        assert 'le="0.1"' in ex_line and 'trace_id="tr01"' in ex_line


# ----------------------------------------------------------- request trace
class TestRequestTrace:
    def test_spans_events_summary(self):
        tr = RequestTrace("tid1", "rid1", "/v1/*/predict", model="m")
        with tr.span("quota_check"):
            pass
        t0 = time.monotonic()
        tr.add_span("queue_wait", t0 - 0.01, t0)
        tr.event("shed", reason="deadline")
        tr.finish("shed", code=504, reason="deadline")
        s = tr.summary()
        assert s["trace_id"] == "tid1" and s["disposition"] == "shed"
        assert s["stages"]["queue_wait"]["seconds"] == pytest.approx(
            0.01, abs=5e-3)
        assert s["events"] == ["shed"] and s["done"]

    def test_to_chrome_shape(self):
        tr = RequestTrace("tid2", "rid2", "/v1/*/generate")
        with tr.span("prefill", prompt_len=3):
            pass
        tr.event("retire", reason="eos")
        tr.finish("served", code=200)
        doc = tr.to_chrome()
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        phases = [e["ph"] for e in doc["traceEvents"]]
        assert "M" in phases and "X" in phases and "i" in phases
        metas = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
        assert {"process_name", "thread_name"} <= metas
        xs = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"prefill", "request /v1/*/generate"} <= xs
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] >= 0
        json.dumps(doc)  # serializable as-is

    def test_mirrors_into_span_tracer(self):
        tracer = monitoring.start_tracing()
        tr = RequestTrace("tid3", "rid3", "/r")
        with tr.span("gather"):
            pass
        tr.event("shed", reason="slo")
        names = {(e["ph"], e["name"]) for e in tracer.events()}
        assert ("X", "gather") in names and ("i", "shed") in names

    def test_header_adoption_and_sanitization(self):
        rt = RequestTracer()
        t1 = rt.begin("/r", headers={"X-Trace-Id": "client-id_9.a"})
        assert t1.trace_id == "client-id_9.a"
        # hostile / malformed ids are replaced, never adopted
        for bad in ("evil\nid", "x" * 65, "", 'a"b', None):
            t = rt.begin("/r", headers={"X-Trace-Id": bad} if bad is not None
                         else None)
            assert t.trace_id != bad
            assert len(t.trace_id) == 16

    def test_completed_ring_and_lookup(self):
        rt = RequestTracer(capacity=3)
        traces = [rt.begin("/r") for _ in range(5)]
        assert len(rt.inflight()) == 5
        for t in traces:
            rt.finish(t, "served", code=200)
        assert not rt.inflight()
        assert len(rt.completed()) == 3
        assert rt.get(traces[0].trace_id) is None        # evicted
        assert rt.get(traces[-1].trace_id) is traces[-1]
        d = rt.describe()
        assert d["capacity"] == 3 and len(d["completed"]) == 3
        # newest first
        assert d["completed"][0]["trace_id"] == traces[-1].trace_id

    def test_bind_current_thread_local(self):
        tr = RequestTrace("tid4", "rid4", "/r")
        assert current() is None
        with bind(tr):
            assert current() is tr and current_trace_id() == "tid4"
            seen = {}

            def other():
                seen["trace"] = current()

            th = threading.Thread(target=other)
            th.start()
            th.join()
            assert seen["trace"] is None     # thread-local, not global
        assert current() is None
        with bind(None):
            assert current() is None         # transparent no-op

    def test_async_step_error_carries_ambient_trace(self):
        from deeplearning4j_tpu.optimize.async_dispatch import AsyncStepError

        class _Model:
            step_count = 3
            epoch_count = 1
            listeners = ()

        from deeplearning4j_tpu.optimize.async_dispatch import AsyncScoreWindow
        win = AsyncScoreWindow(_Model(), max_in_flight=4)
        tr = RequestTrace("tidw", "ridw", "/train")
        with bind(tr):
            h = win.submit(np.float32(1.5))
        assert h.trace_id == "tidw"
        win2 = AsyncScoreWindow(_Model(), max_in_flight=4)
        bad = win2.submit("not-a-number")
        with pytest.raises(AsyncStepError) as ei:
            win2.drain()
        assert ei.value.trace_id is None     # dispatched unbound
        assert bad._error is ei.value


# --------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_tail_and_describe(self):
        rec = FlightRecorder(capacity=4)
        for i in range(7):
            rec.record("admit", route="/r", n=i)
        assert [e["n"] for e in rec.tail()] == [3, 4, 5, 6]
        d = rec.describe(tail=2)
        assert d["recorded_total"] == 7 and d["dropped"] == 3
        assert len(d["events"]) == 2 and d["capacity"] == 4

    def test_trigger_dump_bundle(self, tmp_path):
        monitoring.enable()
        monitoring.serving_monitor()   # register metrics for the snapshot
        rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                             min_dump_interval_s=0.0)
        tr = RequestTrace("tdump123", "r1", "/v1/*/predict")
        rec.record("admit", route="/v1/*/predict", trace=tr)
        rec.record("shed", severity="warn", reason="deadline", trace=tr)
        assert not rec.dumps                  # non-trigger kinds: no dump
        rec.record("worker_crash", severity="error", worker="pi-m-0",
                   trace=tr)
        assert len(rec.dumps) == 1
        bundle = json.loads((tmp_path / rec.dumps[0].split("/")[-1]
                             ).read_text())
        assert bundle["reason"] == "worker_crash"
        kinds = [e["kind"] for e in bundle["events"]]
        assert kinds == ["admit", "shed", "worker_crash"]
        assert all(e["trace_id"] == "tdump123" for e in bundle["events"])
        assert bundle["trace"]["summary"]["trace_id"] == "tdump123"
        assert "traceEvents" in bundle["trace"]["chrome"]
        assert "dl4j_serving_" in bundle["metrics"]

    def test_dump_rate_limit_and_force(self, tmp_path):
        rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                             min_dump_interval_s=3600.0)
        rec.record("worker_crash", severity="error")
        rec.record("worker_crash", severity="error")
        assert len(rec.dumps) == 1           # second crash rate-limited
        assert rec.dump("manual", force=True) is not None
        assert len(rec.dumps) == 2

    def test_env_arming(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_FLIGHT", "1")
        monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("DL4J_TPU_FLIGHT_CAP", "9")
        flight.reset()
        rec = flight.recorder()
        assert rec is not None
        assert rec.capacity == 9 and rec.dump_dir == str(tmp_path)
        monkeypatch.delenv("DL4J_TPU_FLIGHT")
        monkeypatch.delenv("DL4J_TPU_FLIGHT_DIR")
        monkeypatch.delenv("DL4J_TPU_FLIGHT_CAP")
        flight.reset()
        assert flight.recorder() is None


# -------------------------------------------------------- debug endpoints
class TestDebugEndpoints:
    def test_traced_predict_full_surface(self):
        monitoring.enable()
        gw = ServingGateway(port=0, seed=0, trace=True).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            gw.register_model("m", "v1", StubModel(), warmup=False)
            code, body, _ = _post(base, "/v1/m/predict",
                                  {"inputs": [[1.0, 2.0]]},
                                  headers={"X-Trace-Id": "predsmoke1"})
            assert code == 200

            code, raw, _ = _get(base, "/debug/requests")
            d = json.loads(raw)
            assert code == 200 and d["enabled"]
            (row,) = [t for t in d["completed"]
                      if t["trace_id"] == "predsmoke1"]
            assert row["disposition"] == "served" and row["code"] == 200
            assert {"quota_check", "submit", "queue_wait",
                    "device_dispatch", "gather",
                    "serialize"} <= set(row["stages"])

            code, raw, _ = _get(base, "/debug/trace/predsmoke1")
            doc = json.loads(raw)
            assert code == 200
            assert set(doc) == {"traceEvents", "displayTimeUnit"}
            xs = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
            assert {"queue_wait", "device_dispatch",
                    "request /v1/*/predict"} <= xs
            threads = {e["args"]["name"] for e in doc["traceEvents"]
                       if e["ph"] == "M" and e["name"] == "thread_name"}
            # the inference worker's named thread shows up as its own track
            assert any(t.startswith("pi-m-v1-") for t in threads)

            assert json.loads(_get(base, "/debug/trace/missing0")[1]
                              )["error"]
            assert _get(base, "/debug/trace/missing0")[0] == 404
            # no recorder armed in this test
            assert json.loads(_get(base, "/debug/flight")[1]) == {
                "enabled": False}

            # exemplars: the latency histogram's bucket points back at the
            # trace — only under ?exemplars=1 / the OpenMetrics type
            code, plain, hdrs = _get(base, "/metrics")
            assert "# {" not in plain
            assert hdrs["Content-Type"].startswith("text/plain")
            code, om, hdrs = _get(base, "/metrics?exemplars=1")
            assert hdrs["Content-Type"].startswith(
                "application/openmetrics-text")
            assert 'trace_id="predsmoke1"' in om
        finally:
            gw.stop()

    def test_untraced_gateway_debug_disabled(self):
        gw = ServingGateway(port=0, seed=0).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            assert gw.tracer is None
            assert json.loads(_get(base, "/debug/requests")[1]) == {
                "enabled": False}
            assert _get(base, "/debug/trace/any1")[0] == 404
        finally:
            gw.stop()

    def test_env_armed_tracing(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TRACING", "1")
        gw = ServingGateway(port=0, seed=0)
        assert gw.tracer is not None
        gw2 = ServingGateway(port=0, seed=0, trace=False)
        assert gw2.tracer is None            # explicit False beats env


class TestTracedGenerate:
    def test_one_traced_generate_request(self):
        """ISSUE-12 tier-1 smoke: tiny gateway, ONE traced generate
        request, /debug/trace/<id> returns well-formed Chrome JSON with
        the slot-lifetime span names."""
        from test_generation import _lstm_net
        from deeplearning4j_tpu.generation import GenerationEngine

        eng = GenerationEngine(_lstm_net(units=12, seed=7), slots=2,
                               max_len=32)
        gw = ServingGateway(port=0, seed=0, trace=True).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            gw.register_generator("tg", eng)
            req = urllib.request.Request(
                base + "/v1/tg/generate",
                data=json.dumps({"prompt_ids": [1, 2, 3],
                                 "max_new_tokens": 4,
                                 "stream": True}).encode(),
                headers={"X-Trace-Id": "gensmoke01"})
            lines = [json.loads(l) for l in
                     urllib.request.urlopen(req, timeout=60) if l.strip()]
            assert lines[-1]["done"] and lines[-1]["n_tokens"] == 4

            code, raw, _ = _get(base, "/debug/trace/gensmoke01")
            doc = json.loads(raw)
            assert code == 200
            xs = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
            assert {"quota_check", "queue_wait", "prefill", "decode",
                    "request /v1/*/generate"} <= xs
            instants = {e["name"] for e in doc["traceEvents"]
                        if e["ph"] == "i"}
            assert {"admit", "retire"} <= instants
            (row,) = [t for t in json.loads(_get(base,
                                                 "/debug/requests")[1]
                                            )["completed"]
                      if t["trace_id"] == "gensmoke01"]
            assert row["disposition"] == "served"
            assert row["reason"] == "length"
        finally:
            gw.stop()


# ------------------------------------------------------ chaos propagation
class TestChaosTracePropagation:
    def test_crash_dump_names_the_trace(self, tmp_path):
        """Armed worker_crash + infer_crash chaos under a traced gateway
        with the recorder dumping: the postmortem bundle carries the
        victim's trace id, the shed reason, and the worker restart."""
        monitoring.enable()
        flight.configure(enabled=True, dump_dir=str(tmp_path),
                         min_dump_interval_s=0.0)
        gw = ServingGateway(port=0, seed=0, trace=True,
                            queue_timeout_s=0.001).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            gw.register_model("m", "v1", StubModel(delay=0.3),
                              warmup=False, batch_limit=1)
            with faults.injected("infer_crash:1") as plan:
                # the crash fires at dequeue, BEFORE the slow forward, so
                # this request fails fast with the fanned-back error
                code, body, _ = _post(
                    base, "/v1/m/predict", {"inputs": [[1.0, 2.0]]},
                    headers={"X-Trace-Id": "chaostrace1"})
                assert code == 500
                assert plan.injected["infer_crash"] == 1
            # a second request sheds on deadline: dispatched quickly (the
            # worker is idle) but its 300 ms forward outlives the 30 ms
            # budget, so gather times out and records the shed reason
            code, _, _ = _post(base, "/v1/m/predict",
                               {"inputs": [[1.0, 2.0]], "timeout_ms": 30},
                               headers={"X-Trace-Id": "chaostrace2"})
            assert code == 504
            rec = flight.recorder()
            deadline = time.monotonic() + 5
            while (not any(e["kind"] == "worker_crash" for e in rec.tail())
                    and time.monotonic() < deadline):
                time.sleep(0.01)
            kinds = {e["kind"] for e in rec.tail()}
            assert {"admit", "fault_injected", "worker_crash",
                    "shed"} <= kinds
            (shed,) = [e for e in rec.tail() if e["kind"] == "shed"]
            assert shed["reason"] == "deadline"
            assert shed["trace_id"] == "chaostrace2"
            assert rec.dumps        # worker_crash is a trigger kind
            bundle = json.loads(open(rec.dumps[0]).read())
            assert bundle["reason"] == "worker_crash"
            ev_kinds = [e["kind"] for e in bundle["events"]]
            assert "worker_crash" in ev_kinds
            traced = {e.get("trace_id") for e in bundle["events"]}
            assert "chaostrace1" in traced
            (crash,) = [e for e in bundle["events"]
                        if e["kind"] == "worker_crash"]
            assert crash["worker"].startswith("pi-m-v1")
            # the restart is also visible in recovery metrics
            assert ('outcome="worker_restarted"'
                    in monitoring.metrics_text())
            # and the victim's trace records its disposition
            row = gw.tracer.get("chaostrace1").summary()
            assert row["disposition"] == "error"
        finally:
            gw.stop()
            flight.reset()

    def test_unconfigured_chaos_lane_zero_instrument_calls(
            self, monkeypatch, monitoring_off):
        """With tracing, flight, and monitoring ALL unconfigured, a full
        predict round-trip performs zero tracer/recorder instrument calls
        (the spy-guarded half of the acceptance gate)."""
        assert not monitoring.enabled()
        assert flight.recorder() is None
        calls = []

        def spy(name):
            def record(self, *a, **kw):
                calls.append(name)
            return record

        monkeypatch.setattr(RequestTracer, "begin", spy("RequestTracer.begin"))
        monkeypatch.setattr(RequestTrace, "add_span",
                            spy("RequestTrace.add_span"))
        monkeypatch.setattr(RequestTrace, "event", spy("RequestTrace.event"))
        monkeypatch.setattr(FlightRecorder, "record",
                            spy("FlightRecorder.record"))
        monkeypatch.setattr(FlightRecorder, "dump", spy("FlightRecorder.dump"))
        monkeypatch.setattr(SpanTracer, "span", spy("SpanTracer.span"))
        monkeypatch.setattr(SpanTracer, "complete", spy("SpanTracer.complete"))
        monkeypatch.setattr(SpanTracer, "instant", spy("SpanTracer.instant"))
        gw = ServingGateway(port=0, seed=0).start()
        base = f"http://127.0.0.1:{gw.port}"
        try:
            assert gw.tracer is None
            gw.register_model("m", "v1", StubModel(), warmup=False)
            code, body, _ = _post(base, "/v1/m/predict",
                                  {"inputs": [[1.0, 2.0]]},
                                  headers={"X-Trace-Id": "ignored001"})
            assert code == 200 and body["outputs"] == [[1.0, 2.0]]
        finally:
            gw.stop()
        assert calls == []


# ------------------------------------------------- the fit path's span spine
def _mln(seed=5):
    from deeplearning4j_tpu.nn import (
        InputType, MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd

    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(lr=0.1)).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _cg(seed=3):
    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optimize import Sgd

    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(lr=0.1)).graph_builder()
            .add_inputs("in")
            .set_input_types(**{"in": InputType.feed_forward(4)})
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("o", OutputLayer(n_out=3, activation="softmax",
                                        loss="mcxent"), "d")
            .set_outputs("o").build())
    return ComputationGraph(conf).init()


def _xy(n=24, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


class TestSpanFields:
    def test_clock_parent_and_ids(self):
        """A span is stamped with ``time.time_ns()`` (the profiler's clock),
        names the span that caused it, and carries its identifiers."""
        tr = SpanTracer()
        before = time.time_ns()
        with tr.span("outer", step=7):
            with tr.span("inner", seq=2):
                pass
            tr.complete("measured", 0.001)
        after = time.time_ns()
        outer, inner, measured = (
            next(s for s in tr.spans() if s.name == n)
            for n in ("outer", "inner", "measured"))
        assert before <= outer.start_ns <= inner.start_ns
        assert inner.end_ns <= outer.end_ns <= after
        assert outer.parent is None and outer.args == {"step": 7}
        assert inner.parent == outer.id and inner.args == {"seq": 2}
        assert measured.parent == outer.id
        assert measured.end_ns - measured.start_ns == 1_000_000
        assert outer.thread == threading.current_thread().name
        assert isinstance(outer, tuple)
        # the Chrome export keeps microseconds from the tracer's start
        begin = next(e for e in tr.events() if e["ph"] == "B")
        assert begin["ts"] == pytest.approx(
            (outer.start_ns - tr.start_ns) / 1e3)
        validate_nesting(tr.events())

    def test_explicit_parent_crosses_threads(self):
        tr = SpanTracer()
        with tr.span("submit") as _:
            cause = tr.current()

            def work():
                with tr.span("worker", parent=cause):
                    pass

            t = threading.Thread(target=work)
            t.start()
            t.join()
        worker = next(s for s in tr.spans() if s.name == "worker")
        submit = next(s for s in tr.spans() if s.name == "submit")
        assert worker.parent == submit.id and worker.tid != submit.tid

    def test_enable_arms_the_ring_and_spans_outlive_disable(self):
        """One switch: ``enable()`` alone, no ``start_tracing()``."""
        assert monitoring.tracer() is None and monitoring.spans() == []
        monitoring.enable()
        ring = monitoring.tracer()
        assert ring is not None
        with monitoring.span("x", step=1):
            pass
        monitoring.enable()                       # a second call keeps the ring
        assert monitoring.tracer() is ring
        monitoring.disable()
        assert [s.name for s in monitoring.spans()] == ["x"]
        # stop_tracing hands the ring out; enabled, a fresh one takes its place
        monitoring.enable()
        assert monitoring.stop_tracing() is ring
        assert monitoring.tracer() not in (None, ring)

    def test_span_is_in_the_profilers_own_trace(self, tmp_path):
        """``SpanTracer.span`` enters a ``TraceAnnotation`` of the same name
        and arguments, and ``profiler.trace`` records it (host tracer level 1)
        on the clock ``profile_start_time`` anchors."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from deeplearning4j_tpu.profiler import profiler

        tr = SpanTracer()
        with profiler.trace(str(tmp_path)):
            with tr.span("fit.dispatch", step=3):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        (span,) = tr.spans()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        data = ProfileData.from_file(path)
        start = next(dict(p.stats)["profile_start_time"]
                     for p in data.planes if p.name == "Task Environment")
        found = [e for p in data.planes for line in p.lines
                 for e in line.events if e.name == "fit.dispatch"]
        assert len(found) == 1
        assert str(dict(found[0].stats)["step"]) == "3"
        # the annotation wraps the span: it starts first, within a millisecond
        assert 0 <= span.start_ns - (start + found[0].start_ns) < 1_000_000


class TestFitPathSpans:
    @pytest.mark.parametrize("entry", ["mln", "cg", "parallel_wrapper"])
    def test_three_steps_leave_one_span_of_each_phase_a_step(self, entry):
        from deeplearning4j_tpu.datasets.iterators import (
            ArrayDataSetIterator, AsyncPrefetchIterator,
        )

        monitoring.enable()
        model = _cg() if entry == "cg" else _mln()
        model.fit(*_xy(8))             # step 0: the spans below start at step 1
        model.score_value              # (drains it)
        first = model.step_count
        ring = monitoring.start_tracing()
        it = ArrayDataSetIterator(*_xy(24), batch_size=8)
        if entry == "parallel_wrapper":
            import jax

            from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper

            ParallelWrapper(model, DeviceMesh(devices=jax.devices()[:4])).fit(it)
        else:
            model.fit(AsyncPrefetchIterator(it))
        spans = ring.spans()
        validate_nesting(ring.events())
        steps = list(range(first, first + 3))
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        for name in ("fit.dispatch", "fit.drain", "fit.listeners"):
            assert [s.args["step"] for s in by_name[name]] == steps, name
        # one pull a step and the one that ends the epoch
        waits = by_name["fit.data_wait"]
        assert [(s.args["step"], s.args["seq"]) for s in waits] == [
            (first, 0), (first + 1, 1), (first + 2, 2), (first + 3, 3)]
        staged = by_name["prefetch.stage"]
        assert [s.args["seq"] for s in staged] == [0, 1, 2]
        fit_tid = waits[0].tid
        assert {s.tid for s in staged} != {fit_tid} and len({s.tid for s in staged}) == 1
        assert all(s.tid == fit_tid for n in ("fit.dispatch", "fit.drain")
                   for s in by_name[n])
        assert "fit.device_step" not in by_name
        # the step's spans in causal order: waited for, dispatched, drained
        for k, step in enumerate(steps):
            assert (waits[k].end_ns <= by_name["fit.dispatch"][k].start_ns
                    <= by_name["fit.drain"][k].start_ns
                    <= by_name["fit.listeners"][k].start_ns)
        reg = monitoring.registry()
        assert reg.get("dl4j_prefetch_staged_bytes_total").value == 24 * (4 + 3) * 4
        assert reg.get("dl4j_prefetch_stage_seconds").count == 3
        assert reg.get("dl4j_train_data_wait_seconds").count == 4

    @pytest.mark.parametrize("entry", ["mln", "cg"])
    def test_sync_mode_records_device_step(self, monkeypatch, entry):
        monkeypatch.setattr(env, "async_steps", 0)
        monitoring.enable()
        model = _cg() if entry == "cg" else _mln()
        model.fit(*_xy(8))
        model.fit(*_xy(8))
        names = [(s.name, s.args.get("step")) for s in monitoring.spans()
                 if s.name.startswith("fit.")]      # a cold cache adds "compile"
        assert names == [("fit.device_step", 0), ("fit.listeners", 0),
                         ("fit.device_step", 1), ("fit.listeners", 1)]

    def test_recompile_inside_a_span_is_its_child(self):
        """``monitoring/compile.py`` records a backend compile as an
        already-measured span whose parent is the span open on the thread."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.monitoring.compile import install_hooks

        install_hooks()
        monitoring.enable()
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            with monitoring.span("fit.dispatch", step=11):
                jax.jit(lambda a: jnp.tanh(a) * 3.25 + 11)(jnp.ones(7))
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
        spans = monitoring.spans()
        dispatch = next(s for s in spans if s.name == "fit.dispatch")
        compiles = [s for s in spans if s.name == "compile"]
        assert compiles and all(c.parent == dispatch.id for c in compiles)
        assert all(c.end_ns > c.start_ns for c in compiles)
        assert monitoring.registry().get("dl4j_compiles_total").value == len(compiles)


class TestLayerScopes:
    """``jax.named_scope`` on every layer of both cores: the lowered train
    step names forward operations ``jvp(<name>.<Class>)`` and backward ones
    ``transpose(jvp(<name>.<Class>))``, and the loss and the updater theirs."""

    @staticmethod
    def _lowered(model, args):
        if model._jit_cache.get("train") is None:
            model._jit_cache["train"] = model._make_train_step()
        return model._jit_cache["train"].lower(*args).as_text(debug_info=True)

    def test_multilayer_train_step(self):
        import jax
        import jax.numpy as jnp

        m = _mln()
        x, y = _xy(8)
        text = self._lowered(m, (m.params, m.state, m.opt_state,
                                 jnp.asarray(0, jnp.int32), jnp.asarray(x),
                                 jnp.asarray(y), jax.random.key(0), None, None))
        for want in ("jvp(0.DenseLayer)/dot_general",
                     "transpose(jvp(0.DenseLayer))/dot_general",
                     "jvp(1.OutputLayer)/dot_general",
                     "transpose(jvp(1.OutputLayer))/dot_general",
                     "jvp(loss)/", "transpose(jvp(loss))/", "/updater/sub"):
            assert want in text, want

    @pytest.mark.parametrize("remat", [False, True])
    def test_graph_train_step(self, remat):
        import jax
        import jax.numpy as jnp

        g = _cg()
        g.conf.remat = remat
        x, y = _xy(8)
        text = self._lowered(g, (g.params, g.state, g.opt_state,
                                 jnp.asarray(0, jnp.int32),
                                 {"in": jnp.asarray(x)}, {"o": jnp.asarray(y)},
                                 jax.random.key(0), None, None))
        for want in ("jvp(d.DenseLayer)", "transpose(jvp(d.DenseLayer))",
                     "jvp(o.OutputLayer)/dot_general",
                     "transpose(jvp(o.OutputLayer))/dot_general",
                     "jvp(loss)/", "transpose(jvp(loss))/", "/updater/sub"):
            assert want in text, want

    def test_guarded_and_clipped_step_names_guard_and_clip(self):
        import jax
        import jax.numpy as jnp

        m = _mln()
        m.conf.max_grad_norm = 1.0
        step = m._make_train_step(guarded=True)
        x, y = _xy(8)
        ctrl = jnp.zeros((4,), jnp.float32)
        text = step.lower(m.params, m.state, m.opt_state,
                          jnp.asarray(0, jnp.int32), jnp.asarray(x),
                          jnp.asarray(y), jax.random.key(0), None, None,
                          ctrl).as_text(debug_info=True)
        assert "/guard/" in text and "/clip/" in text and "/updater/" in text

    def test_pallas_impls_run_under_their_kernels_scope(self):
        from deeplearning4j_tpu.ops import registry
        import deeplearning4j_tpu.ops.pallas  # noqa: F401 (registers the impls)

        scopes = {impl.scope for op in ("dot_product_attention", "lrn",
                                        "gru_layer", "lstm_layer")
                  for impl in registry.get_op(op).impls
                  if impl.platform == "pallas"}
        assert scopes == {"flash_attention", "lrn", "fused_gru", "fused_lstm"}
