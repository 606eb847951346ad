"""Monitoring-layer tests: metrics registry semantics, Prometheus
exposition, Chrome-trace span tracer, /metrics on both HTTP servers,
fit-loop instrumentation, and the zero-overhead (default-off) guard.

Reference analog: the reference's observability tests cover
StatsListener -> StatsStorage -> UIServer; this suite covers the pull-model
half the reference lacked (registry + scrape endpoints) plus the host-side
span timeline.
"""

import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import (
    Counter, Gauge, Histogram, MetricsRegistry, SpanTracer, validate_nesting,
)
from deeplearning4j_tpu.nn import (
    InputType, MultiLayerNetwork, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize import Sgd


@pytest.fixture(autouse=True)
def _fresh_monitoring():
    """Each test gets a fresh registry/tracer and env-default enablement."""
    monitoring.reset()
    yield
    monitoring.reset()


def _model(seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(lr=0.1)).list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _data(n=16, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


class TestRegistry:
    def test_counter_inc_and_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "things")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("g", "a gauge")
        g.set(2.5)
        g.inc()
        g.dec(0.5)
        assert g.value == pytest.approx(3.0)

    def test_labels_independent_children(self):
        reg = MetricsRegistry()
        c = reg.counter("req_total", "requests", labels=("route",))
        c.labels(route="/a").inc(2)
        c.labels(route="/b").inc(5)
        assert c.labels(route="/a").value == 2
        assert c.labels(route="/b").value == 5
        # wrong label names fail loud
        with pytest.raises(ValueError):
            c.labels(path="/a")
        # labeled family does not proxy bare ops
        with pytest.raises(ValueError):
            c.inc()

    def test_histogram_fixed_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        cum, s, c = h._only().snapshot()
        assert cum == [1, 3, 4, 5]          # cumulative incl. +Inf
        assert c == 5
        assert s == pytest.approx(56.05)

    def test_registration_idempotent_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        a = reg.counter("n_total", "n")
        assert reg.counter("n_total") is a
        with pytest.raises(ValueError):
            reg.gauge("n_total")
        with pytest.raises(ValueError):
            reg.counter("n_total", labels=("x",))

    def test_thread_safety_concurrent_increments(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        h = reg.histogram("h_seconds", buckets=(0.5,))
        g = reg.gauge("g")
        n_threads, per = 8, 500

        def work():
            for i in range(per):
                c.inc()
                h.observe(i % 2)
                g.inc()

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per
        assert h.count == n_threads * per
        assert g.value == n_threads * per
        cum, _, cnt = h._only().snapshot()
        assert cum[-1] == cnt == n_threads * per


class TestExposition:
    def test_text_format(self):
        reg = MetricsRegistry()
        reg.counter("jobs_total", "jobs done").inc(3)
        reg.gauge("depth", "queue depth").set(7)
        reg.histogram("lat_seconds", "latency",
                      buckets=(0.1, 1.0)).observe(0.2)
        text = reg.exposition()
        assert "# HELP jobs_total jobs done" in text
        assert "# TYPE jobs_total counter" in text
        assert "\njobs_total 3\n" in text
        assert "# TYPE depth gauge" in text
        assert "\ndepth 7\n" in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.1"} 0' in text
        assert 'lat_seconds_bucket{le="1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_sum" in text
        assert "lat_seconds_count 1" in text

    def test_labels_rendered_and_escaped(self):
        reg = MetricsRegistry()
        c = reg.counter("r_total", "r", labels=("route",))
        c.labels(route='/a"b\\c').inc()
        text = reg.exposition()
        assert 'r_total{route="/a\\"b\\\\c"} 1' in text

    def test_unexercised_families_export_zero(self):
        # no-label families create their child eagerly, so a scrape shows
        # the metric at 0 rather than omitting it
        reg = MetricsRegistry()
        reg.counter("never_total", "never incremented")
        assert "\nnever_total 0\n" in reg.exposition()


class TestSpanTracer:
    def test_nesting_and_json_validity(self, tmp_path):
        tr = SpanTracer()
        with tr.span("outer", step=1):
            with tr.span("inner"):
                pass
            with tr.span("inner2"):
                pass
        path = tmp_path / "trace.json"
        tr.save(path)
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        validate_nesting(evs)
        be = [(e["ph"], e["name"]) for e in evs if e["ph"] in "BE"]
        assert be == [("B", "outer"), ("B", "inner"), ("E", "inner"),
                      ("B", "inner2"), ("E", "inner2"), ("E", "outer")]
        # timestamps are monotone within the thread
        ts = [e["ts"] for e in evs if e["ph"] in "BE"]
        assert ts == sorted(ts)
        outer = next(e for e in evs if e["ph"] == "B" and e["name"] == "outer")
        assert outer.get("args") == {"step": 1}

    def test_thread_aware_tids(self):
        tr = SpanTracer()

        def work():
            with tr.span("worker"):
                pass

        t = threading.Thread(target=work)
        with tr.span("main"):
            t.start()
            t.join()
        tids = {e["tid"] for e in tr.events() if e["ph"] in "BE"}
        assert len(tids) == 2
        validate_nesting(tr.events())

    def test_unbalanced_detected(self):
        bad = [{"name": "a", "ph": "B", "tid": 1},
               {"name": "b", "ph": "E", "tid": 1}]
        with pytest.raises(ValueError):
            validate_nesting(bad)


class TestFitInstrumentation:
    def test_fit_populates_registry_and_trace(self, tmp_path):
        """Async dispatch (the default) splits the old device_step phase
        into dispatch (enqueue) + drain (deferred fetch); fit() drains every
        in-flight step by epoch end, so the counts still match 1:1."""
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

        monitoring.enable()
        monitoring.start_tracing()
        model = _model()
        x, y = _data(16)
        it = ArrayDataSetIterator(x, y, batch_size=8)
        model.fit(it, epochs=3)

        reg = monitoring.registry()
        assert reg.get("dl4j_train_iterations_total").value == 6
        assert reg.get("dl4j_train_dispatch_seconds").count == 6
        assert reg.get("dl4j_train_drain_seconds").count == 6
        # one data-wait observation per pull, incl. the terminating one
        assert reg.get("dl4j_train_data_wait_seconds").count >= 6
        assert np.isfinite(reg.get("dl4j_train_score").value)
        text = monitoring.metrics_text()
        assert "dl4j_train_dispatch_seconds_bucket" in text
        assert "dl4j_train_data_wait_seconds_bucket" in text

        path = tmp_path / "fit_trace.json"
        monitoring.stop_tracing(str(path))
        doc = json.load(open(path))        # acceptance: json.loads cleanly
        validate_nesting(doc["traceEvents"])
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"fit.data_wait", "fit.dispatch", "fit.drain",
                "fit.listeners"} <= names

    def test_fit_sync_mode_keeps_device_step_accounting(self, monkeypatch):
        """DL4J_TPU_ASYNC_STEPS=0 restores the original sync accounting:
        the host fetch is timed inside device_step, no dispatch/drain."""
        from deeplearning4j_tpu.common.env import env
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

        monkeypatch.setenv("DL4J_TPU_ASYNC_STEPS", "0")
        env.reload()
        try:
            monitoring.enable()
            model = _model()
            x, y = _data(16)
            model.fit(ArrayDataSetIterator(x, y, batch_size=8), epochs=3)
            reg = monitoring.registry()
            assert reg.get("dl4j_train_iterations_total").value == 6
            assert reg.get("dl4j_train_device_step_seconds").count == 6
            assert reg.get("dl4j_train_dispatch_seconds").count == 0
            assert reg.get("dl4j_train_drain_seconds").count == 0
        finally:
            monkeypatch.delenv("DL4J_TPU_ASYNC_STEPS")
            env.reload()

    def test_graph_fit_batch_instrumented(self):
        monitoring.enable()
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Sgd(lr=0.1)).graph_builder()
                .add_inputs("in")
                .set_input_types(**{"in": InputType.feed_forward(4)})
                .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
                .add_layer("o", OutputLayer(n_out=3, activation="softmax",
                                            loss="mcxent"), "d")
                .set_outputs("o").build())
        net = ComputationGraph(conf).init()
        x, y = _data(8)
        for _ in range(3):
            net.fit_batch((x, y))
        # async default: 3 dispatches; reading score_value drains the rest
        assert np.isfinite(net.score_value)
        reg = monitoring.registry()
        assert reg.get("dl4j_train_iterations_total").value == 3
        assert reg.get("dl4j_train_dispatch_seconds").count == 3
        assert reg.get("dl4j_train_drain_seconds").count == 3


class TestZeroOverheadGuard:
    """Tier-1 guard: with monitoring disabled (the default), the fit loop
    makes NO registry/tracer calls — observability can never silently
    regress training throughput."""

    @pytest.mark.parametrize("entry", ["fit", "prefetch", "parallel_wrapper"])
    def test_disabled_fit_touches_no_instruments(self, monkeypatch,
                                                 monitoring_off, entry):
        """``fit`` on the bare iterator, behind the prefetch thread (the
        ``prefetch.stage`` span's place) and under ``ParallelWrapper`` (which
        stages through its own prefetch thread)."""
        assert not monitoring.enabled() and monitoring.tracer() is None
        calls = []

        def spy(name):
            def record(self, *a, **k):
                calls.append(name)
            return record

        monkeypatch.setattr(Counter, "inc", spy("Counter.inc"))
        monkeypatch.setattr(Gauge, "set", spy("Gauge.set"))
        monkeypatch.setattr(Gauge, "inc", spy("Gauge.inc"))
        monkeypatch.setattr(Histogram, "observe", spy("Histogram.observe"))
        monkeypatch.setattr(SpanTracer, "span", spy("SpanTracer.span"))
        monkeypatch.setattr(SpanTracer, "complete", spy("SpanTracer.complete"))
        monkeypatch.setattr(monitoring._FitMonitor, "stage", spy("stage"))
        monkeypatch.setattr(monitoring._FitMonitor, "wrap_batches",
                            spy("wrap_batches"))

        model = _model()
        x, y = _data(16)
        from deeplearning4j_tpu.datasets.iterators import (
            ArrayDataSetIterator, AsyncPrefetchIterator,
        )

        it = ArrayDataSetIterator(x, y, batch_size=8)
        if entry == "fit":
            model.fit(it, epochs=2)
        elif entry == "prefetch":
            model.fit(AsyncPrefetchIterator(it), epochs=2)
        else:
            from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper

            mesh = DeviceMesh(devices=jax.devices()[:4])
            ParallelWrapper(model, mesh).fit(it, epochs=2)
        assert model.step_count == 4
        assert calls == []

    def test_enable_disable_round_trip(self):
        assert monitoring.fit_monitor() is None
        monitoring.enable()
        assert monitoring.fit_monitor() is not None
        monitoring.disable()
        assert monitoring.fit_monitor() is None


class TestMetricsEndpoints:
    def test_ui_server_metrics_route(self):
        from deeplearning4j_tpu.ui import UIServer

        monitoring.registry().counter("ui_seen_total", "seen").inc(2)
        server = UIServer(port=0).start()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            with urllib.request.urlopen(url) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                body = resp.read().decode()
        finally:
            server.stop()
        assert "ui_seen_total 2" in body

    def test_model_server_metrics_and_request_instruments(self):
        monitoring.enable()
        from deeplearning4j_tpu.serving import ModelServer

        server = ModelServer(_model(), port=0).start()
        try:
            url = f"http://127.0.0.1:{server.port}"
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps(
                    {"inputs": [[0.1, 0.2, 0.3, 0.4], [1, 2, 3, 4]]}
                ).encode(),
                headers={"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req).read())
            assert len(out["outputs"]) == 2
            body = urllib.request.urlopen(url + "/metrics").read().decode()
        finally:
            server.stop()
        # request latency histogram labeled by route, batch-size dist,
        # queue/in-flight gauges all scraped from the serving server
        assert 'dl4j_serving_request_seconds_bucket{route="/predict"' in body
        assert "dl4j_serving_batch_size_bucket" in body
        assert "dl4j_serving_in_flight" in body
        assert "dl4j_serving_queue_depth" in body
        reg = monitoring.registry()
        assert reg.get("dl4j_serving_batch_size").count >= 1
        assert reg.get("dl4j_serving_in_flight").value == 0  # all drained

    def test_knn_server_also_serves_metrics(self):
        from deeplearning4j_tpu.serving import KNNServer

        pts = np.asarray([[0.0, 0.0], [1.0, 1.0]], np.float32)
        server = KNNServer(pts, port=0, backend="brute").start()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            with urllib.request.urlopen(url) as resp:
                assert resp.status == 200
        finally:
            server.stop()


class TestLocalSgdMetrics:
    def test_rounds_sync_and_dropped_rows(self):
        monitoring.enable()
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer,
        )

        x, y = _data(200, rng_seed=1)
        it = ArrayDataSetIterator(x, y, batch_size=64)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(4).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), _model(seed=7), tm)
        with pytest.warns(UserWarning, match="dropped"):
            spark.fit(it, epochs=4)   # 800 rows: 12 global batches, 3 rounds
        reg = monitoring.registry()
        assert reg.get("dl4j_localsgd_rounds_total").value == 3
        assert reg.get("dl4j_localsgd_sync_seconds").count == 3
        # 800 - 3 rounds * 4 batches * 64 rows = 32 tail rows dropped
        assert reg.get("dl4j_localsgd_dropped_rows_total").value == 32
        text = monitoring.metrics_text()
        assert "dl4j_localsgd_sync_seconds_bucket" in text
        assert "dl4j_localsgd_dropped_rows_total 32" in text


class TestMetricsListener:
    def test_listener_bridges_without_env_flag(self):
        # explicit attachment IS the opt-in: works while enabled() is False
        assert not monitoring.enabled()
        from deeplearning4j_tpu.monitoring import MetricsListener

        model = _model()
        model.set_listeners(MetricsListener(sysmetrics_every=2))
        x, y = _data(16)
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

        model.fit(ArrayDataSetIterator(x, y, batch_size=8), epochs=2)
        reg = monitoring.registry()
        assert np.isfinite(reg.get("dl4j_train_score").value)
        # N iterations produce N-1 gaps per epoch (timer resets at epoch end)
        assert reg.get("dl4j_train_iteration_seconds").count == 2
        assert reg.get("dl4j_train_epochs_total").value == 2
        assert reg.get("dl4j_host_rss_mb").value > 0


class TestCheckpointMetrics:
    def test_save_duration_and_bytes(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        monitoring.enable()
        from deeplearning4j_tpu.util.checkpoints import TrainingCheckpointer

        model = _model()
        ckpt = TrainingCheckpointer(tmp_path / "ck", keep_last=2,
                                    async_save=False)
        try:
            ckpt.save(1, model)
            ckpt.wait()
        finally:
            ckpt.close()
        reg = monitoring.registry()
        assert reg.get("dl4j_checkpoint_saves_total").value == 1
        assert reg.get("dl4j_checkpoint_save_seconds").count == 1
        assert reg.get("dl4j_checkpoint_bytes_total").value > 0


class TestOneSourceOfTruth:
    """Acceptance shape: after fit + serving + local-SGD, BOTH servers'
    /metrics scrapes carry the step/data-wait timings, serving latency +
    batch-size distribution, and local-SGD sync + dropped-rows counter."""

    def test_both_servers_scrape_all_subsystems(self):
        monitoring.enable()
        from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        from deeplearning4j_tpu.parallel.spark import (
            ParameterAveragingTrainingMaster, SparkDl4jMultiLayer,
        )
        from deeplearning4j_tpu.serving import ModelServer
        from deeplearning4j_tpu.ui import UIServer

        model = _model()
        x, y = _data(16)
        model.fit(ArrayDataSetIterator(x, y, batch_size=8), epochs=1)

        x2, y2 = _data(200, rng_seed=2)
        tm = (ParameterAveragingTrainingMaster.Builder()
              .batch_size_per_worker(8).averaging_frequency(2).build())
        spark = SparkDl4jMultiLayer(DeviceMesh(data=8), _model(seed=9), tm)
        with pytest.warns(UserWarning, match="dropped"):
            spark.fit(ArrayDataSetIterator(x2, y2, batch_size=64), epochs=1)

        expected = [
            "dl4j_train_device_step_seconds_bucket",
            "dl4j_train_data_wait_seconds_bucket",
            "dl4j_serving_request_seconds_bucket",
            "dl4j_serving_batch_size_bucket",
            "dl4j_localsgd_sync_seconds_bucket",
            "dl4j_localsgd_dropped_rows_total",
        ]
        model_srv = ModelServer(model, port=0).start()
        ui_srv = UIServer(port=0).start()
        try:
            url = f"http://127.0.0.1:{model_srv.port}"
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"inputs": [[0.0, 0.0, 0.0, 0.0]]}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req).read()
            serving_scrape = urllib.request.urlopen(
                url + "/metrics").read().decode()
            ui_scrape = urllib.request.urlopen(
                f"http://127.0.0.1:{ui_srv.port}/metrics").read().decode()
        finally:
            model_srv.stop()
            ui_srv.stop()
        for name in expected:
            assert name in serving_scrape, f"serving scrape missing {name}"
            assert name in ui_scrape, f"ui scrape missing {name}"
