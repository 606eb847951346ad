"""``benchmarks/program_trace.py`` and the six readers on hand-made events and
spans: the anchor's shift, ``op_name`` parsed from a whole instruction, the
sums by kind of operation, the guard on unnamed operations, the check of
causality, the naming of idle gaps, the lead. And one compile for the chip
described, not attached (``v5e:2x2``): the weight-gradient fusions of a
two-convolution + BatchNorm ``ComputationGraph`` step carry the convolution's
scope, as the readers assume."""

import pytest

from benchmark_tiny import tiny_cell
from benchmarks import program_trace as pt
from benchmarks import trace_reduce
from benchmarks.layer_metrics import (
    conv_dot_roofline, dispatch_lead_ms, idle_named_pct, prefetch_stage_ms,
    step_conv_dot_ms, step_norm_ms,
)
from benchmarks.trace_reduce import Device, Op

ANCHOR = 1_790_000_000_000_000_000      # profile_start_time, Unix ns
STEP = 0.100                            # seconds a step on the device
FIT, FEED = 11, 22                      # thread ids
PEAKS = {"flops_per_s": {"float32": 1e12, "bfloat16": 1e12}}

CONV_FWD = ('%fusion.7 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput, calls=%fc, '
            'metadata={op_name="jit(train_step)/jvp(conv1.ConvolutionLayer)/conv_general_dilated" '
            'source_file="x.py" source_line=3}')
CONV_WGRAD = ('%negate_subtract_fusion.1 = f32[3,3]{1,0} fusion(f32[3,3]{1,0} %p), kind=kOutput, '
              'metadata={op_name="jit(train_step)/transpose(jvp(conv1.ConvolutionLayer))/'
              'conv_general_dilated"}')
DOT = '%fusion.9 = f32[8] fusion(), metadata={op_name="jit(train_step)/jvp(out.OutputLayer)/dot_general"}'
BN_FWD = '%f.2 = f32[8] fusion(), metadata={op_name="jit(train_step)/jvp(bn1.BatchNormalizationLayer)/div"}'
BN_BWD = ('%multiply_reduce_fusion.5 = f32[8] fusion(), metadata={op_name="jit(train_step)/'
          'transpose(jvp(bn1.BatchNormalizationLayer))/reduce_sum"}')
LN = '%f.3 = f32[8] fusion(), metadata={op_name="jit(train_step)/jvp(ln.LayerNormalizationLayer)/mul"}'
LRN = ('%f.4 = f32[8] fusion(), metadata={op_name="jit(train_step)/'
       'jvp(lrn.LocalResponseNormalizationLayer)/mul"}')
UPDATE = '%f.5 = f32[8] fusion(), metadata={op_name="jit(train_step)/updater/sub"}'
BARE = "%fusion.77 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc.7"
COPY_DONE = "%copy-done.620 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, f32[8]{0}, u32[]) %copy-start.620)"
SLICE_DONE = "%slice-done.3 = f32[4]{0} async-done(((f32[8]{0}), f32[4]{0}, u32[]) %slice-start.3)"


def test_every_metric_entered_in_conftest_has_its_reader_and_case_here(tested_in_their_own_file):
    from benchmarks import harness

    here = {r.__name__.rsplit(".", 1)[-1] for r in (
        conv_dot_roofline, dispatch_lead_ms, idle_named_pct, prefetch_stage_ms,
        step_conv_dot_ms, step_norm_ms)}
    assert {name for name, where in tested_in_their_own_file.items()
            if where == "test_program_trace.py"} == here
    assert here <= {m["name"] for m in harness.load_manifest()["per_layer"]}


def test_op_name_from_a_whole_instruction_then_from_a_stat():
    assert pt.op_name_of(CONV_FWD) == (
        "jit(train_step)/jvp(conv1.ConvolutionLayer)/conv_general_dilated")
    assert pt.op_name_of(BARE) is None
    assert pt.op_name_of("fusion.7", [("flops", 3), ("tf_op", "jit(f)/jvp(a.B)/dot_general")]) == (
        "jit(f)/jvp(a.B)/dot_general")
    assert pt.op_name_of('%x = f32[] add(), metadata={op_name=""}') is None


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One protobuf field: a varint for an int, length-delimited for bytes."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_op_name_from_the_metadata_records_stats_in_the_files_own_bytes(tmp_path):
    """An ``XSpace`` by hand, as the chip's runtime writes it: the event is
    named by its instruction without the metadata group, and ``tf_op`` sits in
    the stats of the event's metadata record, once as a string and once as a
    reference to a stat's name."""
    stat_names = {3: "flops", 7: "tf_op", 9: "jit(train_step)/updater/sub:"}
    stat_metadata = b"".join(
        _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, name)))
        for i, name in stat_names.items())
    conv = "%fusion.7 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput, calls=%fc"
    update = "%negate_subtract_fusion.69 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    bare = "%copy-done.620 = f32[8]{0:S(1)} copy-done(%copy-start.620)"
    conv_op = "jit(train_step)/jvp(conv1.ConvolutionLayer)/conv_general_dilated"

    def event_metadata(i, name, *stats):
        return _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, name) + b"".join(
            _field(5, stat) for stat in stats)))

    plane = (_field(1, 1) + _field(2, "/device:TPU:0")
             + _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1) + _field(2, 5)))
             + event_metadata(1, conv, _field(1, 3) + _field(3, 12345),
                              _field(1, 7) + _field(5, conv_op + ":"))
             + event_metadata(2, update, _field(1, 7) + _field(7, 9))
             + event_metadata(3, bare, _field(1, 3) + _field(3, 1))
             + stat_metadata)
    other = _field(1, 2) + _field(2, "/host:CPU") + event_metadata(1, "no stats here")
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, other))
    assert pt.metadata_op_names(str(path)) == {
        conv: conv_op, update: "jit(train_step)/updater/sub"}


def test_self_seconds_gives_nested_time_to_the_innermost_operation():
    outer, inner, later = (pt.NamedOp("o", "o", 0.0, 10.0), pt.NamedOp("i", "i", 2.0, 5.0),
                           pt.NamedOp("l", "l", 12.0, 13.0))
    own = {op.op_name: s for op, s in pt.self_seconds([later, inner, outer])}
    assert own == {"o": pytest.approx(7.0), "i": pytest.approx(3.0), "l": pytest.approx(1.0)}


def _trace(steps=8, lead=0.183, drain_lag=0.0026, gap=0.0002, unnamed_ms=0.0, moves_ms=0.0,
           clock_error=0.0, stage=0.053, before=3, after=3, drain_blocks=0.089):
    """A traced run by hand, shaped like the chip's. Device 0 runs ``steps``
    steps ``STEP`` long with ``gap`` idle between them; each is 40 ms of
    convolutions and a dense product, 30 ms under normalization scopes, the
    rest others. The host — whose ring also holds ``before`` steps from before
    the trace and ``after`` it has dispatched beyond it — dispatches each step
    ``lead`` before it starts (nearly two steps: the async window), blocks in
    its drain for ``drain_blocks`` and returns from it ``drain_lag`` after the
    step ends; the feed thread stages a batch a step. ``clock_error`` moves
    every span: a clock that is not the trace's."""
    layout = [(CONV_FWD, 0.020), (BN_FWD, 0.010), (DOT, 0.005), (BN_BWD, 0.015),
              (LN, 0.005), (CONV_WGRAD, 0.015), (LRN, 0.010),
              (UPDATE, 0.020 - gap - (unnamed_ms + moves_ms) * 1e-3)]
    if unnamed_ms:
        layout.append((BARE, unnamed_ms * 1e-3))
    if moves_ms:
        layout.append((COPY_DONE, moves_ms * 1e-3))
    ops, named, programs, spans = [], [], [], []

    def ns(seconds):
        return ANCHOR + int(round((seconds + clock_error) * 1e9))

    def span(name, a, b, tid, **args):
        spans.append((name, ns(a), ns(b), tid, "t", len(spans) + 1, None, args))

    for k in range(-before, steps + after):
        t0 = 1.0 + k * STEP
        t = t0 + STEP - gap
        if 0 <= k < steps:
            t = t0
            for name, dur in layout:
                ops.append(Op(trace_reduce.short_name(name), t, t + dur))
                named.append(pt.NamedOp(trace_reduce.short_name(name), pt.op_name_of(name),
                                        t, t + dur))
                t += dur
            programs.append(Op("jit_train_step(77)", t0, t))
        span("fit.data_wait", t0 - lead - 0.0005, t0 - lead, FIT, step=k, seq=k)
        span("fit.dispatch", t0 - lead, t0 - lead + 0.003, FIT, step=k)
        span("fit.drain", t - drain_blocks, t + drain_lag, FIT, step=k)
        span("fit.listeners", t + drain_lag, t + drain_lag + 0.0001, FIT, step=k)
        span("prefetch.stage", t0 + 0.010, t0 + 0.010 + stage, FEED, seq=k)
    red = trace_reduce.reduce_events([Device(0, ops, programs)], [], "train_step")
    return red, {0: named}, sorted(spans, key=lambda s: s[1])


def _ctx(red, named, spans):
    cell = tiny_cell("resnet50")
    return {"trace": red, "cell": cell, "chips": 1, "peaks": PEAKS,
            "module": __import__("benchmarks.configs.resnet50", fromlist=["x"]), "counters": {},
            "program_trace": pt.assemble(red, ANCHOR, named, spans)}


def test_the_anchor_puts_spans_on_the_traces_clock():
    red, named, spans = _trace()
    first = next(s for s in pt.shift_spans(spans, ANCHOR) if s.args == {"step": 0, "seq": 0})
    assert first.name == "fit.data_wait" and first.start == pytest.approx(1.0 - 0.183 - 0.0005)
    assert first.tid == FIT


def test_sums_by_kind_and_the_roofline():
    ctx = _ctx(*_trace())
    assert step_conv_dot_ms.read(ctx) == pytest.approx(40.0)       # forward, weight gradient, dense
    assert step_norm_ms.read(ctx) == pytest.approx(30.0)           # batch and layer norm, not LRN
    kinds = pt.scope_seconds(pt.load(ctx))
    assert kinds["other"] == pytest.approx(0.030 - 0.0002) and kinds["unnamed"] == 0
    cell = ctx["cell"]
    flops = ctx["module"].train_flops_per_sample(cell.config, cell.traffic) * cell.traffic["batch"]
    assert conv_dot_roofline.read(ctx) == pytest.approx(
        100 * flops / PEAKS["flops_per_s"][cell.config["compute_dtype"]] / 0.040)


@pytest.mark.parametrize("unnamed_ms, readable", [(1.5, True), (2.5, False)])
def test_unnamed_operations_past_two_percent_leave_the_scope_metrics_out(unnamed_ms, readable):
    ctx = _ctx(*_trace(unnamed_ms=unnamed_ms))
    values = [r.read(ctx) for r in (step_conv_dot_ms, step_norm_ms, conv_dot_roofline)]
    assert all(v is not None for v in values) if readable else values == [None, None, None]
    assert dispatch_lead_ms.read(ctx) is not None       # the shared clock does not depend on it


def test_the_compilers_own_copies_are_moves_not_unnamed():
    """``copy-done`` and a slice's ``async-done`` come from no line of the
    program: 3 % of the step in them (the chip reads 3.1 %) is no stale cache."""
    assert pt.NamedOp(trace_reduce.short_name(COPY_DONE), None, 0, 1).moves_data
    assert pt.NamedOp(trace_reduce.short_name(SLICE_DONE), None, 0, 1).moves_data
    assert not pt.NamedOp(trace_reduce.short_name(BARE), None, 0, 1).moves_data
    ctx = _ctx(*_trace(moves_ms=3.0))
    kinds = pt.scope_seconds(pt.load(ctx))
    assert kinds["moves"] == pytest.approx(0.003) and kinds["unnamed"] == 0
    assert step_conv_dot_ms.read(ctx) == pytest.approx(40.0)


def test_a_program_without_scopes_or_spans_gives_nothing_and_does_not_raise():
    red, named, _ = _trace()
    bare = {0: [pt.NamedOp(o.name, None if o.op_name is None else
                           o.op_name.replace("conv1.ConvolutionLayer", "jit(conv)")
                           .replace("bn1.BatchNormalizationLayer", "jit(bn)")
                           .replace("ln.LayerNormalizationLayer", "jit(ln)")
                           .replace("lrn.LocalResponseNormalizationLayer", "jit(lrn)")
                           .replace("out.OutputLayer", "jit(out)"), o.start, o.end)
                for o in named[0]]}
    ctx = _ctx(red, bare, None)
    assert step_norm_ms.read(ctx) is None
    assert step_conv_dot_ms.read(ctx) == pytest.approx(40.0)    # the primitive's name is JAX's own
    assert [r.read(ctx) for r in (prefetch_stage_ms, dispatch_lead_ms, idle_named_pct)] == [
        None, None, None]
    assert pt.named_gaps(ctx) is None


def test_lead_stage_and_causality_slack():
    ctx = _ctx(*_trace())
    c = pt.load(ctx).causality
    assert c.ok and c.steps == 8
    assert dispatch_lead_ms.read(ctx) == pytest.approx(180.0)       # 183 less the dispatch's own 3
    assert c.dispatch_to_start_s == pytest.approx(0.183)
    assert c.end_to_drain_s == pytest.approx((0.0026, 0.0026))
    assert prefetch_stage_ms.read(ctx) == pytest.approx(53.0)


def test_the_hosts_two_steps_of_lead_still_leave_one_match(monkeypatch):
    """The next dispatch also precedes the execution and its drain also
    follows it, a whole step late: the limit on the fetch rules it out.
    Without that limit two shifts are in causal order."""
    red, named, spans = _trace()
    assert pt.load(_ctx(red, named, spans)).causality.ok
    executions = [(p.start, p.end) for p in red.devices[0].programs]
    monkeypatch.setattr(pt, "FETCH_MAX_S", 10.0)        # nothing bounds the drain from above
    monkeypatch.setattr(pt, "FETCH_MAX_SHARE", 100.0)
    twice = pt.check_causality(executions, list(range(8)), pt.shift_spans(spans, ANCHOR))
    assert not twice.ok and twice.why.startswith("2 ways")


def test_a_host_that_comes_late_to_its_drains_is_in_causal_order_too():
    """A host-bound loop: the host dispatches a step 20 ms before it starts
    and comes to its drain 30 ms after it ended; the fetch returns at once."""
    red, named, spans = _trace(lead=0.020, drain_blocks=-0.030, drain_lag=0.0305)
    ctx = _ctx(red, named, spans)
    assert pt.load(ctx).causality.ok and dispatch_lead_ms.read(ctx) == pytest.approx(17.0)


@pytest.mark.parametrize("clock_error", [-0.003, -0.05, 0.009, 0.06])
def test_a_clock_that_is_not_the_traces_fails_causality(clock_error):
    """Spans 3 ms or 50 ms early (a drain returning before its execution
    ends), 9 ms or 60 ms late (a drain returning longer after its execution
    than a fetch takes): no shift of the two lists puts every step in causal
    order, and the shared-clock readers return nothing."""
    ctx = _ctx(*_trace(clock_error=clock_error))
    assert not pt.load(ctx).causality.ok
    assert [r.read(ctx) for r in (prefetch_stage_ms, dispatch_lead_ms, idle_named_pct)] == [
        None, None, None]
    assert step_conv_dot_ms.read(ctx) == pytest.approx(40.0)    # needs no shared clock


def test_a_clock_off_by_a_whole_step_passes_unseen():
    """What the check cannot tell: every span a step late matches every
    execution to the dispatch before its own, in causal order."""
    ctx = _ctx(*_trace(clock_error=STEP))
    assert pt.load(ctx).causality.ok
    assert dispatch_lead_ms.read(ctx) == pytest.approx(180.0)


def test_idle_gaps_take_the_name_of_the_span_over_them():
    red, named, spans = _trace(gap=0.001, drain_lag=0.0004, drain_blocks=0.010, stage=0.030)
    ctx = _ctx(red, named, spans)
    gaps = pt.named_gaps(ctx)
    assert len(gaps) == 7 and {name for name, _ in gaps} == {"fit.drain"}
    assert all(seconds == pytest.approx(0.001) for _, seconds in gaps)
    # the drain returns 0.4 ms into the 1 ms gap, the listeners take 0.1 ms:
    # half of the idle time lies under a span
    assert idle_named_pct.read(ctx) == pytest.approx(50.0)
    assert pt.name_gaps([(9.5, 9.6)], pt.load(ctx).spans) == [(pt.NO_SPAN, pytest.approx(0.1), 0.0)]
    # with no span of the fit loop's thread over a gap, the feed thread's names it
    feed_only = [s for s in pt.load(ctx).spans if s.tid == FEED or s.name == "fit.dispatch"]
    t0 = 1.0 + 2 * STEP
    assert pt.name_gaps([(t0 + 0.020, t0 + 0.021)], feed_only)[0][0] == "prefetch.stage"


# ------------------------------------------------- compiled for the chip, unattached
@pytest.fixture(scope="module")
def one_chip():
    """One chip of a v5e 2x2 that is described, not attached; the compile
    cache is off meanwhile (such a program can be written to it, not read)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_v5e_weight_gradient_fusions_carry_the_convolutions_scope(one_chip):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.layers import (
        ActivationLayer, BatchNormalizationLayer, ConvolutionLayer, GlobalPoolingLayer,
        OutputLayer,
    )
    from deeplearning4j_tpu.optimize import Nesterovs

    conf = (NeuralNetConfiguration.builder().seed(1).updater(Nesterovs(lr=0.1, momentum=0.9))
            .graph_builder().add_inputs("in")
            .set_input_types(**{"in": InputType.convolutional(32, 32, 16)})
            .add_layer("conv1", ConvolutionLayer(n_out=128, kernel=(3, 3), padding="same",
                                                 has_bias=False), "in")
            .add_layer("bn1", BatchNormalizationLayer(), "conv1")
            .add_layer("relu1", ActivationLayer(activation="relu"), "bn1")
            .add_layer("conv2", ConvolutionLayer(n_out=128, kernel=(3, 3), padding="same",
                                                 has_bias=False), "relu1")
            .add_layer("gap", GlobalPoolingLayer(pooling_type="avg"), "conv2")
            .add_layer("out", OutputLayer(n_out=10, activation="softmax", loss="mcxent"), "gap")
            .set_outputs("out").build())
    graph = ComputationGraph(conf).init()

    def shape(a, dtype=None):
        return jax.ShapeDtypeStruct(getattr(a, "shape", a), dtype or a.dtype, sharding=one_chip)

    args = (*jax.tree.map(shape, (graph.params, graph.state, graph.opt_state)),
            shape((), jnp.int32), {"in": shape((64, 32, 32, 16), jnp.float32)},
            {"out": shape((64, 10), jnp.float32)}, shape((), jax.random.key(0).dtype), None, None)
    text = graph._make_train_step().lower(*args).compile().as_text()
    names = {}
    for line in text.splitlines():
        line = line.strip()
        if line.startswith(("%", "ROOT %")) and " fusion(" in line:
            names[line.removeprefix("ROOT ").split(" = ")[0]] = pt.op_name_of(line)
    by_scope = {}
    for instruction, op_name in names.items():
        if op_name is not None:
            by_scope.setdefault(op_name.split("jit(train_step)/")[-1], []).append(instruction)
    for conv in ("conv1", "conv2"):
        assert f"jvp({conv}.ConvolutionLayer)/conv_general_dilated" in by_scope, sorted(by_scope)
        # the weight gradient, whatever XLA fused onto it (the Nesterov
        # update: the fusion is named after the update's arithmetic)
        assert f"transpose(jvp({conv}.ConvolutionLayer))/conv_general_dilated" in by_scope
    assert any(pt.NORM_SCOPE.search(k) and k.startswith("jvp(") for k in by_scope)
    assert any(pt.NORM_SCOPE.search(k) and k.startswith("transpose(") for k in by_scope)
    assert any(k.startswith("updater/") for k in by_scope)
    assert all(pt.CONV_DOT.search(k) is None for k in by_scope if k.startswith("updater/"))
