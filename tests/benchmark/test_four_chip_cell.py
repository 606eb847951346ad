"""The four-chip cell ``resnet50_pw4_b1024`` and the collectives layer, on the
CPU: its files, ``collective_exposed_pct`` and every other reader on hand-made
traces of four devices that start each step a little apart, the one-chip
readings pinned to what the parent's code gave for the same events, the idle
gaps' names from the spine, and the generator at batch 1024."""

import importlib
import json

import numpy as np
import pytest

from benchmark_tiny import ROOT, devices_for, tiny_cell
from benchmarks import harness, program_trace as pt, reference_train, trace_reduce as tr, traffic_gen
from benchmarks.drivers import fit
from benchmarks.layer_metrics import collective_exposed_pct
from benchmarks.trace_reduce import Device, Op

CELL, ONE_CHIP_CELL = "resnet50_pw4_b1024", "resnet50_fit_b256"
MANIFEST = harness.load_manifest()
ANCHOR = 1_790_000_000_000_000_000      # profile_start_time, Unix ns
STEP, GAP = 0.100, 0.0002               # seconds a step on the device, idle between two
FIT, FEED = 11, 22                      # thread ids
PEAKS = {"flops_per_s": {"bfloat16": 197e12}}
TRAIN = "jit(train_step)/"
CONV = TRAIN + "jvp(conv1.ConvolutionLayer)/conv_general_dilated"
WGRAD = TRAIN + "transpose(jvp(conv1.ConvolutionLayer))/conv_general_dilated"
BN_SUM = TRAIN + "jvp(bn1.BatchNormalizationLayer)/reduce_sum"
BN_BWD = TRAIN + "transpose(jvp(bn1.BatchNormalizationLayer))/mul"
# one step of one device: (name, op_name, hlo_category, seconds, only across chips)
LAYOUT = [
    ("fusion.7 fusion", CONV, "convolution fusion", 0.030, False),
    ("all-reduce.418 all-reduce", BN_SUM, "all-reduce", 0.002, True),       # BatchNorm's statistics
    ("fusion.9 fusion", BN_BWD, "loop fusion", 0.015, False),
    ("fusion.11 fusion", WGRAD, "convolution fusion", 0.030, False),
    ("all-reduce-start.1 all-reduce-start", WGRAD, None, 0.0005, True),     # the gradients, behind
    ("fusion.13 fusion", TRAIN + "updater/sub", "loop fusion", 0.010, False),   # the update
    ("all-reduce-done.1 all-reduce-done", WGRAD, None, 0.003, True),
    ("copy-done.5 copy-done", None, "copy-done", 0.002, False),
]
EXPOSED_S = 0.002 + 0.0005 + 0.003


def synthetic(chips: int, steps: int = 12, skew: float = 0.00004, lead: float = 0.183):
    """A traced run by hand: ``chips`` devices run ``steps`` steps of ``LAYOUT``,
    device *d* starting each ``d * skew`` after device 0 (further apart than the
    idle gap between two steps); the host dispatches each step ``lead`` ahead,
    returns from its drain 2 ms after the last device ends it, and a feed
    thread stages a batch a step."""
    layout = [row for row in LAYOUT if chips > 1 or not row[4]]
    devices, named, spans = [], {}, []

    def span(name, a, b, tid, **args):
        spans.append((name, ANCHOR + round(a * 1e9), ANCHOR + round(b * 1e9), tid, "t",
                      len(spans) + 1, None, args))

    for d in range(chips):
        ops, programs, named[d] = [], [], []
        for k in range(steps):
            t = t0 = 1.0 + k * STEP + d * skew
            for name, op_name, category, seconds, _ in layout:
                ops.append(Op(name, t, t + seconds))
                named[d].append(pt.NamedOp(name, op_name, t, t + seconds, category))
                t += seconds
            programs.append(Op("jit_train_step(77)", t0, t0 + STEP - GAP))
        devices.append(Device(d, ops, programs))
    for k in range(-3, steps + 3):
        t0, end = 1.0 + k * STEP, 1.0 + (k + 1) * STEP - GAP + (chips - 1) * skew
        span("fit.data_wait", t0 - lead - 0.0005, t0 - lead, FIT, step=k, seq=k)
        span("fit.dispatch", t0 - lead, t0 - lead + 0.003, FIT, step=k)
        span("fit.drain", end - 0.080, end + 0.002, FIT, step=k)
        span("fit.listeners", end + 0.002, end + 0.0021, FIT, step=k)
        span("prefetch.stage", t0 + 0.010, t0 + 0.063, FEED, seq=k)
    return tr.reduce_events(devices, [], "train_step"), named, sorted(spans, key=lambda s: s[1])


def context(cell_name: str, red, named, spans, counters=None) -> dict:
    cell = harness.load_cell(cell_name)
    return {"trace": red, "cell": cell, "chips": len(red.devices), "peaks": PEAKS,
            "module": importlib.import_module(cell.config["reference"]),
            "counters": counters or {"data_wait_s": 0.0012, "traced_host_s": 1.5},
            "program_trace": pt.assemble(red, ANCHOR, named, spans)}


# ------------------------------------------------------------------ the cell
def test_the_four_chip_cell_and_its_three_files():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("resnet50", "fit_b1024", 4)
    for path in ("benchmarks/cells/resnet50_pw4_b1024.json", "benchmarks/traffic/fit_b1024.json",
                 "benchmarks/configs/resnet50.json"):
        assert (ROOT / path).is_file()
    cell = harness.load_cell(CELL)
    assert cell.traffic["batch"] == 1024 and cell.traffic["batch"] % cell.chips == 0
    assert set(cell.limits) == set(harness.load_cell(ONE_CHIP_CELL).limits)
    assert {m["name"] for m in cell.per_layer} == {m["name"] for m in MANIFEST["per_layer"]}
    assert "collective_exposed_pct" not in {
        m["name"] for m in harness.load_cell(ONE_CHIP_CELL).per_layer}


def test_at_most_a_quarter_of_the_cells_and_never_fewer_than_one_may_ask_for_four_chips():
    cells = MANIFEST["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert four == [CELL] and len(four) <= max(1, len(cells) // 4)


def test_the_traced_stretch_is_cut_as_the_one_chip_cells():
    ours, theirs = (json.loads((ROOT / "benchmarks/traffic" / f"{n}.json").read_text())
                    for n in ("fit_b1024", "fit_b256"))
    assert {k: v for k, v in ours.items() if k != "batch"} == {
        k: v for k, v in theirs.items() if k != "batch"}


def test_the_generator_at_batch_1024_gives_four_distinct_batches_of_the_stated_shapes():
    cell = harness.load_cell(CELL)
    pool = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, 2 ** 31 + 77)
    assert len(pool) == 4
    for x, y in pool:
        assert x.shape == (1024, 224, 224, 3) and x.dtype.name == "bfloat16"
        assert y.shape == (1024, 1000) and y.dtype == np.float32 and (y.sum(axis=1) == 1).all()
    heads = [x[:2].tobytes() for x, _ in pool]
    assert len(set(heads)) == 4
    assert pool[0][0][0].tobytes() != pool[0][0][1].tobytes()      # rows differ too


# --------------------------------------- the faults ``calibrate.py`` plants
@pytest.fixture(scope="module")
def tiny_program():
    """The tiny cell over four of the CPU's devices, set up and freed as
    ``calibrate.py`` does, with its reference's readings."""
    cell = tiny_cell("resnet50", chips=4)
    prog = fit.Program(cell, 2 ** 31 + 9, devices_for(cell))
    prog.free()
    return cell, prog, prog.reference()


@pytest.mark.parametrize("share", [0.5, 0.25], ids=["fault_half_batch", "fault_no_exchange"])
def test_a_fault_planted_in_the_reference_over_four_devices_is_not_correct(tiny_program, share):
    """The planted batch has to lie over the devices as the batch did: the
    reference's step is compiled for that layout and takes no other."""
    cell, prog, reference = tiny_program
    planted = prog.reference(keep_fraction=share)
    verdict = reference_train.compare(planted, reference, cell.limits)
    assert not verdict["correct"]
    assert verdict["checks"]["grad_norm_gap"]["value"] > 0.1


# ------------------------------------------------------- collective_exposed_pct
def _two_devices(collective, name="all-reduce.3 all-reduce", category=None):
    """Ten steps on two devices, each one convolution from 0 to 80 ms; device 1
    also runs a collective over ``collective`` (start, end within the step)."""
    starts = [i / 10 for i in range(10)]
    devices, named = [], {}
    for d in range(2):
        rows = [("fusion.1 fusion", CONV, "convolution fusion", 0.0, 0.080)]
        if d == 1 and collective is not None:
            rows.append((name, BN_SUM, category, *collective))
        named[d] = [pt.NamedOp(n, op, s + a, s + b, c) for s in starts for n, op, c, a, b in rows]
        devices.append(Device(d, [Op(o.name, o.start, o.end) for o in named[d]],
                              [Op("jit_train_step(1)", s, s + 0.1) for s in starts]))
    return context(CELL, tr.reduce_events(devices, [], "train_step"), named, None)


@pytest.mark.parametrize("collective, exposed_ms, busy_ms", [
    ((0.020, 0.040), 0.0, 80.0),        # wholly under the convolution
    ((0.080, 0.090), 10.0, 90.0),       # wholly alone
    ((0.070, 0.090), 10.0, 90.0),       # half covered
    (None, None, 80.0),                 # no device ran a collective: nothing to read
], ids=["covered", "alone", "half_covered", "none"])
def test_collective_time_that_no_other_operation_covers(collective, exposed_ms, busy_ms):
    got = collective_exposed_pct.read(_two_devices(collective))
    if exposed_ms is None:
        assert got is None
    else:
        assert got == pytest.approx(100.0 * exposed_ms / busy_ms)


@pytest.mark.parametrize("name, category, counts", [
    ("all-reduce-done.1 all-reduce-done", None, True),      # the wait of an asynchronous one
    ("all-gather-start.2 all-gather-start", None, True),
    ("reduce-scatter.4 reduce-scatter", None, True),
    ("collective-permute-done.1 collective-permute-done", None, True),
    ("fusion.88 fusion", "all-reduce fusion", True),        # by category, whatever the opcode
    ("fusion.88 fusion", "loop fusion", False),
    ("copy-done.5 copy-done", None, False),
    ("all-reduce-scatter-fusion.1 fusion", None, False),    # a name is not an opcode
])
def test_a_collective_by_its_category_else_by_its_opcode(name, category, counts):
    assert pt.NamedOp(name, None, 0.0, 1.0, category).is_collective is counts
    got = collective_exposed_pct.read(_two_devices((0.080, 0.090), name, category))
    assert (got == pytest.approx(100.0 * 10.0 / 90.0)) if counts else got is None


def test_one_device_gives_the_collectives_reader_nothing():
    ctx = context(ONE_CHIP_CELL, *synthetic(1))
    assert collective_exposed_pct.read(ctx) is None
    line = harness.read_layer_metrics(ctx["cell"], ctx)
    assert "collective_exposed_pct" not in line and len(line) == 11


def test_a_gradients_all_reduce_is_not_its_convolutions_time():
    """GSPMD gives the gradient's all-reduce the ``op_name`` of the weight
    gradient it reduces: the scope readers count it as a collective."""
    kinds = pt.scope_seconds(context(CELL, *synthetic(4))["program_trace"])
    assert kinds["collective"] == pytest.approx(EXPOSED_S)
    assert kinds["conv_dot"] == pytest.approx(0.060) and kinds["norm"] == pytest.approx(0.015)


# --------------------------------------------------- every reader, four devices
BUSY_4 = sum(row[3] for row in LAYOUT)


def _flops(ctx):
    cell = ctx["cell"]
    return ctx["module"].train_flops_per_sample(cell.config, cell.traffic) * cell.traffic["batch"]


FOUR = {
    "data_wait_pct": lambda ctx: 100.0 * 0.0012 / 1.5,
    "step_gap_p50_ms": lambda ctx: 1e3 * GAP,
    "step_device_ms": lambda ctx: 1e3 * BUSY_4,
    # twelve whole steps on every device over the window that holds them all
    "step_mfu_pct": lambda ctx: 100.0 * _flops(ctx) * 12 / (12 * STEP - GAP + 3 * 0.00004) / (
        4 * 197e12),
    "device_idle_pct": lambda ctx: 100.0 * (1 - 12 * BUSY_4 / (12 * STEP - GAP + 3 * 0.00004)),
    "prefetch_stage_ms": lambda ctx: 53.0,
    "dispatch_lead_ms": lambda ctx: 180.0,
    "idle_named_pct": lambda ctx: 100.0,
    "step_conv_dot_ms": lambda ctx: 60.0,
    "step_norm_ms": lambda ctx: 15.0,
    "conv_dot_roofline": lambda ctx: 100.0 * _flops(ctx) / (4 * 197e12) / 0.060,
    "collective_exposed_pct": lambda ctx: 100.0 * EXPOSED_S / BUSY_4,
}


def test_the_table_below_holds_every_metric_of_the_cell():
    assert set(FOUR) == {m["name"] for m in harness.load_cell(CELL).per_layer}


@pytest.mark.parametrize("name", sorted(FOUR))
def test_reader_gives_a_number_on_four_devices_that_start_apart(name):
    ctx = context(CELL, *synthetic(4))
    assert ctx["program_trace"].causality.ok, ctx["program_trace"].causality.why
    got = importlib.import_module(f"benchmarks.layer_metrics.{name}").read(ctx)
    assert got == pytest.approx(FOUR[name](ctx), rel=1e-6)
    if name.endswith("_roofline") or "mfu" in name:
        assert 0 < got < 100


def test_the_window_takes_the_same_executions_whole_on_every_device():
    red, _, _ = synthetic(4, skew=0.0004)       # twice the idle gap between two steps
    assert red.window == pytest.approx((1.0, 1.0 + 12 * STEP - GAP + 3 * 0.0004))
    assert [len(tr.steps_in_window(red, d)) for d in red.devices] == [12] * 4
    short = synthetic(4)[0].devices
    short[2].programs.pop()                     # one device a step short: an error, not a cut
    with pytest.raises(ValueError, match="device 2: 11 executions"):
        tr.cut_window(short, "train_step")


# -------------------------------------- one chip: what the parent's code printed
#: the readers of the parent commit (172b7b5) over ``synthetic(1)``, to the digit
ONE_CHIP_AS_THE_PARENT_READ_IT = {
    "data_wait_pct": 0.08,
    "step_gap_p50_ms": 0.19999999999997797,
    "step_device_ms": 86.99999999999986,
    "step_mfu_pct": 30.085455404638502,
    "device_idle_pct": 12.98549758293065,
    "prefetch_stage_ms": 52.999999999999936,
    "dispatch_lead_ms": 179.99999999999994,
    "idle_named_pct": 100.0,
    "step_conv_dot_ms": 59.99999999999998,
    "step_norm_ms": 14.99999999999994,
    "conv_dot_roofline": 50.134068603451794,
}


@pytest.mark.parametrize("name", sorted(ONE_CHIP_AS_THE_PARENT_READ_IT))
def test_one_chip_reading_is_the_parents_to_the_digit(name):
    ctx = context(ONE_CHIP_CELL, *synthetic(1))
    got = importlib.import_module(f"benchmarks.layer_metrics.{name}").read(ctx)
    assert got == ONE_CHIP_AS_THE_PARENT_READ_IT[name]


# ------------------------------------------------------ the idle gaps' names
def test_idle_gaps_take_their_names_from_the_spine_where_the_run_has_spans():
    ctx = context(CELL, *synthetic(4))
    out = fit.breakdown(ctx)
    assert out["device_ops"] == tr.breakdown(ctx["trace"])["device_ops"]
    assert len(out["idle_gaps"]) == 10 and {name for name, _ in out["idle_gaps"]} == {"fit.drain"}
    assert out["idle_gaps"] == pt.named_gaps(ctx)
    json.dumps(out)


def test_idle_gaps_keep_the_old_answer_where_there_are_no_spans():
    red, named, _ = synthetic(4)
    ctx = context(CELL, red, named, None)
    assert fit.breakdown(ctx) == tr.breakdown(red)
    assert {name for name, _ in fit.breakdown(ctx)["idle_gaps"]} == {"fit loop (no benchmark span)"}


def test_this_files_metric_is_entered_in_conftest(tested_in_their_own_file):
    assert tested_in_their_own_file["collective_exposed_pct"] == "test_four_chip_cell.py"
