"""The reduction from trace events to metrics, and every per-layer reader, on
small hand-made event lists with known busy, idle and program intervals."""

import importlib
import json
import types

import pytest

from benchmark_tiny import PEAKS
from benchmarks import harness, trace_reduce as tr
from benchmarks.trace_reduce import Device, Op

MANIFEST = harness.load_manifest()


def test_interval_sums():
    merged = tr.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert merged == [(0, 3), (5, 6)] and tr.total(merged) == 4
    assert tr.clip(merged, 2, 5.5) == [(2, 3), (5, 5.5)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_names_of_programs_and_operations():
    assert tr.program_name("jit_train_step(636361907851639042)") == "train_step"
    assert tr.short_name("%all-reduce.3 = f32[128]{0} all-reduce(f32[128]{0} %p), "
                         "replica_groups={}") == "all-reduce.3 all-reduce"
    assert tr.short_name("not an instruction") == "not an instruction"
    assert tr.short_name("%multiply_reduce_fusion.5 = (bf16[256]{0}, bf16[256]{0}) fusion("
                         "bf16[256,56,56,64]{0,3,2,1} %x), kind=kOutput, calls=%f") == \
        "multiply_reduce_fusion.5 fusion"


def _device(dev_id: int, starts, step: float = 0.1, busy: float = 0.08, more: float = 0.0):
    """Executions of train_step at ``starts``, each ``step`` long, with one
    operation of ``busy`` seconds and then ``more`` seconds of another."""
    programs = [Op("jit_train_step(1)", s, s + step) for s in starts]
    ops = []
    for s in starts:
        ops.append(Op("fusion.1 fusion", s, s + busy))
        if more:
            ops.append(Op("fusion.2 fusion", s + busy, s + busy + more))
    return Device(dev_id, ops, programs)


def test_window_is_the_longest_stall_free_stretch_less_its_ends():
    starts = [0.0, 0.1, 0.2, 3.0, 3.1, 3.2, 3.3, 3.4, 6.0, 6.1]     # two profiler stalls
    red = tr.reduce_events([_device(0, starts)], [], "train_step", 1, 1, 0.5)
    assert red.window == pytest.approx((3.1, 3.4))
    assert tr.busy_seconds(red, red.devices[0]) == pytest.approx(0.24)
    assert len(tr.steps_in_window(red, red.devices[0])) == 3
    with pytest.raises(ValueError):
        tr.reduce_events([_device(0, [0.0])], [], "train_step")


@pytest.mark.parametrize("skip_first,min_steps,kept", [(1, 4, 4), (2, 4, None), (0, 6, None)])
def test_a_stretch_shorter_than_the_cell_asks_for_is_an_error_not_another_cut(
        skip_first, min_steps, kept):
    starts = [0.0, 0.1, 0.2, 3.0, 3.1, 3.2, 3.3, 3.4, 6.0, 6.1]     # the longest holds 5
    if kept is None:
        with pytest.raises(ValueError, match=r"stretches of \[3, 5, 2\]"):
            tr.reduce_events([_device(0, starts)], [], "train_step", skip_first, 0, 0.5, min_steps)
    else:
        red = tr.reduce_events([_device(0, starts)], [], "train_step", skip_first, 0, 0.5,
                               min_steps)
        assert len(tr.steps_in_window(red, red.devices[0])) == kept


def _context(devices, counters=None, chips=1, batch=8):
    red = tr.reduce_events(devices, [Op("bench.next_batch", 0.185, 0.2)], "train_step")
    cell = types.SimpleNamespace(
        traffic={"batch": batch}, config={"compute_dtype": "bfloat16"})
    module = types.SimpleNamespace(train_flops_per_sample=lambda cfg, traffic: 1e9)
    return {"trace": red, "cell": cell, "chips": chips, "peaks": PEAKS, "module": module,
            "counters": counters or {}}


# ten executions 0.1 s apart, 0.08 s busy each on device 0; device 1 is busy
# 0.01 s more in each. The window runs from 0.0 to 1.0.
STARTS = [i / 10 for i in range(10)]
ONE = [_device(0, STARTS)]
TWO = [_device(0, STARTS), _device(1, STARTS, more=0.01)]
EXPECTED = {
    "data_wait_pct": (ONE, {"data_wait_s": 0.05, "traced_host_s": 2.0}, 2.5),
    "step_gap_p50_ms": (ONE, {}, 0.0),
    "step_device_ms": (TWO, {}, 90.0),
    "step_mfu_pct": (ONE, {}, 100.0 * 1e9 * (10 * 8 / 1.0) / 1e12),
    "device_idle_pct": (TWO, {}, 20.0),
}


def test_every_metric_of_the_manifest_has_a_case_below():
    assert {m["name"] for m in MANIFEST["per_layer"]} <= set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_what_the_events_hold(name):
    devices, counters, want = EXPECTED[name]
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    got = reader.read(_context(devices, counters, chips=len(devices)))
    assert got == pytest.approx(want, abs=1e-9)


def test_reader_with_nothing_to_read_returns_nothing():
    reader = importlib.import_module("benchmarks.layer_metrics.data_wait_pct")
    assert reader.read(_context(ONE)) is None


def test_breakdown_names_the_heaviest_operations_and_the_longest_gaps():
    red = _context(TWO)["trace"]
    out = tr.breakdown(red)
    assert out["device_ops"][0][0] == "fusion.1 fusion"
    assert out["device_ops"][0][1] == pytest.approx(0.8)
    assert len(out["idle_gaps"]) <= 10 and out["idle_gaps"][0][1] == pytest.approx(0.01)   # the busiest device
    assert sorted(g[0] for g in out["idle_gaps"]) == (
        ["bench.next_batch"] + ["fit loop (no benchmark span)"] * 9)
    json.dumps(out)


def test_result_line_of_a_traced_run_has_the_contracts_keys_and_leaves_out_the_unread():
    cell = harness.load_cell(MANIFEST["workloads"][0]["name"])
    context = _context(ONE)
    context["cell"] = cell
    context["module"] = importlib.import_module(cell.config["reference"])
    verdict = {"correct": True, "checks": {"loss1_gap": {"value": 1e-5, "limit": 1e-3}}}
    run = {"layer_context": context, "verdict": verdict, "attempted": 10, "failed": 0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1, "busy_s": 0.8, "window_s": 1.0},
           "breakdown": tr.breakdown(context["trace"])}
    line = harness.result_line(cell, run, trace=True)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks" and line["checks"]["loss1_gap"] == [1e-5, 1e-3]
    assert "data_wait_pct" not in line["metrics"]           # no counter, nothing read
    assert {"step_gap_p50_ms", "step_device_ms", "step_mfu_pct",
            "device_idle_pct"} <= set(line["metrics"])
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
