"""The first gradient's direction (``grad_largest_turn``, ``grad_median_turn``,
``grad_whole_turn``) and the cell it makes judgeable,
``bert_base_finetune_b32_s384``: the gradient is kept exactly where a cell's
limits name its direction, it is the timed step's own (read out of the
optimizer's state after the first step), a sound run passes and the control in
the precision below fails by it alone, and so does each fault of the step."""

import json
import math
import time

import jax
import numpy as np
import pytest

from benchmark_tiny import CONFIGS, PEAKS, ROOT, devices_for, plant, tiny_cell
from benchmarks import harness, reference_train as rt
from benchmarks.drivers import fit

CELL = "bert_base_finetune_b32_s384"
TURNS = {name: limit for name, limit in tiny_cell("bert_base").limits.items()
         if name in rt.DIRECTION_NUMBERS}
MANIFEST = harness.load_manifest()


def _limits_as_written(cell_name: str) -> dict:
    return json.loads((ROOT / "benchmarks" / "cells" / f"{cell_name}.json").read_text())["limits"]


@pytest.mark.parametrize("workload", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_the_gradient_is_kept_exactly_where_the_cells_file_names_its_direction(workload):
    """The rule, not a roster: a later cell that names a turn takes it."""
    cell = harness.load_cell(workload["name"], MANIFEST)
    named = any(name in _limits_as_written(workload["name"]) for name in rt.DIRECTION_NUMBERS)
    assert rt.takes_direction(cell.limits) == named
    assert set(json.loads((ROOT / "benchmarks" / "cells" / f"{workload['name']}.json").read_text())) \
        == {"limits"}, "a cell's file holds its limits and nothing else"


@pytest.mark.parametrize("name", ["resnet50_fit_b256", "resnet50_pw4_b1024"])
def test_the_resnet_cells_name_no_turn_and_keep_their_nine_numbers(name):
    limits = _limits_as_written(name)
    assert not rt.takes_direction(limits) and len(limits) == 9


@pytest.mark.parametrize("name", CONFIGS)
def test_no_limit_names_a_turn_so_no_gradient_is_kept_and_the_line_keeps_its_keys(name, monkeypatch):
    cell = tiny_cell(name)
    cell.limits = {k: v for k, v in cell.limits.items() if k not in rt.DIRECTION_NUMBERS}
    kept = []
    real = rt.follow
    monkeypatch.setattr(rt, "follow", lambda *a, **how: kept.append(how["keep_gradient"]) or real(*a, **how))
    monkeypatch.setattr(rt, "leaf_turns", None)         # calling it would raise
    out = fit.run(cell, seed=2 ** 31 + 5, seconds=0.3, trace=False, devices=devices_for(cell),
                  peaks=PEAKS, clock0=time.perf_counter())
    line = harness.result_line(cell, out, trace=False)
    assert kept == [False]
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == set(cell.limits)
    assert not set(rt.DIRECTION_NUMBERS) & (set(line["checks"]) | set(out["verdict"]["unlimited"]))


def test_a_limit_names_a_turn_so_both_sides_keep_the_first_gradient_of_their_own_step():
    cell = tiny_cell("bert_base")
    assert rt.takes_direction(cell.limits)
    prog = fit.Program(cell, 2 ** 31 + 6, devices_for(cell))
    readings = prog.first_steps()
    prog.free()
    reference = prog.reference()
    _, factor = rt.first_gradient_from_moment(cell.config["updater"])
    got, want = jax.tree.leaves(readings["grad1"]), jax.tree.leaves(reference["grad1"])
    assert len(got) == len(want) == len(reference["leaves"])
    alive = rt.DEAD_GRADIENT_SHARE * rt.median_of_positive(reference["grad_norms"])
    for g, w, norm in zip(got, want, readings["grad_norms"]):
        assert isinstance(g, np.ndarray) and g.shape == w.shape        # on the host
        # the optimizer's first moment after the one step: the gradient it was given
        assert factor * np.linalg.norm(g) == pytest.approx(norm, rel=1e-5)
        if np.linalg.norm(w) >= alive:      # a key's bias under softmax is round-off on both sides
            assert np.allclose(factor * g, w, rtol=1e-3, atol=1e-5 * np.abs(w).max())
    verdict = rt.compare(readings, reference, cell.limits)
    assert verdict["correct"] and set(TURNS) == set(rt.DIRECTION_NUMBERS) <= set(verdict["checks"])
    only = rt.compare(readings, reference, {"grad_largest_turn": TURNS["grad_largest_turn"]})
    assert only["correct"] and {"grad_median_turn", "grad_whole_turn"} <= set(only["unlimited"])


@pytest.mark.parametrize("seed", [21, 22, 2 ** 31 + 23])
def test_sound_run_passes_and_the_control_fails_by_the_turn_alone(seed):
    """The tests' size runs in float32, so the control is the reference in
    bfloat16, as in every test here."""
    cell = tiny_cell("bert_base")
    cell.limits = dict(TURNS)
    prog = fit.Program(cell, seed, devices_for(cell))
    readings = prog.first_steps()
    prog.free()
    reference = prog.reference()
    sound = rt.compare(readings, reference, cell.limits)
    assert sound["correct"], sound["checks"]
    control = prog.reference(precision=rt.CONTROL_PRECISION[cell.config["compute_dtype"]])
    failed = rt.compare(control, reference, cell.limits)
    assert not failed["correct"]
    for name in TURNS:      # each at ten times its limit or more
        assert failed["checks"][name]["value"] > 10 * failed["checks"][name]["limit"], failed["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct_by_the_turn_alone(fault):
    cell = tiny_cell("bert_base")
    cell.limits = dict(TURNS)
    out = fit.run(cell, seed=2 ** 31 + 7, seconds=0.3, trace=False, devices=devices_for(cell),
                  peaks=PEAKS, clock0=time.perf_counter(), patch=plant(fault, cell.chips))
    checks = out["verdict"]["checks"]
    assert not out["verdict"]["correct"]
    for name in TURNS:
        value = checks[name]["value"]
        # an unchanged state leaves the moment at nought, which points nowhere;
        # half a batch is a gradient of other rows, near a right angle away
        assert math.isnan(value) if fault == "state_unchanged" else value > 0.5, checks


def test_a_turn_is_the_angle_between_two_leaves_whatever_their_sizes():
    a = np.array([3.0, 0.0, 0.0], np.float32)
    tree = {"same": a, "scaled": a, "right": a, "opposite": a, "small": a, "none": a}
    other = {"same": a, "scaled": 10 * a, "right": np.array([0.0, 2.0, 0.0], np.float32),
             "opposite": -a, "small": np.array([3.0, 0.03, 0.0], np.float32), "none": 0 * a}
    turns, whole = rt.leaf_turns(other, tree)
    by = dict(zip(sorted(tree), (float(t) for t in turns)))
    assert by["same"] == by["scaled"] == 0.0
    assert by["right"] == pytest.approx(math.sqrt(2)) and by["opposite"] == pytest.approx(2.0)
    assert by["small"] == pytest.approx(0.01, rel=1e-3)          # radians, while small
    assert math.isnan(by["none"])
    # the whole tree as one vector: (3, 30, 0 2 0, -3, 3 .03, 0) against six times (3 0 0)
    g = np.concatenate([other[k] for k in sorted(other)])
    w = np.concatenate([tree[k] for k in sorted(tree)])
    assert float(whole) == pytest.approx(
        np.linalg.norm(g / np.linalg.norm(g) - w / np.linalg.norm(w)), rel=1e-5)


def test_the_largest_leaf_is_found_by_its_size_and_a_dead_leaf_is_left_out_of_the_median():
    base = {"losses": [1.0], "grad_norms": [1.0, 1.0, 1.0, 1e-6],
            "change_norms": [1.0, 1.0, 1.0, 1.0], "leaves": ["a", "big", "b", "dead"]}
    a, big = np.array([1.0, 0.0], np.float32), np.array([1.0, 0.0, 0.0], np.float32)
    ref = {**base, "grad1": [a, big, a, a]}
    turned = {**base, "grad1": [a, np.array([1.0, 0.2, 0.0], np.float32),
                                np.array([1.0, 0.1], np.float32), -a]}
    limits = {"grad_largest_turn": 0.25, "grad_median_turn": 0.15}
    verdict = rt.compare(turned, ref, limits)
    assert verdict["correct"] and verdict["left_out_of_change"] == 1
    assert verdict["checks"]["grad_largest_turn"]["value"] == pytest.approx(0.1987, rel=1e-2)
    assert verdict["checks"]["grad_median_turn"]["value"] == pytest.approx(0.0998, rel=1e-2)
    assert verdict["unlimited"]["grad_whole_turn"] > 0.9        # the whole tree leaves nothing out
    nowhere = {**base, "grad1": [a, 0 * big, 0 * a, a]}
    verdict = rt.compare(nowhere, ref, {name: 10.0 for name in limits})
    assert not verdict["correct"]
    assert all(math.isnan(c["value"]) for c in verdict["checks"].values())


def test_a_limit_whose_number_was_not_taken_is_not_correct():
    base = {"losses": [1.0], "grad_norms": [1.0], "change_norms": [1.0], "leaves": ["a"]}
    assert rt.compare(base, base, {"loss1_gap": 1e-6})["correct"]
    verdict = rt.compare(base, base, {"loss1_gap": 1e-6, "grad_largest_turn": 1.0})
    assert not verdict["correct"] and "grad_largest_turn" not in verdict["checks"]


def test_the_cells_traffic_is_bert_at_32_by_384_cut_as_the_other_cells_are():
    cell = harness.load_cell(CELL)
    other = harness.load_cell("resnet50_fit_b256")
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "bert_base", "fit_b32_s384")
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"]) == (32, 384, 4)
    assert cell.traffic["seq"] <= cell.config["max_position_embeddings"]
    assert ({k: v for k, v in cell.traffic.items() if k.startswith("trace_")}
            == {k: v for k, v in other.traffic.items() if k.startswith("trace_")})
    assert "state_leaf_gap" not in cell.limits, "BERT carries no state forward"
    assert rt.takes_direction(cell.limits), "the number the fp8 control fails in this cell"
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "bert_base")
    assert entry["reduced"] == cell.config["reduced"] and len(entry["source"]) <= 200
