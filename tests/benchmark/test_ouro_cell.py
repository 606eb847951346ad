"""The cell ``ouro_2p6b_pretrain_b2_s4096`` and its configuration on the CPU:
the files and the manifest's entries, the configuration against its source,
the analytic counts, the four new readers (``loop_stack_ms``, ``loop_exit_ms``,
``attention_kernel_ms``, ``attention_kernel_roofline``) on a hand-made trace,
and the generator at the cell's traffic. The configuration's sound run, its
faults, its control and its gradient's direction are cases of the tests that
take every configuration under ``benchmarks/configs`` by its files."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmark_tiny import CONFIGS, ROOT, tiny_cell
from benchmarks import harness, program_trace as pt, trace_reduce as tr, traffic_gen
from benchmarks.configs import ouro_2p6b as reference
from benchmarks.layer_metrics import (
    attention_kernel_ms, attention_kernel_roofline, conv_dot_roofline, loop_exit_ms, loop_stack_ms,
    step_conv_dot_ms, step_mfu_pct, step_norm_ms,
)
from benchmarks.trace_reduce import Device, Op

CELL, CONFIG = "ouro_2p6b_pretrain_b2_s4096", "ouro_2p6b"
NEW_METRICS = ("loop_stack_ms", "loop_exit_ms", "attention_kernel_ms", "attention_kernel_roofline")
MANIFEST = harness.load_manifest()


# ------------------------------------------------------------- the cell's files
def test_the_cell_its_files_and_its_entries():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "fit_b2_s4096", 1)
    assert MANIFEST["workloads"][-1] == entry and MANIFEST["configs"][-1]["name"] == CONFIG
    for path in (f"benchmarks/cells/{CELL}.json", "benchmarks/traffic/fit_b2_s4096.json",
                 f"benchmarks/configs/{CONFIG}.json", f"benchmarks/configs/{CONFIG}.py",
                 f"benchmarks/configs/{CONFIG}.tiny.json",
                 *(f"benchmarks/layer_metrics/{m}.py" for m in NEW_METRICS)):
        assert (ROOT / path).is_file(), path
    cell = harness.load_cell(CELL)
    assert cell.traffic["batch"] == 2 and cell.traffic["seq"] == 4096 and cell.traffic["pool"] == 4
    assert cell.traffic["trace_host_level"] == 0
    # a window of about 25 steps of a second each: the traced stretch fits it,
    # and one step's length is no stall
    assert cell.traffic["trace_after_steps"] + cell.traffic["trace_steps"] <= 18
    assert cell.traffic["trace_stall_s"] >= 2.0 and cell.traffic["trace_min_steps"] >= 4
    assert "grad_largest_turn" in cell.limits
    reported = {m["name"] for m in cell.per_layer}
    assert reported == {m["name"] for m in MANIFEST["per_layer"]} - {"collective_exposed_pct"}
    assert len(reported) == 15
    tail = MANIFEST["per_layer"][-4:]
    assert [m["name"] for m in tail] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s_per_chip" for m in tail)
    assert [m["layer"] for m in tail] == ["looped stack", "exits", "kernels", "kernels"]
    for other in MANIFEST["workloads"][:-1]:        # no other cell reports them
        assert not {m["name"] for m in harness.load_cell(other["name"]).per_layer} & set(NEW_METRICS)


def test_the_configuration_is_its_source_but_for_the_depth():
    """Every number of the published ``config.json`` (as the catalog beside the
    ``model-configs`` guide holds it) under its own key; the one cut is depth."""
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48, "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152}
    cfg = json.loads((ROOT / f"benchmarks/configs/{CONFIG}.json").read_text())
    differs = {k for k, v in published.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert 4 <= cfg["num_hidden_layers"] <= 9 and cfg["published_num_hidden_layers"] == 48
    assert cfg["hidden_act"] == "silu" and cfg["tie_word_embeddings"] is False
    assert cfg["sliding_window"] is None and cfg["rope_scaling"] is None
    assert cfg["layer_types"] == ["full_attention"] * 48
    args = cfg["builder_args"]
    assert cfg["builder"] == "deeplearning4j_tpu.zoo.Ouro" and args["remat"] is True
    assert (args["vocab_size"], args["d_model"], args["n_layers"], args["n_heads"], args["head_dim"],
            args["d_ff"], args["ut_steps"], args["rope_theta"], args["rms_eps"], args["beta"]) == (
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["head_dim"], cfg["intermediate_size"], cfg["total_ut_steps"], cfg["rope_theta"],
        cfg["rms_norm_eps"], cfg["exit_entropy_beta"])
    assert args["lr"] == cfg["updater"]["lr"] and cfg["updater"]["clip_global_norm"] == 1.0
    assert cfg["inputs"]["labels"] == {"kind": "tokens", "vocab": 49152}
    for key in ("sandwich_norm", "norm_between_passes", "exit_gate", "exit_entropy_beta", "updater",
                "weights", "data", "early_exit_threshold"):
        assert cfg["assumed"][key]
    assert any("scale" in d for d in cfg["departures"])


def test_parameters_pinned_to_the_digit_and_each_layer_held_once():
    cfg = harness.load_cell(CELL).config
    params, state = jax.eval_shape(lambda k: reference.make_params(k, cfg), jax.random.key(0))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert total == cfg["num_hidden_layers"] * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert cfg["num_hidden_layers"] != 6 or total == 509_661_185
    assert sorted(params[1]) == [str(i) for i in range(cfg["num_hidden_layers"])] + ["norm"]
    assert state[-1]["exit_share"].shape == (4,)
    # the two largest leaves tie: the token table and the untied head. ``compare``
    # takes the first of them in the tree's order, the token table, whose rows
    # each hold one token's gradient of one row of the batch (PERF.md)
    sizes = [int(np.prod(p.shape)) for p in jax.tree.leaves(params)]
    assert sizes.count(max(sizes)) == 2 and sizes.index(max(sizes)) == 0
    assert jax.tree.leaves(params)[0].shape == (49152, 2048)


def test_the_counts_of_operations_and_bytes():
    cell = harness.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    dot, attention = (f(cfg, traffic) for f in (reference.dot_flops_per_sample,
                                                reference.attention_flops_per_sample))
    assert reference.train_flops_per_sample(cfg, traffic) == dot + attention
    seq, layers, passes = 4096, cfg["num_hidden_layers"], 4
    per_token = layers * passes * 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + passes * 2 * (2048 * 49152 + 2048)
    assert dot == 3 * seq * per_token
    assert per_token == pytest.approx(1e6 * (102.8 * 24 + 201.3 * 4), rel=1e-3) or layers != 6
    # causal: 2 products forward and 5 backward of heads x seq^2 x head_dim, halved for the mask
    assert attention == layers * passes * 7 * (2 * 16 * seq * seq * 128) / 2
    assert reference.attention_bytes_per_sample(cfg, traffic) == layers * passes * 8 * seq * 2048 * 2
    # at 4,096 positions the kernel's products are a seventh of the step's operations
    assert 0.10 < attention / (dot + attention) < 0.17
    tiny = tiny_cell(CONFIG)
    assert reference.attention_bytes_per_sample(tiny.config, tiny.traffic) == 2 * 2 * 8 * 16 * 64 * 4


def test_the_tiny_stand_in_runs_xla_attention_and_is_picked_up_by_its_files():
    assert CONFIG in CONFIGS
    tiny = tiny_cell(CONFIG)
    assert tiny.traffic["seq"] < tiny.config["attention_kernel_from_seq"] <= harness.load_cell(CELL).traffic["seq"]
    assert (tiny.config["builder_args"]["n_layers"], tiny.config["builder_args"]["ut_steps"]) == (2, 2)
    assert tiny.config["builder_args"]["dtype"] == "float32" and tiny.limits
    text = (ROOT / f"benchmarks/configs/{CONFIG}.tiny.json").read_text()
    assert len(json.loads(text)["limits_set_from"]) > 100


def test_the_generator_at_the_cells_traffic():
    cell = harness.load_cell(CELL)
    big = 2 ** 31 + 4242
    pool = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert len(pool) == 4
    for x, y in pool:
        assert x.shape == y.shape == (2, 4096) and x.dtype == y.dtype == np.int32
        assert 0 <= min(x.min(), y.min()) and max(x.max(), y.max()) < 49152
    assert len({x.tobytes() for x, _ in pool}) == 4
    again = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert all(np.array_equal(a, b) for pair, other in zip(pool, again) for a, b in zip(pair, other))


# ------------------------------------------------- the readers, on a hand-made trace
TRAIN = "jit(train_step)/"
FWD = TRAIN + "jvp(1.LoopedStack)/while/body/closed_call/"
BWD = TRAIN + "transpose(jvp(1.LoopedStack))/while/body/closed_call/"
KERNEL = "2.DecoderBlock/flash_attention/flash_attention_{}/pallas_call"
# one step of the device: (name, op_name, hlo_category, seconds)
LAYOUT = [
    ("fusion.1 fusion", TRAIN + "jvp(0.EmbeddingSequenceLayer)/gather", "loop fusion", 0.002),
    ("fusion.2 fusion", FWD + "2.DecoderBlock/dot_general", "convolution fusion", 0.100),
    ("custom-call.1 custom-call", FWD + KERNEL.format("fwd"), "custom-call", 0.030),
    ("fusion.3 fusion", FWD + "norm.RMSNormLayer/mul", "loop fusion", 0.004),
    ("fusion.4 fusion", TRAIN + "jvp(loss)/while/body/closed_call/exit/dot_general",
     "convolution fusion", 0.050),
    ("fusion.5 fusion", TRAIN + "jvp(loss)/while/body/closed_call/exit/reduce_max", "loop fusion", 0.020),
    ("fusion.6 fusion", TRAIN + "jvp(loss)/mul", "loop fusion", 0.001),
    ("fusion.7 fusion", TRAIN + "transpose(jvp(loss))/while/body/closed_call/checkpoint/"
     "rematted_computation/exit/dot_general", "convolution fusion", 0.100),
    ("custom-call.2 custom-call", BWD + "2.DecoderBlock/2.DecoderBlock/checkpoint/rematted_computation/"
     "flash_attention/flash_attention_fwd/pallas_call", "custom-call", 0.030),
    ("custom-call.3 custom-call", BWD + "2.DecoderBlock/" + KERNEL.format("bwd_dkv"), "custom-call", 0.050),
    ("custom-call.4 custom-call", BWD + "2.DecoderBlock/" + KERNEL.format("bwd_dq"), "custom-call", 0.040),
    ("fusion.8 fusion", BWD + "2.DecoderBlock/2.DecoderBlock/checkpoint/flash_attention/reduce_sum",
     "loop fusion", 0.002),
    ("fusion.9 fusion", BWD + "2.DecoderBlock/2.DecoderBlock/checkpoint/dot_general",
     "convolution fusion", 0.300),
    ("fusion.10 fusion", TRAIN + "updater/sub", "loop fusion", 0.040),
    ("copy-done.5 copy-done", None, "copy-done", 0.002),
]
STACK_S = 0.100 + 0.030 + 0.004 + 0.030 + 0.050 + 0.040 + 0.002 + 0.300
EXIT_S = 0.050 + 0.020 + 0.001 + 0.100
KERNEL_S = 0.030 + 0.030 + 0.050 + 0.040 + 0.002
DOT_S = 0.100 + 0.050 + 0.100 + 0.300
STEP_S = sum(row[3] for row in LAYOUT)
PEAKS = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}


def hand_made(layout=LAYOUT, steps=10, while_over=None):
    """``steps`` steps of ``layout`` on one device, 1 ms apart. ``while_over``:
    (first, last) rows that a ``while`` operation of the looped stack spans, as
    the trace has the loop's own event around its body's."""
    ops, programs, named = [], [], []
    for k in range(steps):
        t = t0 = 1.0 + k * (STEP_S + 0.001)
        starts = []
        for name, op_name, category, seconds in layout:
            starts.append(t)
            ops.append(Op(name, t, t + seconds))
            named.append(pt.NamedOp(name, op_name, t, t + seconds, category))
            t += seconds
        if while_over:
            a, b = while_over
            lo, hi = starts[a], starts[b] + layout[b][3]
            ops.append(Op("while.1 while", lo, hi))
            named.append(pt.NamedOp("while.1 while", TRAIN + "jvp(1.LoopedStack)/while", lo, hi, "while"))
        programs.append(Op("jit_train_step(77)", t0, t))
    red = tr.reduce_events([Device(0, ops, programs)], [], "train_step")
    cell = harness.load_cell(CELL)
    return {"trace": red, "cell": cell, "chips": 1, "peaks": PEAKS,
            "module": importlib.import_module(cell.config["reference"]), "counters": {},
            "program_trace": pt.assemble(red, None, {0: named}, None)}


def test_the_four_metrics_have_their_cases_here(scoped_metric_cases, tested_in_their_own_file):
    """``tests/conftest.py`` names this file for the metrics that list this cell
    alone, and says why the table of ``tests/benchmark/conftest.py`` cannot."""
    assert scoped_metric_cases == dict.fromkeys(NEW_METRICS, "test_ouro_cell.py")
    assert not set(NEW_METRICS) & set(tested_in_their_own_file)
    listed = {m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert listed == set(NEW_METRICS)


@pytest.mark.parametrize("reader, want_ms", [
    (loop_stack_ms, 1e3 * STACK_S), (loop_exit_ms, 1e3 * EXIT_S), (attention_kernel_ms, 1e3 * KERNEL_S)],
    ids=lambda v: getattr(v, "__name__", "").rpartition(".")[2] or None)
def test_reader_sums_the_operations_under_its_scopes(reader, want_ms):
    assert reader.read(hand_made()) == pytest.approx(want_ms)


def test_the_loops_own_while_is_not_counted_twice():
    # the trace has the loop's own event around its body's (rows 1-3 here):
    # each operation's time counts less that of the operations nested in it
    ctx = hand_made(while_over=(1, 3))
    assert loop_stack_ms.read(ctx) == pytest.approx(1e3 * STACK_S)
    assert loop_exit_ms.read(ctx) == pytest.approx(1e3 * EXIT_S)
    assert attention_kernel_ms.read(ctx) == pytest.approx(1e3 * KERNEL_S)


def test_the_kernels_roofline_is_bound_by_operations_and_counts_no_recomputation():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.attention_flops_per_sample(cell.config, cell.traffic) * 2
    moved = reference.attention_bytes_per_sample(cell.config, cell.traffic) * 2
    assert flops / 197e12 > moved / 819e9
    assert attention_kernel_roofline.read(ctx) == pytest.approx(100 * flops / 197e12 / KERNEL_S)
    slow_memory = {**ctx, "peaks": {**PEAKS, "hbm_bytes_per_s": 1e9}}
    assert attention_kernel_roofline.read(slow_memory) == pytest.approx(100 * moved / 1e9 / KERNEL_S)
    without = {**ctx, "module": importlib.import_module("benchmarks.configs.bert_base")}
    assert attention_kernel_roofline.read(without) is None


def test_the_readers_every_cell_has_read_the_same_trace():
    ctx = hand_made()
    cell = ctx["cell"]
    assert step_conv_dot_ms.read(ctx) == pytest.approx(1e3 * DOT_S)
    assert step_norm_ms.read(ctx) == 0.0            # no stand-alone norm layer (PERF.md, open)
    dot = reference.dot_flops_per_sample(cell.config, cell.traffic) * 2
    assert conv_dot_roofline.read(ctx) == pytest.approx(100 * dot / 197e12 / DOT_S)
    whole = reference.train_flops_per_sample(cell.config, cell.traffic) * 2
    assert step_mfu_pct.read(ctx) == pytest.approx(
        100 * whole * (ctx["trace"].window_s and len(tr.steps_in_window(
            ctx["trace"], ctx["trace"].devices[0])) / ctx["trace"].window_s) / 197e12)
    kinds = pt.scope_seconds(ctx["program_trace"])
    assert kinds["scoped"] and kinds["moves"] == pytest.approx(0.002) and kinds["unnamed"] == 0.0


def test_a_step_without_the_scopes_gives_the_four_nothing_to_read():
    plain = [("fusion.1 fusion", TRAIN + "jvp(3.TransformerEncoderLayer)/dot_general", "convolution fusion", 0.05),
             ("fusion.2 fusion", TRAIN + "jvp(loss)/reduce_sum", "loop fusion", 0.001),
             ("fusion.3 fusion", TRAIN + "updater/sub", "loop fusion", 0.004)]
    ctx = hand_made(plain)
    for reader in (loop_stack_ms, loop_exit_ms, attention_kernel_ms, attention_kernel_roofline):
        assert reader.read(ctx) is None
    unnamed = hand_made(LAYOUT + [("fusion.77 fusion", None, None, 0.05)])     # over 2 % unnamed
    assert pt.scope_seconds(unnamed["program_trace"]) is None
    for reader in (loop_stack_ms, loop_exit_ms, attention_kernel_ms, attention_kernel_roofline):
        assert reader.read(unnamed) is None


def test_the_result_line_of_a_traced_run_carries_all_fifteen():
    ctx = hand_made()
    ctx["counters"] = {"data_wait_s": 0.001, "traced_host_s": 10.0}
    metrics = harness.read_layer_metrics(ctx["cell"], ctx)
    # the three span readers need the program's spans on the trace's clock: none here
    assert set(metrics) == {m["name"] for m in ctx["cell"].per_layer} - {
        "prefetch_stage_ms", "dispatch_lead_ms", "idle_named_pct"}
    assert 0 < metrics["attention_kernel_roofline"]["value"] < 100
    assert metrics["attention_kernel_roofline"]["unit"] == "%"
