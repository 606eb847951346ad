"""The cell ``mellum2_12b_pretrain_b1_s8192`` and its configuration on the CPU:
the files and the manifest's entries, the configuration against its source,
the analytic counts, the six new readers (``moe_ms``, ``moe_route_ms``,
``expert_matmul_roofline``, ``moe_load_max_over_mean``,
``banded_attention_kernel_ms``, ``banded_attention_kernel_roofline``) on a
hand-made trace, and the generator at the cell's traffic. The configuration's
sound run, its faults, its control and its gradient's direction are cases of
the tests that take every configuration under ``benchmarks/configs`` by its
files."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmark_tiny import CONFIGS, ROOT, tiny_cell
from benchmarks import harness, program_trace as pt, trace_reduce as tr, traffic_gen
from benchmarks.configs import mellum2_12b as reference
from benchmarks.layer_metrics import (
    attention_kernel_ms, banded_attention_kernel_ms, banded_attention_kernel_roofline,
    conv_dot_roofline, expert_matmul_roofline, loop_stack_ms, moe_load_max_over_mean, moe_ms,
    moe_route_ms, step_conv_dot_ms, step_mfu_pct,
)
from benchmarks.trace_reduce import Device, Op

CELL, CONFIG, TRAFFIC = "mellum2_12b_pretrain_b1_s8192", "mellum2_12b", "fit_b1_s8192"
NEW_METRICS = ("moe_ms", "moe_route_ms", "expert_matmul_roofline", "moe_load_max_over_mean",
               "banded_attention_kernel_ms", "banded_attention_kernel_roofline")
MANIFEST = harness.load_manifest()
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0, "moe_intermediate_size": 896,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "sliding_window": 1024, "vocab_size": 98304}


# ------------------------------------------------------------- the cell's files
def test_the_cell_its_files_and_its_entries():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "quarter" in entry["why"] and len(entry["why"]) <= 200
    for path in (f"benchmarks/cells/{CELL}.json", f"benchmarks/traffic/{TRAFFIC}.json",
                 f"benchmarks/configs/{CONFIG}.json", f"benchmarks/configs/{CONFIG}.py",
                 f"benchmarks/configs/{CONFIG}.tiny.json",
                 *(f"benchmarks/layer_metrics/{m}.py" for m in NEW_METRICS)):
        assert (ROOT / path).is_file(), path
    cell = harness.load_cell(CELL)
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"]) == (1, 8192, 4)
    assert cell.traffic["driver"] == "fit" and cell.traffic["trace_host_level"] == 0
    assert "grad_largest_turn" in cell.limits
    reported = [m["name"] for m in cell.per_layer]
    assert reported[-6:] == list(NEW_METRICS) and len(reported) == 8 + 6
    # the three readers of the program's spans list the cells before this one (PERF.md §7)
    spans = [m for m in MANIFEST["per_layer"] if m["source"] == "program_span"]
    assert len(spans) == 3 and all(CELL not in m["workloads"] and len(m["workloads"]) == 4
                                   for m in spans)
    entries = [m for m in MANIFEST["per_layer"] if m["name"] in NEW_METRICS]
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s_per_chip"
               for m in entries)
    assert [m["layer"] for m in entries] == ["sparse experts", "sparse experts", "kernels",
                                             "sparse experts", "kernels", "kernels"]
    assert [m["source"] for m in entries] == ["device_trace"] * 3 + ["program_counter"] + [
        "device_trace"] * 2
    for other in MANIFEST["workloads"]:         # no other cell reports them
        if other["name"] != CELL:
            assert not {m["name"] for m in harness.load_cell(other["name"]).per_layer} & set(NEW_METRICS)
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) == 1 <= len(MANIFEST["workloads"]) // 4


def test_the_configuration_is_its_source_but_for_the_chips_share():
    """Every number of the published ``config.json`` (as the catalog beside the
    ``model-configs`` guide holds it) under its own key; the cuts are depth and
    one chip's share of the experts and of the vocabulary, no width."""
    cfg = json.loads((ROOT / f"benchmarks/configs/{CONFIG}.json").read_text())
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} == set(cfg["reduced"])
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 24576)
    assert (cfg["published_num_hidden_layers"], cfg["published_num_experts"],
            cfg["published_vocab_size"]) == (28, 64, 98304)
    assert cfg["router_experts"] == 64 and cfg["experts_held_first"] == 0
    assert 4 * cfg["num_experts"] == 64 and 4 * cfg["vocab_size"] == 98304    # four chips share a layer
    assert "four chips share each layer" in cfg["deployment"]
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 28 and cfg["norm_topk_prob"] is True
    assert cfg["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["tie_word_embeddings"] is False and cfg["attention_bias"] is False
    args = cfg["builder_args"]
    assert cfg["builder"] == "deeplearning4j_tpu.zoo.Mellum2" and args["remat"] is True
    assert (args["vocab_size"], args["d_model"], args["n_layers"], args["n_heads"],
            args["n_kv_heads"], args["head_dim"], args["n_experts"], args["top_k"],
            args["d_expert"], args["experts_held"], args["window"], args["rms_eps"],
            args["aux_coef"]) == (
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["router_experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        [cfg["experts_held_first"], cfg["num_experts"]], cfg["sliding_window"],
        cfg["rms_norm_eps"], cfg["router_aux_loss_coef"])
    full = cfg["rope_parameters"]["full_attention"]
    assert args["rope_yarn"] == [full["factor"], full["original_max_position_embeddings"],
                                 full["beta_fast"], full["beta_slow"], full["attention_factor"]]
    assert args["rope_theta"] == full["rope_theta"] and args["lr"] == cfg["updater"]["lr"]
    sched = cfg["updater"]["schedule"]
    assert (sched["kind"], args["warmup"], args["total_steps"]) == (
        "warmup_cosine", sched["warmup_steps"], sched["total_steps"]) and args["warmup"] == 2000
    # the program builds its blocks by the list the reference reads
    assert args["layer_types"] == reference.layer_types(cfg) == cfg["layer_types"][:4]
    assert cfg["inputs"]["labels"] == {"kind": "tokens", "vocab": 24576}
    for key in ("pre_norm", "qk_norm", "router_aux_loss_coef", "router_float32", "updater",
                "weights", "data", "intermediate_size", "yarn", "mtp"):
        assert cfg["assumed"][key]
    assert any("scale" in d for d in cfg["departures"])


def test_the_zoos_updater_under_the_files_arguments_is_the_references():
    """The updater ``zoo.Mellum2`` builds from the configuration's ``builder_args``
    (the warm-up among them) against the reference's, written out, over the
    first steps of the warm-up and a step far past it."""
    import jax.numpy as jnp

    from benchmarks import reference_train as rt
    from deeplearning4j_tpu.zoo import Mellum2

    cfg = harness.load_cell(CELL).config
    spec, theirs = cfg["updater"], Mellum2(**cfg["builder_args"]).conf().updater
    for step, want in ((0, 0.0), (1, 1.5e-7), (1000, 1.5e-4), (2000, 3e-4)):
        assert float(theirs._lr(step)) == pytest.approx(want) == pytest.approx(
            float(rt.learning_rate(spec, step)))
    k1, k2 = jax.random.split(jax.random.key(3))
    params = {"a": jax.random.normal(k1, (5, 7)), "b": jax.random.normal(k2, (7,))}
    mine_p, mine_o = params, rt.init_opt(spec, params)
    their_p, their_o = params, theirs.init_state(params)
    for step in (0, 1, 2, 1500):
        grads = jax.tree.map(lambda p: 3.0 * jnp.sin(p + step), mine_p)
        mine_p, mine_o = rt.apply_updater(spec, grads, mine_o, mine_p, step)
        upd, their_o = theirs.update(grads, their_o, their_p, step)
        their_p = jax.tree.map(lambda p, d: p - d, their_p, upd)
    for a, b in zip(jax.tree.leaves(mine_p), jax.tree.leaves(their_p)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert float(jnp.abs(mine_p["a"] - params["a"]).max()) > 1e-5       # the fourth step moved them
    constant = Mellum2().conf().updater                     # no warm-up asked for: the rate itself
    assert constant.lr == 3e-4


def test_parameters_pinned_to_the_digit():
    cfg = harness.load_cell(CELL).config
    params, state = jax.eval_shape(lambda k: reference.make_params(k, cfg), jax.random.key(0))
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    layer = attention + 2304 * 64 + 16 * 3 * 2304 * 896 + 2 * 2304 + 2 * 128
    assert attention == 21_233_664 and 3 * 2304 * 896 == 6_193_152
    total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert total == 4 * layer + 2 * 24576 * 2304 + 2304 == 595_154_176
    assert len(params) == len(state) == 7 and sorted(params[1]["mlp"]) == ["Wd", "Wg", "Wr", "Wu"]
    assert state[1]["moe_stats"].shape == (4,) and state[1]["loss_term"].shape == ()
    # the two largest leaves tie, the token table and the untied head: ``compare``
    # takes the first in the tree's order, the token table (PERF.md)
    sizes = [int(np.prod(p.shape)) for p in jax.tree.leaves(params)]
    assert sizes.count(max(sizes)) == 2 and sizes.index(max(sizes)) == 0
    from benchmarks.drivers import fit

    model_shapes = jax.eval_shape(lambda: fit.build_model(cfg).params)
    assert jax.tree.structure(model_shapes) == jax.tree.structure(params)
    assert [s.shape for s in jax.tree.leaves(model_shapes)] == [
        p.shape for p in jax.tree.leaves(params)]


def test_the_counts_of_operations_and_bytes():
    cell = harness.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    dot, attention, experts = (f(cfg, traffic) for f in (
        reference.dot_flops_per_sample, reference.attention_flops_per_sample,
        reference.expert_flops_per_sample))
    assert reference.train_flops_per_sample(cfg, traffic) == dot + attention + experts
    seq = 8192
    assert dot == 6 * seq * (4 * (21_233_664 + 2304 * 64) + 2304 * 24576)
    band, half = 1024 * 1025 // 2 + (seq - 1024) * 1024, seq * (seq + 1) // 2
    assert attention == 7 * 2 * 32 * 128 * (3 * band + half)
    # 2 held experts a token are expected: 9 products of 2 x 2,304 x 896 a pair
    assert experts == 4 * 9 * 2 * 2304 * 896 * (2 * seq)
    whole = dot + attention + experts
    assert 12.5e12 < whole < 13e12
    assert 0.17 < experts / whole < 0.21 and 0.24 < attention / whole < 0.28
    assert reference.attention_bytes_per_sample(cfg, traffic) == 4 * seq * (4 * 4096 + 4 * 512) * 2
    assert reference.expert_bytes_per_sample(cfg, traffic) == 4 * 2 * (
        9 * 16 * 2304 * 896 + 3 * 2 * seq * (2 * 2304 + 3 * 896))
    # operations bound both kernels at the v5e's peaks
    assert experts / 197e12 > reference.expert_bytes_per_sample(cfg, traffic) / 819e9
    assert attention / 197e12 > reference.attention_bytes_per_sample(cfg, traffic) / 819e9


def test_the_tiny_stand_in_keeps_every_mechanism_and_is_picked_up_by_its_files():
    assert CONFIG in CONFIGS
    tiny = tiny_cell(CONFIG)
    assert tiny.traffic["seq"] < tiny.config["attention_kernel_from_seq"] <= harness.load_cell(CELL).traffic["seq"]
    args = tiny.config["builder_args"]
    assert (args["n_layers"], args["n_heads"], args["n_kv_heads"], args["n_experts"], args["top_k"],
            args["experts_held"]) == (4, 4, 2, 8, 2, [2, 2])
    assert args["window"] < tiny.traffic["seq"] and args["dtype"] == "float32" and tiny.limits
    assert reference.layer_types(tiny.config) == ["sliding_attention"] * 3 + ["full_attention"]
    assert tiny.config["rope_parameters"]["full_attention"]["factor"] == 16     # merged, not replaced
    text = (ROOT / f"benchmarks/configs/{CONFIG}.tiny.json").read_text()
    assert len(json.loads(text)["limits_set_from"]) > 100


def test_the_generator_at_the_cells_traffic():
    cell = harness.load_cell(CELL)
    big = 2 ** 31 + 3434
    pool = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert len(pool) == 4
    for x, y in pool:
        assert x.shape == y.shape == (1, 8192) and x.dtype == y.dtype == np.int32
        assert 0 <= min(x.min(), y.min()) and max(x.max(), y.max()) < 24576
    assert len({x.tobytes() for x, _ in pool}) == 4
    again = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert all(np.array_equal(a, b) for pair, other in zip(pool, again) for a, b in zip(pair, other))


# ------------------------------------------------- the readers, on a hand-made trace
TRAIN = "jit(train_step)/"
FWD = TRAIN + "jvp(2.DecoderBlock)/"
BWD = TRAIN + "transpose(jvp(2.DecoderBlock))/jvp(2.DecoderBlock)/checkpoint/"
AGAIN = BWD + "rematted_computation/"
MOE = "mlp.SparseExpertsLayer/"
KERNEL = "flash_attention/flash_attention_{}/pallas_call"
# one step of the device: (name, op_name, hlo_category, seconds)
LAYOUT = [
    ("fusion.1 fusion", TRAIN + "jvp(0.EmbeddingSequenceLayer)/gather", "loop fusion", 0.002),
    ("fusion.2 fusion", FWD + "dot_general", "convolution fusion", 0.020),
    ("custom-call.1 custom-call", FWD + KERNEL.format("fwd"), "custom-call", 0.010),
    ("fusion.3 fusion", FWD + MOE + "router/dot_general", "convolution fusion", 0.001),
    ("sort.1 sort", FWD + MOE + "route/sort", "sort", 0.002),
    ("fusion.4 fusion", FWD + MOE + "dispatch/gather", "loop fusion", 0.003),
    ("custom-call.2 custom-call", "ragged-dot-metadata", "custom-call", 0.0005),
    ("custom-call.3 custom-call", "ragged-dot-none", "custom-call", 0.009),
    ("fusion.5 fusion", FWD + MOE + "expert_matmul/mul", "loop fusion", 0.001),
    ("fusion.6 fusion", FWD + MOE + "combine/reduce_sum", "loop fusion", 0.003),
    ("fusion.7 fusion", TRAIN + "jvp(6.RnnOutputLayer)/dot_general", "convolution fusion", 0.015),
    ("fusion.8 fusion", TRAIN + "jvp(loss)/reduce_max", "loop fusion", 0.004),
    ("fusion.9 fusion", AGAIN + MOE + "dispatch/gather", "loop fusion", 0.003),
    ("custom-call.4 custom-call", "ragged-dot-none", "custom-call", 0.009),
    ("custom-call.5 custom-call", "ragged-dot-none", "custom-call", 0.018),
    ("fusion.10 fusion", BWD + MOE + "combine/gather", "loop fusion", 0.004),
    ("fusion.11 fusion", BWD + MOE + "dispatch/reduce_sum", "loop fusion", 0.004),
    ("custom-call.6 custom-call", BWD + KERNEL.format("bwd"), "custom-call", 0.020),
    ("fusion.12 fusion", BWD + "flash_attention/reduce_sum", "loop fusion", 0.002),
    ("fusion.13 fusion", BWD + "dot_general", "convolution fusion", 0.040),
    ("fusion.14 fusion", TRAIN + "updater/sub", "loop fusion", 0.020),
    ("copy-done.5 copy-done", None, "copy-done", 0.002),
]
MATMUL_S = 0.0005 + 0.009 + 0.001 + 0.009 + 0.018
ROUTE_S = 0.001 + 0.002 + 0.003 + 0.003 + 0.003 + 0.004 + 0.004
KERNEL_S = 0.010 + 0.020 + 0.002
DOT_S = 0.020 + 0.001 + 0.015 + 0.040
STEP_S = sum(row[3] for row in LAYOUT)
PEAKS = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}


def hand_made(layout=LAYOUT, steps=10, cell=CELL):
    """``steps`` steps of ``layout`` on one device, 1 ms apart."""
    ops, programs, named = [], [], []
    for k in range(steps):
        t = t0 = 1.0 + k * (STEP_S + 0.001)
        for name, op_name, category, seconds in layout:
            ops.append(Op(name, t, t + seconds))
            named.append(pt.NamedOp(name, op_name, t, t + seconds, category))
            t += seconds
        programs.append(Op("jit_train_step(77)", t0, t))
    red = tr.reduce_events([Device(0, ops, programs)], [], "train_step")
    cell = harness.load_cell(cell)
    return {"trace": red, "cell": cell, "chips": 1, "peaks": PEAKS,
            "module": importlib.import_module(cell.config["reference"]), "counters": {},
            "program_trace": pt.assemble(red, None, {0: named}, None)}


def test_the_six_metrics_have_their_cases_here(scoped_metric_cases, tested_in_their_own_file):
    """``tests/conftest.py`` names this file for the metrics that list this cell
    alone, and says why the table of ``tests/benchmark/conftest.py`` cannot."""
    assert scoped_metric_cases == dict.fromkeys(NEW_METRICS, "test_mellum2_cell.py")
    assert not set(NEW_METRICS) & set(tested_in_their_own_file)
    listed = {m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert listed == set(NEW_METRICS)


@pytest.mark.parametrize("reader, want_ms", [
    (moe_ms, 1e3 * (MATMUL_S + ROUTE_S)), (moe_route_ms, 1e3 * ROUTE_S),
    (banded_attention_kernel_ms, 1e3 * KERNEL_S)],
    ids=lambda v: getattr(v, "__name__", "").rpartition(".")[2] or None)
def test_reader_sums_the_operations_under_its_scopes(reader, want_ms):
    assert reader.read(hand_made()) == pytest.approx(want_ms)


def test_the_grouped_products_roofline_counts_expected_pairs_and_no_recomputation():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.expert_flops_per_sample(cell.config, cell.traffic)
    moved = reference.expert_bytes_per_sample(cell.config, cell.traffic)
    assert expert_matmul_roofline.read(ctx) == pytest.approx(100 * flops / 197e12 / MATMUL_S)
    slow_memory = {**ctx, "peaks": {**PEAKS, "hbm_bytes_per_s": 1e9}}
    assert expert_matmul_roofline.read(slow_memory) == pytest.approx(100 * moved / 1e9 / MATMUL_S)
    without = {**ctx, "module": importlib.import_module("benchmarks.configs.bert_base")}
    assert expert_matmul_roofline.read(without) is None


def test_the_banded_kernels_roofline_takes_this_configurations_band():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.attention_flops_per_sample(cell.config, cell.traffic)
    assert banded_attention_kernel_roofline.read(ctx) == pytest.approx(
        100 * flops / 197e12 / KERNEL_S)
    assert banded_attention_kernel_ms.read(ctx) == attention_kernel_ms.read(ctx)
    # a full causal layer in every place would count 1.7 times as many pairs
    every_full = {**cell.config, "layer_types": ["full_attention"] * 4}
    assert reference.attention_flops_per_sample(every_full, cell.traffic) / flops == pytest.approx(
        4 * 33_558_528 / (3 * 7_864_832 + 33_558_528))


def test_the_load_is_read_from_the_programs_gauge(monitoring_off):
    monitoring = monitoring_off
    assert moe_load_max_over_mean.read({}) is None          # a program without the gauge
    monitoring.enable()
    gauge = monitoring.fit_monitor().moe_load["load_max_over_mean"]
    gauge.labels(layer="1").set(1.07)
    gauge.labels(layer="3").set(1.21)
    assert moe_load_max_over_mean.read({}) == 1.21          # the worst layer
    monitoring.disable()
    assert moe_load_max_over_mean.read({}) == 1.21          # read after the window has closed


def test_the_readers_every_cell_has_read_the_same_trace():
    ctx = hand_made()
    cell = ctx["cell"]
    # the grouped products carry no ``dot_general`` in their name: neither in the
    # time nor in ``dot_flops_per_sample``; the router's small product is in both
    assert step_conv_dot_ms.read(ctx) == pytest.approx(1e3 * DOT_S)
    dot = reference.dot_flops_per_sample(cell.config, cell.traffic)
    assert conv_dot_roofline.read(ctx) == pytest.approx(100 * dot / 197e12 / DOT_S)
    whole = reference.train_flops_per_sample(cell.config, cell.traffic)
    steps = len(tr.steps_in_window(ctx["trace"], ctx["trace"].devices[0]))
    assert step_mfu_pct.read(ctx) == pytest.approx(100 * whole * steps / ctx["trace"].window_s / 197e12)
    kinds = pt.scope_seconds(ctx["program_trace"])
    assert kinds["scoped"] and kinds["moves"] == pytest.approx(0.002) and kinds["unnamed"] == 0.0
    assert loop_stack_ms.read(ctx) is None          # no looped stack here


def test_a_step_without_the_scopes_gives_the_readers_nothing_to_read():
    plain = [("fusion.1 fusion", TRAIN + "jvp(3.TransformerEncoderLayer)/dot_general", "convolution fusion", 0.05),
             ("custom-call.9 custom-call", "ragged-dot-none", "custom-call", 0.01),     # someone else's
             ("fusion.2 fusion", TRAIN + "jvp(loss)/reduce_sum", "loop fusion", 0.001),
             ("fusion.3 fusion", TRAIN + "updater/sub", "loop fusion", 0.004)]
    ctx = hand_made(plain)
    for reader in (moe_ms, moe_route_ms, expert_matmul_roofline, banded_attention_kernel_ms,
                   banded_attention_kernel_roofline):
        assert reader.read(ctx) is None
    unnamed = hand_made(LAYOUT + [("fusion.77 fusion", None, None, 0.05)])     # over 2 % unnamed
    assert pt.scope_seconds(unnamed["program_trace"]) is None
    for reader in (moe_ms, moe_route_ms, expert_matmul_roofline, banded_attention_kernel_ms):
        assert reader.read(unnamed) is None


def test_the_result_line_of_a_traced_run_carries_all_fourteen(monitoring_off):
    monitoring = monitoring_off
    monitoring.enable()
    monitoring.fit_monitor().moe_load["load_max_over_mean"].labels(layer="2").set(1.09)
    ctx = hand_made()
    ctx["counters"] = {"data_wait_s": 0.001, "traced_host_s": 10.0}
    metrics = harness.read_layer_metrics(ctx["cell"], ctx)
    # the eight every cell reports and this cell's six. The three span readers list
    # the four cells before this one: their check of causality finds no match in
    # about one traced run in ten of any cell, and a new cell may not miss a metric
    assert set(metrics) == {m["name"] for m in ctx["cell"].per_layer}
    assert len(metrics) == 14 and not set(metrics) & {
        "prefetch_stage_ms", "dispatch_lead_ms", "idle_named_pct"}
    for share in ("expert_matmul_roofline", "banded_attention_kernel_roofline"):
        assert metrics[share]["unit"] == "%" and metrics[share]["value"] > 0
    assert metrics["moe_load_max_over_mean"] == {"value": 1.09, "unit": "ratio"}
    assert metrics["moe_ms"]["value"] > metrics["moe_route_ms"]["value"] > 0


# --------------------------------------- the fault a cell of one row can have planted
def test_the_leading_positions_repeated_keep_the_shape():
    from benchmarks.calibrate_positions import leading_positions_repeated

    a = np.arange(2 * 7).reshape(2, 7)
    assert leading_positions_repeated(a, 0.5).tolist() == [[0, 1, 2, 0, 1, 2, 0], [7, 8, 9, 7, 8, 9, 7]]
    assert leading_positions_repeated(a, 1.0).tolist() == a.tolist()
    assert leading_positions_repeated(a, 0.01).tolist() == [[0] * 7, [7] * 7]


@pytest.mark.parametrize("keep, correct", [(0.5, False), (1.0, True)], ids=["half", "whole"])
def test_half_of_the_positions_left_out_comes_out_not_correct(keep, correct):
    """``calibrate.py``'s half a batch is the batch itself in a cell of one row
    (the real cell's); along the positions the fault is planted by
    ``calibrate_positions.py`` and judged by the comparison that decides
    ``correct``: at the tests' size it fails every limit of the stand-in that
    the real cell carries too."""
    from benchmarks import calibrate_positions

    tiny, seed = tiny_cell(CONFIG), 2**31 + 5
    pool = traffic_gen.make_pool(tiny.config["inputs"], tiny.traffic, seed)
    batches = [tuple(jax.numpy.asarray(a) for a in b) for b in pool[:3]]
    got = calibrate_positions.judge(reference, tiny.config, tiny.limits, jax.random.key(seed),
                                    batches, keep)
    assert got["correct"] is correct
    if correct:
        assert max(got["numbers"].values()) == 0.0
    else:
        shared = set(tiny.limits) & set(harness.load_cell(CELL).limits)
        assert shared >= {"grad_largest_turn", "grad_median_gap", "change_median_gap", "state_leaf_gap"}
        assert shared <= set(got["over"])
        assert got["numbers"]["grad_largest_turn"] > 0.5
