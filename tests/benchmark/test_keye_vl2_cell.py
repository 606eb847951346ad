"""The cell ``keye_vl2_30b_pretrain_b1_s16384`` and its configuration on the
CPU: the files and the manifest's entries, the configuration against the
catalog's row key by key, the parameter count to the digit, the analytic
counts, the five new readers (``sparse_attention_ms``, ``indexer_ms``,
``sparse_attention_kernel_roofline``, ``sparse_tiles_visited_pct``,
``indexer_kernel_roofline``) on a hand-made trace and gauge, and the generator at the cell's traffic. The
configuration's sound run, its faults, its control and its gradient's
direction are cases of the tests that take every configuration under
``benchmarks/configs`` by its files."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmark_tiny import CONFIGS, ROOT, tiny_cell
from benchmarks import harness, program_trace as pt, trace_reduce as tr, traffic_gen
from benchmarks.configs import keye_vl2_30b as reference
from benchmarks.layer_metrics import (
    conv_dot_roofline, indexer_kernel_roofline, indexer_ms, sparse_attention_kernel_roofline,
    sparse_attention_ms,
    sparse_tiles_visited_pct, step_conv_dot_ms, step_mfu_pct,
)
from benchmarks.trace_reduce import Device, Op

CELL, CONFIG, TRAFFIC = "keye_vl2_30b_pretrain_b1_s16384", "keye_vl2_30b", "fit_b1_s16384"
NEW_METRICS = ("sparse_attention_ms", "indexer_ms", "sparse_attention_kernel_roofline",
               "sparse_tiles_visited_pct", "indexer_kernel_roofline")
MANIFEST = harness.load_manifest()
#: the catalog row's ``config`` (its ``source_url`` is the configuration's
#: ``source``), every number and flag (the nested groups apart)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_theta": 10000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
GROUPS = {"rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
          "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}}


# ------------------------------------------------------------- the cell's files
def test_the_cell_its_files_and_its_entries():
    entry = MANIFEST["workloads"][-1]
    assert (entry["name"], entry["config"], entry["traffic"], entry["chips"]) == (
        CELL, CONFIG, TRAFFIC, 1)
    assert "1/8" in entry["why"] and len(entry["why"]) <= 200
    for path in (f"benchmarks/cells/{CELL}.json", f"benchmarks/traffic/{TRAFFIC}.json",
                 f"benchmarks/configs/{CONFIG}.json", f"benchmarks/configs/{CONFIG}.py",
                 f"benchmarks/configs/{CONFIG}.tiny.json",
                 *(f"benchmarks/layer_metrics/{m}.py" for m in NEW_METRICS)):
        assert (ROOT / path).is_file(), path
    cell = harness.load_cell(CELL)
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"]) == (1, 16384, 4)
    assert "grad_largest_turn" in cell.limits and "state_leaf_gap" in cell.limits
    entries = MANIFEST["per_layer"][-5:]
    assert [m["name"] for m in entries] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s_per_chip"
               for m in entries)
    assert [m["layer"] for m in entries] == ["sparse-attention mixer"] * 2 + [
        "kernels", "sparse-attention mixer", "kernels"]
    assert [m["source"] for m in entries] == ["device_trace"] * 3 + [
        "program_counter", "device_trace"]
    assert [m["unit"] for m in entries] == ["ms", "ms", "%", "%", "%"]
    reported = [m["name"] for m in cell.per_layer]
    assert reported[-5:] == list(NEW_METRICS)
    # every metric with no list of its own, and nothing that lists another cell
    assert set(reported[:-5]) == {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    for other in MANIFEST["workloads"][:-1]:        # no other cell reports them
        theirs = {m["name"] for m in harness.load_cell(other["name"]).per_layer}
        assert not theirs & set(NEW_METRICS)
    assert MANIFEST["configs"][-1]["name"] == CONFIG and len(MANIFEST["configs"]) == 6


def test_nothing_the_benchmark_had_is_changed():
    """One configuration, one cell and five per-layer entries more, each at the
    end of its list; what stood before stands as it stood."""
    before = json.loads(json.dumps(MANIFEST))
    before["configs"].pop()
    before["workloads"].pop()
    del before["per_layer"][-5:]
    assert len(before["configs"]) == 5 and len(before["workloads"]) == 6
    assert len(before["per_layer"]) == 35
    assert all(CELL not in json.dumps(entry) for entry in
               before["workloads"] + before["configs"] + before["per_layer"])
    assert (before["command"], before["paths"], before["run_seconds"]) == (
        ["python3", "benchmarks/run.py"], ["benchmarks", "tests/benchmark"], 26)
    assert [m["bound"] for m in before["end_to_end"]] == [0.01, 0.01, 0.1]
    # a full comparison of every cell, parent and change, fits in twelve hours
    cells = len(MANIFEST["workloads"])
    assert (2 + 14 * cells) * (MANIFEST["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


def test_the_configuration_is_the_catalogs_row_key_by_key_but_for_the_chips_share():
    cfg = json.loads((ROOT / f"benchmarks/configs/{CONFIG}.json").read_text())
    assert cfg["source"] == "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in cfg or cfg[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} == set(cfg["reduced"])
    assert {k: cfg[k] for k in GROUPS} == GROUPS            # the nested groups whole
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 18992)
    assert (cfg["published_num_hidden_layers"], cfg["published_num_experts"],
            cfg["published_vocab_size"]) == (48, 128, 151936)
    assert cfg["router_experts"] == 128 and cfg["experts_held_first"] == 0
    assert 8 * cfg["num_experts"] == 128 and 8 * cfg["vocab_size"] == 151936
    assert "eight chips share each layer" in cfg["deployment"]
    args = cfg["builder_args"]
    assert cfg["builder"] == "deeplearning4j_tpu.zoo.KeyeVL2" and args["remat"] is True
    sa = cfg["sa_config"]
    assert (args["vocab_size"], args["d_model"], args["n_layers"], args["n_heads"],
            args["n_kv_heads"], args["head_dim"], args["rope_theta"], args["mrope_sections"],
            args["index_heads"], args["index_head_dim"], args["index_rope_dim"], args["topk"],
            args["n_experts"], args["top_k"], args["d_expert"],
            args["experts_held"], args["aux_coef"], args["rms_eps"]) == (
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rope_theta"],
        cfg["rope_scaling"]["mrope_section"], sa["indexer_num_heads"], sa["indexer_head_dim"],
        cfg["indexer_rope_dim"], sa["topk"], cfg["router_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        [cfg["experts_held_first"], cfg["num_experts"]], cfg["router_aux_loss_coef"],
        cfg["rms_norm_eps"])
    sched = cfg["updater"]["schedule"]
    assert (sched["kind"], args["warmup"], args["total_steps"], args["lr"]) == (
        "warmup_cosine", sched["warmup_steps"], sched["total_steps"], cfg["updater"]["lr"])
    assert args["warmup"] == 2000
    assert cfg["inputs"]["labels"] == {"kind": "tokens", "vocab": 18992}
    for key in ("num_hidden_layers", "num_experts", "vocab_size", "pre_norm", "qk_norm", "mrope",
                "indexer_key_norm", "indexer_rope_dim", "indexer_weight_scale", "indexer_query",
                "indexer_input_detached", "indexer_loss_coef", "topk_ties", "chunks",
                "router_aux_loss_coef", "updater", "weights", "data", "vision"):
        assert len(cfg["assumed"][key]) > 40, key
    assert len(cfg["departures"]) == 3 and any("scale" in d for d in cfg["departures"])


#: the chip's readings the cell's limits were set from (PERF.md §6, this cell's entry):
#: number -> (the largest of 18 sound runs, the least of the fp8 control's 3
#: seeds, the least of half the positions planted on 2 seeds)
READINGS = {
    "grad_largest_turn": (0.01879, 0.05639, 0.7680),
    "grad_whole_turn": (0.02194, 0.07085, 0.7288),
    "grad_median_gap": (2.777e-4, 1.922e-3, 0.02762),
    "state_leaf_gap": (1.851e-3, 4.384e-3, 7.516e-3),
    "change_norm_gap": (1.333e-3, 2.18e-5, 8.412e-3),
    "change_median_gap": (1.659e-4, 1.473e-4, 5.181e-4),
}


def test_the_limits_lie_between_the_sound_runs_and_what_must_fail():
    limits = harness.load_cell(CELL).limits
    assert set(limits) == set(READINGS)
    for name, (sound, control, fault) in READINGS.items():
        assert sound * 1.5 < limits[name], name                 # room above every sound run
    # the control fails four numbers on every seed, and not each of them
    failed = {n for n, (_, control, _) in READINGS.items() if control > limits[n]}
    assert failed == {"grad_largest_turn", "grad_whole_turn", "grad_median_gap", "state_leaf_gap"}
    for name in failed:
        assert READINGS[name][1] > 1.4 * limits[name], name
    # the fault fails every number the control does, and the change of the parameters too
    assert {n for n, (_, _, fault) in READINGS.items() if fault > limits[n]} == failed | {"change_norm_gap"}
    # a number the precision hardly moves: between the reading and the 1 of a state
    # left unchanged, with the more room above the reading
    for name in ("change_norm_gap", "change_median_gap"):
        sound = READINGS[name][0]
        assert limits[name] / sound > 2.5 and limits[name] < 1.0


def test_the_zoos_updater_under_the_files_arguments_is_the_references():
    from benchmarks import reference_train as rt
    from deeplearning4j_tpu.zoo import KeyeVL2

    cfg = harness.load_cell(CELL).config
    spec, theirs = cfg["updater"], KeyeVL2(**cfg["builder_args"]).conf().updater
    for step, want in ((0, 0.0), (1, 1.5e-7), (1000, 1.5e-4), (2000, 3e-4)):
        assert float(theirs._lr(step)) == pytest.approx(want) == pytest.approx(
            float(rt.learning_rate(spec, step)))
    assert (theirs.beta1, theirs.beta2, theirs.eps, theirs.weight_decay) == (
        spec["beta1"], spec["beta2"], spec["eps"], spec["weight_decay"])


def test_parameters_pinned_to_the_digit():
    cfg = harness.load_cell(CELL).config
    params, state = jax.eval_shape(lambda k: reference.make_params(k, cfg), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tree))     # noqa: E731
    outside = {k: v for k, v in params[1].items() if k != "mlp"}
    assert count(params[1]["mixer"]["indexer"]) == 2_261_120 == (
        2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64)
    assert count(outside) + count(params[1]["mlp"]["Wr"]) == 21_401_984
    assert count({k: params[1]["mlp"][k] for k in ("Wg", "Wu", "Wd")}) == 16 * 4_718_592
    assert [count(p) for p in params[1:5]] == [96_899_456] * 4
    assert count(params) == 465_391_104 == cfg["parameters"]
    assert len(params) == len(state) == 7
    assert [sorted(s) for s in state] == [
        [], *[["loss_term", "loss_term_indexer", "moe_stats", "sparse_tiles_visited"]] * 4, [], []]
    sizes = [int(np.prod(p.shape)) for p in jax.tree.leaves(params)]
    assert sizes.count(max(sizes)) == 2 and sizes.index(max(sizes)) == 0
    from benchmarks.drivers import fit

    box = {}

    def build():
        box["model"] = fit.build_model(cfg)
        return box["model"].params, box["model"].state

    model_params, model_state = jax.eval_shape(build)
    assert jax.tree.structure(model_params) == jax.tree.structure(params)
    assert [s.shape for s in jax.tree.leaves(model_params)] == [
        p.shape for p in jax.tree.leaves(params)]
    assert jax.tree.structure(model_state) == jax.tree.structure(state)


def test_the_counts_of_operations_and_bytes():
    cell = harness.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    seq, layers = 16384, 4
    pairs = reference.selected_pairs(cfg, traffic)
    assert pairs == 2048 * 2049 // 2 + (seq - 2048) * 2048 == 31_458_304
    assert reference.causal_pairs(traffic) == seq * (seq + 1) // 2
    attention = reference.sparse_attention_flops_per_sample(cfg, traffic)
    assert attention == layers * 7 * 2 * 32 * 128 * pairs
    # the counts the configuration was sized by, a layer: the selected attention
    # 0.52 TFLOP forward, the indexer's scores 0.28 (the head 1.27 a step)
    assert 0.51e12 < attention / layers * 2 / 7 < 0.53e12
    index = reference.indexer_flops_per_sample(cfg, traffic)
    assert 0.27e12 < index / layers < 0.29e12
    dot = reference.dot_flops_per_sample(cfg, traffic)
    macs = layers * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * (1024 + 64 + 16) + 2048 * 128) + 2048 * 18992
    assert dot == 6 * seq * macs                # the three kernels' products apart
    loss = reference.indexer_loss_flops_per_sample(cfg, traffic)
    assert loss == layers * 2 * (32 * 128 + 3 * 16 * 64) * pairs
    experts = reference.expert_flops_per_sample(cfg, traffic)
    assert experts == layers * 9 * 2 * 2048 * 768 * seq        # one held pair a token expected
    whole = reference.train_flops_per_sample(cfg, traffic)
    assert whole == dot + index + attention + loss + experts
    mechanism = index + attention + loss
    assert 0.35 < mechanism / whole < 0.5
    moved = reference.sparse_attention_bytes_per_sample(cfg, traffic)
    assert moved == layers * (seq * (4 * 32 + 4 * 4) * 128 * 2 + 2 * pairs / 8)
    # the operations bound the attention and the choice at the v5e's peaks
    assert attention / 197e12 > moved / 819e9
    chosen = reference.indexer_bytes_per_sample(cfg, traffic)
    assert chosen == layers * (seq * (17 * 64 * 2 + 16 * 4) + reference.causal_pairs(traffic) / 8)
    assert index / 197e12 > chosen / 819e9


def test_the_tiny_stand_in_keeps_every_mechanism_and_is_picked_up_by_its_files():
    assert CONFIG in CONFIGS
    tiny = tiny_cell(CONFIG)
    args = tiny.config["builder_args"]
    assert tiny.traffic["seq"] > args["topk"]                  # the choice bites
    assert (args["n_experts"], args["top_k"], args["experts_held"], args["warmup"]) == (
        8, 2, [2, 2], 4)
    assert args["dtype"] == "float32" and tiny.limits
    assert args["n_heads"] > args["n_kv_heads"] and len(set(args["mrope_sections"])) > 1
    assert tiny.config["sa_config"]["topk"] == args["topk"]
    text = (ROOT / f"benchmarks/configs/{CONFIG}.tiny.json").read_text()
    assert len(json.loads(text)["limits_set_from"]) > 100
    assert set(harness.load_cell(CELL).limits) - {"change_norm_gap"} <= set(tiny.limits)


def test_the_generator_at_the_cells_traffic():
    cell = harness.load_cell(CELL)
    big = 2 ** 31 + 4040
    pool = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert len(pool) == 4
    for x, y in pool:
        assert x.shape == y.shape == (1, 16384) and x.dtype == y.dtype == np.int32
        assert 0 <= min(x.min(), y.min()) and max(x.max(), y.max()) < 18992
    assert len({x.tobytes() for x, _ in pool}) == 4
    again = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert all(np.array_equal(a, b) for pair, other in zip(pool, again) for a, b in zip(pair, other))


# ------------------------------------------------- the readers, on a hand-made trace
TRAIN = "jit(train_step)/"
SPARSE = "mixer.SparseAttentionLayer/"
EXPERTS = "mlp.SparseExpertsLayer/"


def fwd(n):
    return TRAIN + f"jvp({n}.DecoderBlock)/checkpoint/"


def bwd(n):
    return TRAIN + f"transpose(jvp({n}.DecoderBlock))/jvp({n}.DecoderBlock)/checkpoint/"


def again(n):
    return bwd(n) + "rematted_computation/"


KERNEL = "sparse_attention/sparse_attention_{}/pallas_call"
# one step of the device: (name, op_name, hlo_category, seconds)
LAYOUT = [
    ("fusion.1 fusion", TRAIN + "jvp(0.EmbeddingSequenceLayer)/gather", "loop fusion", 0.002),
    ("fusion.2 fusion", fwd(1) + SPARSE + "dot_general", "convolution fusion", 0.010),
    ("fusion.3 fusion", fwd(1) + SPARSE + "mul", "loop fusion", 0.002),
    ("fusion.4 fusion", fwd(1) + SPARSE + "indexer/dot_general", "convolution fusion", 0.003),
    ("custom-call.1 custom-call", fwd(1) + SPARSE + "indexer/indexer_topk/indexer_topk/pallas_call",
     "custom-call", 0.020),
    ("fusion.14 fusion", fwd(1) + SPARSE + "indexer/indexer_topk/transpose", "loop fusion", 0.001),
    ("custom-call.2 custom-call", fwd(1) + SPARSE + KERNEL.format("fwd"), "custom-call", 0.030),
    ("fusion.5 fusion", fwd(1) + SPARSE + "indexer/while/body/dot_general", "convolution fusion", 0.015),
    ("fusion.6 fusion", fwd(1) + EXPERTS + "router/dot_general", "convolution fusion", 0.001),
    ("custom-call.3 custom-call", "ragged-dot-none", "custom-call", 0.004),
    ("fusion.7 fusion", TRAIN + "jvp(6.RnnOutputLayer)/dot_general", "convolution fusion", 0.008),
    ("fusion.8 fusion", again(1) + SPARSE + "dot_general", "convolution fusion", 0.010),
    ("custom-call.4 custom-call", bwd(1) + SPARSE + KERNEL.format("bwd"), "custom-call", 0.060),
    ("fusion.9 fusion", bwd(1) + SPARSE + "sparse_attention/reduce_sum", "loop fusion", 0.002),
    ("fusion.10 fusion", bwd(1) + SPARSE + "indexer/while/body/dot_general", "convolution fusion",
     0.025),
    ("fusion.11 fusion", bwd(1) + SPARSE + "dot_general", "convolution fusion", 0.020),
    ("fusion.12 fusion", bwd(1) + EXPERTS + "combine/mul", "loop fusion", 0.003),
    ("fusion.13 fusion", TRAIN + "updater/sub", "loop fusion", 0.020),
    ("copy-done.5 copy-done", None, "copy-done", 0.002),
]
KERNEL_S = 0.030 + 0.060 + 0.002
CHOICE_S = 0.020 + 0.001
INDEXER_S = 0.003 + CHOICE_S + 0.015 + 0.025
MIXER_S = INDEXER_S + KERNEL_S + 0.010 + 0.002 + 0.010 + 0.020
DOT_S = sum(row[3] for row in LAYOUT if row[1] and row[1].endswith("dot_general"))
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


def test_the_five_metrics_have_their_cases_here(scoped_metric_cases, tested_in_their_own_file):
    assert scoped_metric_cases == dict.fromkeys(NEW_METRICS, "test_keye_vl2_cell.py")
    assert not set(NEW_METRICS) & set(tested_in_their_own_file)
    listed = {m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert listed == set(NEW_METRICS)


@pytest.mark.parametrize("reader, want_ms", [
    (sparse_attention_ms, 1e3 * MIXER_S), (indexer_ms, 1e3 * INDEXER_S)],
    ids=lambda v: getattr(v, "__name__", "").rpartition(".")[2] or None)
def test_reader_sums_the_operations_under_its_scopes(reader, want_ms):
    assert reader.read(hand_made()) == pytest.approx(want_ms)


def test_the_kernels_roofline_counts_the_chosen_pairs():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.sparse_attention_flops_per_sample(cell.config, cell.traffic)
    moved = reference.sparse_attention_bytes_per_sample(cell.config, cell.traffic)
    assert sparse_attention_kernel_roofline.read(ctx) == pytest.approx(
        100 * flops / 197e12 / KERNEL_S)                            # the operations bound it
    slow_memory = {**ctx, "peaks": {**PEAKS, "hbm_bytes_per_s": 1e9}}
    assert sparse_attention_kernel_roofline.read(slow_memory) == pytest.approx(
        100 * moved / 1e9 / KERNEL_S)
    without = {**ctx, "module": importlib.import_module("benchmarks.configs.mellum2_12b")}
    assert sparse_attention_kernel_roofline.read(without) is None
    assert 0 < sparse_attention_kernel_roofline.read(ctx) < 100
    # a kernel at the chip's peak over every causal pair still reads under 100
    causal = reference.causal_pairs(cell.traffic) / reference.selected_pairs(cell.config, cell.traffic)
    assert causal > 4


def test_the_choices_roofline_counts_every_causal_pairs_scores():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.indexer_flops_per_sample(cell.config, cell.traffic)
    moved = reference.indexer_bytes_per_sample(cell.config, cell.traffic)
    assert indexer_kernel_roofline.read(ctx) == pytest.approx(100 * flops / 197e12 / CHOICE_S)
    slow_memory = {**ctx, "peaks": {**PEAKS, "hbm_bytes_per_s": 1e9}}
    assert indexer_kernel_roofline.read(slow_memory) == pytest.approx(100 * moved / 1e9 / CHOICE_S)
    without = {**ctx, "module": importlib.import_module("benchmarks.configs.kimi_linear_48b")}
    assert indexer_kernel_roofline.read(without) is None
    assert 0 < indexer_kernel_roofline.read(ctx) < 100


def test_the_tiles_reader_reads_the_gauge_worst_layer(monitoring_off):
    monitoring = monitoring_off
    ctx = hand_made()
    assert sparse_tiles_visited_pct.read(ctx) is None           # no gauge, nothing to read
    gauge = monitoring.registry().gauge(
        "dl4j_train_sparse_attn_tiles_visited", "share", labels=("layer",))
    for layer, share in (("1", 0.97), ("2", 1.0), ("3", 0.5)):
        gauge.labels(layer=layer).set(share)
    assert sparse_tiles_visited_pct.read(ctx) == pytest.approx(100.0)


def test_the_readers_every_cell_has_read_the_same_trace():
    ctx = hand_made()
    cell = ctx["cell"]
    assert step_conv_dot_ms.read(ctx) == pytest.approx(1e3 * DOT_S)
    dot = reference.dot_flops_per_sample(cell.config, cell.traffic)
    assert conv_dot_roofline.read(ctx) == pytest.approx(100 * dot / 197e12 / DOT_S)
    whole = reference.train_flops_per_sample(cell.config, cell.traffic)
    steps = len(tr.steps_in_window(ctx["trace"], ctx["trace"].devices[0]))
    assert step_mfu_pct.read(ctx) == pytest.approx(100 * whole * steps / ctx["trace"].window_s / 197e12)
    kinds = pt.scope_seconds(ctx["program_trace"])
    assert kinds["scoped"] and kinds["moves"] == pytest.approx(0.002) and kinds["unnamed"] == 0.0


def test_a_step_without_the_scopes_gives_the_readers_nothing_to_read():
    plain = [("fusion.1 fusion", TRAIN + "jvp(2.DecoderBlock)/dot_general", "convolution fusion", 0.05),
             ("fusion.2 fusion", TRAIN + "jvp(loss)/reduce_sum", "loop fusion", 0.001),
             ("fusion.3 fusion", TRAIN + "updater/sub", "loop fusion", 0.004)]
    ctx = hand_made(plain)
    for reader in (sparse_attention_ms, indexer_ms, sparse_attention_kernel_roofline,
                   indexer_kernel_roofline):
        assert reader.read(ctx) is None
    # an indexer scope under another layer is not this mixer's
    stray = hand_made(plain + [("fusion.9 fusion", TRAIN + "jvp(3.Other)/indexer/mul",
                                "loop fusion", 0.01)])
    assert indexer_ms.read(stray) is None
    unnamed = hand_made(LAYOUT + [("fusion.77 fusion", None, None, 0.05)])     # over 2 % unnamed
    assert pt.scope_seconds(unnamed["program_trace"]) is None
    for reader in (sparse_attention_ms, indexer_ms, sparse_attention_kernel_roofline,
                   indexer_kernel_roofline):
        assert reader.read(unnamed) is None


def test_the_result_line_of_a_traced_run_carries_every_metric_of_the_cell(monitoring_off):
    monitoring = monitoring_off
    monitoring.registry().gauge("dl4j_train_sparse_attn_tiles_visited", "share",
                                labels=("layer",)).labels(layer="1").set(0.9)
    ctx = hand_made()
    ctx["counters"] = {"data_wait_s": 0.001, "traced_host_s": 10.0}
    metrics = harness.read_layer_metrics(ctx["cell"], ctx)
    setup = {"setup_init_s", "setup_step_lower_s", "setup_step_load_s", "setup_cache_misses"}
    assert set(metrics) == {m["name"] for m in ctx["cell"].per_layer} - setup, (
        {m["name"] for m in ctx["cell"].per_layer} ^ set(metrics))
    assert metrics["sparse_attention_ms"]["value"] > metrics["indexer_ms"]["value"] > 0
    assert 0 < metrics["sparse_attention_kernel_roofline"]["value"] < 100
    assert 0 < metrics["indexer_kernel_roofline"]["value"] < 100
    assert metrics["sparse_tiles_visited_pct"]["value"] == pytest.approx(90.0)


def test_the_readers_read_nothing_and_do_not_raise_on_a_program_without_the_mixer(monitoring_off):
    """The parent's side of a traced run: the cell's readers over another
    configuration's trace return None, and the line leaves them out."""
    ctx = hand_made(cell="mellum2_12b_pretrain_b1_s8192", layout=[
        ("fusion.1 fusion", TRAIN + "jvp(2.DecoderBlock)/dot_general", "convolution fusion", 0.05),
        ("fusion.2 fusion", TRAIN + "updater/sub", "loop fusion", 0.004)])
    for name in NEW_METRICS:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert reader.read(ctx) is None
