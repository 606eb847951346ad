"""The cell ``sdar_30b_pretrain_b1_s8192`` and its configuration on the CPU: the
files and the manifest's entries, the configuration against the catalog's row
key by key, the parameter count to the digit, the analytic counts (the visible
pairs against a brute-force count of the dense mask), the three new readers
(``block_diffusion_attention_ms``, ``block_diffusion_attention_roofline``,
``diffusion_loss_ms``) and the four thin callers of the expert layer's
readers (``diffusion_moe_ms``, ``diffusion_moe_route_ms``,
``diffusion_expert_matmul_roofline``, ``diffusion_moe_load_max_over_mean``) on
hand-made traces, and the generator at the cell's traffic. The configuration's sound run, its faults and its control are cases
of the tests that take every configuration under ``benchmarks/configs`` by its
files."""

import importlib
import json

import jax
import numpy as np
import pytest

from benchmark_tiny import CONFIGS, ROOT, tiny_cell
from benchmarks import harness, program_trace as pt, trace_reduce as tr, traffic_gen
from benchmarks.configs import sdar_30b as reference
from benchmarks.layer_metrics import (
    block_diffusion_attention_ms, block_diffusion_attention_roofline, conv_dot_roofline,
    diffusion_expert_matmul_roofline, diffusion_loss_ms, diffusion_moe_load_max_over_mean,
    diffusion_moe_ms, diffusion_moe_route_ms, step_conv_dot_ms, step_mfu_pct,
)
from benchmarks.trace_reduce import Device, Op

CELL, CONFIG, TRAFFIC = "sdar_30b_pretrain_b1_s8192", "sdar_30b", "fit_b1_s8192"
NEW_METRICS = ("block_diffusion_attention_ms", "block_diffusion_attention_roofline",
               "diffusion_loss_ms", "diffusion_moe_ms", "diffusion_moe_route_ms",
               "diffusion_expert_matmul_roofline", "diffusion_moe_load_max_over_mean")
NEW = len(NEW_METRICS)
MANIFEST = harness.load_manifest()
#: the catalog row's ``config`` (its ``source_url`` is the configuration's ``source``)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


# ------------------------------------------------------------- the cell's files
def test_the_cell_its_files_and_its_entries():
    entry = MANIFEST["workloads"][-1]
    assert (entry["name"], entry["config"], entry["traffic"], entry["chips"]) == (
        CELL, CONFIG, TRAFFIC, 1)
    assert "288 of 1,024" in entry["why"] and "1/8" in entry["why"] and len(entry["why"]) <= 200
    for path in (f"benchmarks/cells/{CELL}.json", f"benchmarks/traffic/{TRAFFIC}.json",
                 f"benchmarks/configs/{CONFIG}.json", f"benchmarks/configs/{CONFIG}.py",
                 f"benchmarks/configs/{CONFIG}.tiny.json",
                 *(f"benchmarks/layer_metrics/{m}.py" for m in NEW_METRICS)):
        assert (ROOT / path).is_file(), path
    cell = harness.load_cell(CELL)
    assert (cell.traffic["batch"], cell.traffic["seq"], cell.traffic["pool"]) == (1, 8192, 4)
    entries = MANIFEST["per_layer"][-NEW:]
    assert [m["name"] for m in entries] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_samples_per_s_per_chip"
               for m in entries)
    assert [m["source"] for m in entries] == ["device_trace"] * 6 + ["program_counter"]
    assert [m["layer"] for m in entries] == [
        "kernels", "kernels", "block-diffusion objective", "sparse experts", "sparse experts",
        "kernels", "sparse experts"]
    assert [(m["unit"], m["better"]) for m in entries] == [
        ("ms", "lower"), ("%", "higher"), ("ms", "lower"), ("ms", "lower"), ("ms", "lower"),
        ("%", "higher"), ("ratio", "lower")]
    # the thin callers' layers and units are those of the entries they call
    for name in NEW_METRICS[3:]:
        theirs = next(m for m in MANIFEST["per_layer"] if m["name"] == name[len("diffusion_"):])
        mine = next(m for m in entries if m["name"] == name)
        assert [mine[k] for k in ("unit", "better", "source", "layer", "moves")] == [
            theirs[k] for k in ("unit", "better", "source", "layer", "moves")]
    reported = [m["name"] for m in cell.per_layer]
    assert reported[-NEW:] == list(NEW_METRICS)
    # every metric with no list of its own, and nothing that lists another cell
    assert set(reported[:-NEW]) == {m["name"] for m in MANIFEST["per_layer"]
                                    if "workloads" not in m}
    for other in MANIFEST["workloads"][:-1]:
        theirs = {m["name"] for m in harness.load_cell(other["name"]).per_layer}
        assert not theirs & set(NEW_METRICS)
    assert MANIFEST["configs"][-1]["name"] == CONFIG and len(MANIFEST["configs"]) == 7


def test_nothing_the_benchmark_had_is_changed():
    """One configuration, one cell and seven per-layer entries more, each at the
    end of its list; what stood before stands as it stood."""
    before = json.loads(json.dumps(MANIFEST))
    before["configs"].pop()
    before["workloads"].pop()
    del before["per_layer"][-NEW:]
    assert len(before["configs"]) == 6 and len(before["workloads"]) == 7
    assert len(before["per_layer"]) == 40
    assert all(CELL not in json.dumps(entry) and CONFIG not in json.dumps(entry) for entry in
               before["workloads"] + before["configs"] + before["per_layer"])
    assert (before["command"], before["paths"], before["run_seconds"]) == (
        ["python3", "benchmarks/run.py"], ["benchmarks", "tests/benchmark"], 26)
    assert [m["bound"] for m in before["end_to_end"]] == [0.01, 0.01, 0.1]
    cells = len(MANIFEST["workloads"])
    assert (2 + 14 * cells) * (MANIFEST["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_configuration_is_the_catalogs_row_key_by_key_but_for_the_chips_share():
    cfg = json.loads((ROOT / f"benchmarks/configs/{CONFIG}.json").read_text())
    assert cfg["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    differs = {k for k, v in PUBLISHED.items() if k not in cfg or cfg[k] != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} == set(cfg["reduced"])
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 18992)
    assert (cfg["published_num_hidden_layers"], cfg["published_num_experts"],
            cfg["published_vocab_size"]) == (48, 128, 151936)
    assert 8 * cfg["num_experts"] == cfg["router_experts"] == 128 and 8 * cfg["vocab_size"] == 151936
    assert "eight chips share each layer" in cfg["deployment"] and "1/8" in cfg["deployment"]
    args = cfg["builder_args"]
    assert cfg["builder"] == "deeplearning4j_tpu.zoo.SDAR" and args["remat"] is True
    noise = cfg["noise"]
    assert (args["vocab_size"], args["d_model"], args["n_layers"], args["n_heads"],
            args["n_kv_heads"], args["head_dim"], args["rope_theta"], args["n_experts"],
            args["top_k"], args["d_expert"], args["experts_held"], args["aux_coef"],
            args["rms_eps"], args["block"], args["t_min"], args["mask_id"]) == (
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rope_theta"],
        cfg["router_experts"], cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        [cfg["experts_held_first"], cfg["num_experts"]], cfg["router_aux_loss_coef"],
        cfg["rms_norm_eps"], noise["block"], noise["t_min"], noise["mask_id"])
    # the noise's seed is the run's (a leaf of the state), not the file's
    assert set(noise) == {"block", "t_min", "mask_id"}
    # the mask is the slice's last id, and no clean token or label is it
    assert noise["mask_id"] == cfg["vocab_size"] - 1
    assert cfg["inputs"]["features"] == cfg["inputs"]["labels"] == {
        "kind": "tokens", "vocab": noise["mask_id"]}
    sched = cfg["updater"]["schedule"]
    assert (sched["kind"], args["warmup"], args["total_steps"], args["lr"]) == (
        "warmup_cosine", sched["warmup_steps"], sched["total_steps"], cfg["updater"]["lr"])
    assert args["warmup"] == 2000
    for key in ("num_hidden_layers", "num_experts", "vocab_size", "block_length", "noise_schedule",
                "noise_draws", "mask_id", "target_alignment", "positions", "attention_mask",
                "pre_norm", "qk_norm", "router_aux_loss_coef", "updater", "weights", "data",
                "expert_placement"):
        assert len(cfg["assumed"][key]) > 40, key
    assert len(cfg["departures"]) == 3 and any("scale" in d for d in cfg["departures"])


def test_the_zoos_updater_under_the_files_arguments_is_the_references():
    from benchmarks import reference_train as rt
    from deeplearning4j_tpu.zoo import SDAR

    cfg = harness.load_cell(CELL).config
    spec, theirs = cfg["updater"], SDAR(**cfg["builder_args"]).conf().updater
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
    assert count(outside) + count(params[1]["mlp"]["Wr"]) == 19_140_864 == (
        18_874_368 + 262_144 + 4_352)
    assert count({k: params[1]["mlp"][k] for k in ("Wg", "Wu", "Wd")}) == 16 * 4_718_592
    assert [count(p) for p in params[1:5]] == [94_638_336] * 4
    assert count(params[0]) + count(params[-1]) == 77_791_232
    assert count(params) == 456_346_624 == cfg["parameters"]
    assert 16 * count(params) / 1e9 == pytest.approx(7.30, abs=0.005)
    assert [sorted(s) for s in state] == [["noise_seed", "noise_step"],
                                          *[["loss_term", "moe_stats"]] * 4, [], []]
    # the published model, from the same parts: 48 layers of 128 experts, the whole vocabulary
    published = 48 * (19_140_864 + 128 * 4_718_592) + 2 * 151_936 * 2048 + 2048
    assert published == pytest.approx(30.53e9, rel=1e-3)
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


@pytest.mark.parametrize("seq,block", [(8, 4), (48, 4), (64, 8), (96, 4), (128, 16)])
def test_the_visible_pairs_are_a_brute_force_count_of_the_mask(seq, block):
    i, j = np.arange(2 * seq)[:, None], np.arange(2 * seq)[None, :]
    half = seq
    qb, kb = np.where(i >= half, i - half, i) // block, np.where(j >= half, j - half, j) // block
    brute = 0
    for q in range(2 * seq):
        for k in range(2 * seq):
            if q < half:
                brute += (k < half and kb[0, k] == qb[q, 0]) or (k >= half and kb[0, k] < qb[q, 0])
            else:
                brute += k >= half and kb[0, k] <= qb[q, 0]
    assert reference.visible_pairs(seq, block) == brute
    assert int(np.asarray(reference.visible(i, j, seq, block)).sum()) == brute


def test_the_counts_of_operations_and_bytes_at_the_cells_size():
    cell = harness.load_cell(CELL)
    cfg, traffic = cell.config, cell.traffic
    seq, layers = 8192, 4
    pairs = reference.visible_pairs(seq, 4)
    assert pairs == 32_768 + 33_538_048 + 33_570_816 == 67_141_632
    assert pairs == pytest.approx(2 * seq * (seq + 1) // 2, rel=1e-3)   # twice a causal mask over L
    assert reference.tiles_visited(seq, 4) == 288
    attention = reference.block_diffusion_attention_flops_per_sample(cfg, traffic)
    assert attention == layers * 32 * (4 + 10) * 128 * pairs
    assert attention / 1e12 == pytest.approx(15.4007, abs=1e-4)
    assert attention * 12 / 14 / 1e12 == pytest.approx(13.2, abs=0.01)     # at 3x the forward
    dot = reference.dot_flops_per_sample(cfg, traffic)
    head = 6 * seq * 2048 * 18992
    assert head / 1e12 == pytest.approx(1.91, abs=0.01)
    assert dot - head == 6 * 2 * seq * layers * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128)
    experts = reference.expert_flops_per_sample(cfg, traffic)
    assert experts == layers * 9 * 2 * 2048 * 768 * 2 * seq     # one held pair a position
    assert (dot - head + experts) / 1e12 == pytest.approx(9.38, abs=0.01)
    whole = reference.train_flops_per_sample(cfg, traffic)
    assert whole == dot + attention + experts
    assert whole / 1e12 == pytest.approx(26.69, abs=0.01)
    assert 0.57 < attention / whole < 0.58
    moved = reference.block_diffusion_attention_bytes_per_sample(cfg, traffic)
    assert moved == layers * 2 * seq * (4 * 32 + 4 * 4) * 128 * 2
    assert attention / 197e12 > moved / 819e9                  # the operations bound it


def test_the_tiny_stand_in_keeps_every_mechanism_and_is_picked_up_by_its_files():
    assert CONFIG in CONFIGS
    tiny = tiny_cell(CONFIG)
    args, noise = tiny.config["builder_args"], tiny.config["noise"]
    assert (args["n_experts"], args["top_k"], args["experts_held"], args["warmup"]) == (8, 2, [2, 4], 4)
    assert args["dtype"] == "float32" and tiny.limits and args["n_heads"] > args["n_kv_heads"]
    assert noise["mask_id"] == args["mask_id"] == args["vocab_size"] - 1 == 127
    assert tiny.config["inputs"]["features"]["vocab"] == 127
    assert tiny.traffic["seq"] % noise["block"] == 0 and tiny.traffic["seq"] // noise["block"] > 4
    text = (ROOT / f"benchmarks/configs/{CONFIG}.tiny.json").read_text()
    assert len(json.loads(text)["limits_set_from"]) > 100
    assert set(harness.load_cell(CELL).limits) - {"change_norm_gap"} <= set(tiny.limits)


def test_the_leak_is_planted_in_the_steps_and_not_in_the_weights():
    """``calibrate_leak.py``'s fault: the same weights and placement as the
    sound run (the placement's probe goes through the mask as written), and a
    step that sees the clean block of a noisy block's own index."""
    from benchmarks.calibrate_leak import leak_planted

    tiny = tiny_cell(CONFIG)
    cfg, key = tiny.config, jax.random.key(2 ** 31 + 21)
    sound = jax.jit(lambda k: reference.make_params(k, cfg))(key)
    x, y = (jax.numpy.asarray(a) for a in traffic_gen.make_pool(cfg["inputs"], tiny.traffic, 9)[0])
    loss = jax.jit(lambda p, s: reference.loss_fn(p, s, x, y, cfg)[0])(*sound)
    with leak_planted(reference) as module:
        planted = jax.jit(lambda k: module.make_params(k, cfg))(key)
        leaked_loss = jax.jit(lambda p, s: module.loss_fn(p, s, x, y, cfg)[0])(*planted)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(sound), jax.tree.leaves(planted)))
    assert float(leaked_loss) != float(loss)
    assert reference.visible.__name__ == "visible"


def test_the_generator_at_the_cells_traffic():
    cell = harness.load_cell(CELL)
    big = 2 ** 31 + 4242
    pool = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert len(pool) == 4
    for x, y in pool:
        assert x.shape == y.shape == (1, 8192) and x.dtype == y.dtype == np.int32
        assert 0 <= min(x.min(), y.min()) and max(x.max(), y.max()) < 18991    # never the mask
    assert len({x.tobytes() for x, _ in pool}) == 4
    again = traffic_gen.make_pool(cell.config["inputs"], cell.traffic, big)
    assert all(np.array_equal(a, b) for pair, other in zip(pool, again) for a, b in zip(pair, other))


# ------------------------------------------------- the readers, on a hand-made trace
TRAIN = "jit(train_step)/"


def fwd(n):
    return TRAIN + f"jvp({n}.DecoderBlock)/"


def bwd(n):
    return TRAIN + f"transpose(jvp({n}.DecoderBlock))/jvp({n}.DecoderBlock)/checkpoint/"


KERNEL = "flash_attention/block_diffusion/flash_attention_block_diffusion_{}/pallas_call"
NOISE = "0.BlockDiffusionNoiseLayer"
MOE = "mlp.SparseExpertsLayer/"
# one step of the device: (name, op_name, hlo_category, seconds)
LAYOUT = [
    ("fusion.1 fusion", TRAIN + f"jvp({NOISE})/block_diffusion_noise/jit(_uniform)/xor",
     "loop fusion", 0.001),
    ("fusion.2 fusion", TRAIN + f"jvp({NOISE})/block_diffusion_noise/gather", "loop fusion", 0.002),
    ("fusion.3 fusion", fwd(1) + "dot_general", "convolution fusion", 0.020),
    ("custom-call.1 custom-call", fwd(1) + KERNEL.format("fwd"), "custom-call", 0.030),
    ("fusion.4 fusion", fwd(1) + "mlp.SparseExpertsLayer/router/dot_general",
     "convolution fusion", 0.001),
    ("fusion.5 fusion", TRAIN + "jvp(loss)/block_diffusion_loss/dot_general",
     "convolution fusion", 0.010),
    ("fusion.6 fusion", TRAIN + "jvp(loss)/block_diffusion_loss/reduce_max", "loop fusion", 0.004),
    ("fusion.7 fusion", TRAIN + "transpose(jvp(loss))/block_diffusion_loss/dot_general",
     "convolution fusion", 0.020),
    ("fusion.8 fusion", bwd(1) + "rematted_computation/dot_general", "convolution fusion", 0.020),
    ("custom-call.2 custom-call", bwd(1) + KERNEL.format("bwd"), "custom-call", 0.070),
    ("fusion.9 fusion", bwd(1) + "flash_attention/block_diffusion/reduce_sum", "loop fusion", 0.002),
    ("fusion.10 fusion", bwd(1) + "dot_general", "convolution fusion", 0.040),
    ("fusion.11 fusion", TRAIN + f"transpose(jvp({NOISE}))/jvp({NOISE})/checkpoint/"
     "block_diffusion_noise/scatter-add", "loop fusion", 0.003),
    ("fusion.12 fusion", TRAIN + "updater/sub", "loop fusion", 0.020),
    ("copy-done.5 copy-done", None, "copy-done", 0.002),
    ("sort.1 sort", fwd(1) + MOE + "route/sort", "sort", 0.002),
    ("custom-call.3 custom-call", fwd(1) + MOE + "expert_matmul/gmm/pallas_call", "custom-call",
     0.009),
    ("fusion.13 fusion", bwd(1) + MOE + "combine/gather", "loop fusion", 0.004),
    ("custom-call.4 custom-call", "ragged-dot-none", "custom-call", 0.018),
]
KERNEL_S = 0.030 + 0.070 + 0.002
OBJECTIVE_S = 0.001 + 0.002 + 0.010 + 0.004 + 0.020 + 0.003
MATMUL_S = 0.009 + 0.018
ROUTE_S = 0.001 + 0.002 + 0.004
EXPERTS_S = MATMUL_S + ROUTE_S
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


def test_the_three_metrics_have_their_cases_here(scoped_metric_cases, tested_in_their_own_file):
    assert scoped_metric_cases == dict.fromkeys(NEW_METRICS, "test_sdar_cell.py")
    assert not set(NEW_METRICS) & set(tested_in_their_own_file)
    listed = {m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]}
    assert listed == set(NEW_METRICS)


@pytest.mark.parametrize("reader, want_ms", [
    (block_diffusion_attention_ms, 1e3 * KERNEL_S), (diffusion_loss_ms, 1e3 * OBJECTIVE_S),
    (diffusion_moe_ms, 1e3 * EXPERTS_S), (diffusion_moe_route_ms, 1e3 * ROUTE_S)],
    ids=lambda v: getattr(v, "__name__", "").rpartition(".")[2] or None)
def test_reader_sums_the_operations_under_its_scopes(reader, want_ms):
    assert reader.read(hand_made()) == pytest.approx(want_ms)


def test_the_kernels_roofline_counts_the_visible_pairs():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.block_diffusion_attention_flops_per_sample(cell.config, cell.traffic)
    moved = reference.block_diffusion_attention_bytes_per_sample(cell.config, cell.traffic)
    assert block_diffusion_attention_roofline.read(ctx) == pytest.approx(
        100 * flops / 197e12 / KERNEL_S)                            # the operations bound it
    slow_memory = {**ctx, "peaks": {**PEAKS, "hbm_bytes_per_s": 1e9}}
    assert block_diffusion_attention_roofline.read(slow_memory) == pytest.approx(
        100 * moved / 1e9 / KERNEL_S)
    without = {**ctx, "module": importlib.import_module("benchmarks.configs.mellum2_12b")}
    assert block_diffusion_attention_roofline.read(without) is None
    assert 0 < block_diffusion_attention_roofline.read(ctx) < 100


def test_the_grouped_products_roofline_counts_one_held_expert_a_position():
    ctx = hand_made()
    cell = ctx["cell"]
    flops = reference.expert_flops_per_sample(cell.config, cell.traffic)
    assert flops == 4 * 9 * 2 * 2048 * 768 * 2 * 8192              # one pair a position of the 2L
    assert diffusion_expert_matmul_roofline.read(ctx) == pytest.approx(
        100 * flops / 197e12 / MATMUL_S)
    without = {**ctx, "module": importlib.import_module("benchmarks.configs.ouro_2p6b")}
    assert diffusion_expert_matmul_roofline.read(without) is None


def test_the_held_load_is_read_from_the_programs_gauge(monitoring_off):
    monitoring = monitoring_off
    assert diffusion_moe_load_max_over_mean.read({}) is None      # a program without the gauge
    monitoring.enable()
    gauge = monitoring.fit_monitor().moe_load["load_max_over_mean"]
    gauge.labels(layer="1").set(4.61)
    gauge.labels(layer="4").set(4.83)
    assert diffusion_moe_load_max_over_mean.read({}) == 4.83       # the worst layer
    monitoring.disable()


def test_the_readers_every_cell_has_read_the_same_trace():
    ctx = hand_made()
    cell = ctx["cell"]
    assert step_conv_dot_ms.read(ctx) == pytest.approx(1e3 * DOT_S)
    dot = reference.dot_flops_per_sample(cell.config, cell.traffic)
    assert conv_dot_roofline.read(ctx) == pytest.approx(100 * dot / 197e12 / DOT_S)
    whole = reference.train_flops_per_sample(cell.config, cell.traffic)
    steps = len(tr.steps_in_window(ctx["trace"], ctx["trace"].devices[0]))
    assert step_mfu_pct.read(ctx) == pytest.approx(100 * whole * steps / ctx["trace"].window_s / 197e12)


def test_a_step_without_the_scopes_gives_the_readers_nothing_to_read():
    plain = [("fusion.1 fusion", TRAIN + "jvp(2.DecoderBlock)/flash_attention/flash_attention_fwd/"
              "pallas_call", "custom-call", 0.05),
             ("fusion.2 fusion", TRAIN + "jvp(loss)/reduce_sum", "loop fusion", 0.001),
             ("fusion.3 fusion", TRAIN + "updater/sub", "loop fusion", 0.004)]
    ctx = hand_made(plain)
    for reader in (block_diffusion_attention_ms, block_diffusion_attention_roofline,
                   diffusion_loss_ms, diffusion_moe_ms, diffusion_moe_route_ms,
                   diffusion_expert_matmul_roofline):
        assert reader.read(ctx) is None
    unnamed = hand_made(LAYOUT + [("fusion.77 fusion", None, None, 0.05)])     # over 2 % unnamed
    assert pt.scope_seconds(unnamed["program_trace"]) is None
    for reader in (block_diffusion_attention_ms, block_diffusion_attention_roofline,
                   diffusion_loss_ms, diffusion_moe_ms, diffusion_moe_route_ms,
                   diffusion_expert_matmul_roofline):
        assert reader.read(unnamed) is None


def test_the_result_line_of_a_traced_run_carries_every_metric_of_the_cell(monitoring_off):
    monitoring_off.enable()
    monitoring_off.fit_monitor().moe_load["load_max_over_mean"].labels(layer="2").set(4.7)
    ctx = hand_made()
    ctx["counters"] = {"data_wait_s": 0.001, "traced_host_s": 10.0}
    metrics = harness.read_layer_metrics(ctx["cell"], ctx)
    setup = {"setup_init_s", "setup_step_lower_s", "setup_step_load_s", "setup_cache_misses"}
    assert set(metrics) == {m["name"] for m in ctx["cell"].per_layer} - setup, (
        {m["name"] for m in ctx["cell"].per_layer} ^ set(metrics))
    assert metrics["block_diffusion_attention_ms"]["value"] == pytest.approx(1e3 * KERNEL_S)
    assert 0 < metrics["block_diffusion_attention_roofline"]["value"] < 100
    assert metrics["diffusion_loss_ms"]["value"] == pytest.approx(1e3 * OBJECTIVE_S)
    assert metrics["diffusion_moe_ms"]["value"] == pytest.approx(1e3 * EXPERTS_S)
    assert 0 < metrics["diffusion_expert_matmul_roofline"]["value"] < 100
    assert metrics["diffusion_moe_load_max_over_mean"] == {"value": 4.7, "unit": "ratio"}
    monitoring_off.disable()


def test_the_readers_read_nothing_and_do_not_raise_on_a_program_without_the_mask(monitoring_off):
    """The parent's side of a traced run: the cell's readers over another
    configuration's trace return None, and the line leaves them out."""
    ctx = hand_made(cell="mellum2_12b_pretrain_b1_s8192", layout=[
        ("fusion.1 fusion", TRAIN + "jvp(2.DecoderBlock)/dot_general", "convolution fusion", 0.05),
        ("fusion.2 fusion", TRAIN + "updater/sub", "loop fusion", 0.004)])
    for name in NEW_METRICS:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert reader.read(ctx) is None
