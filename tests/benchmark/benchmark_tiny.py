"""Tiny stand-ins of the benchmark's cells for the CPU tests: the same files,
drivers and code paths, with the sizes a test run can hold. Widths that the
zoo builders fix stay as they are; images, batch, vocabulary and depth shrink.
"""

import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import harness  # noqa: E402

PEAKS = {"flops_per_s": {"bfloat16": 1e12}, "hbm_bytes_per_s": 1e11}


#: limits at the tests' size and in float32, set as the cells' are: above what
#: sound runs read there over six seeds (float32 round-off: ResNet's last stage
#: normalises over 16 values at this size and amplifies it, change_leaf_gap up
#: to 0.009; BERT reads 0 but for LayerNorm's gains, and turns its first gradient
#: by 4e-7) and below what the control (the reference in bfloat16: grad_leaf_gap
#: 0.038 or more, BERT's gradient turned by 0.005 or more) and the faults read
TINY_LIMITS = {
    "resnet50": {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 5e-4, "grad_leaf_gap": 0.01,
                 "change_leaf_gap": 0.025, "grad_median_gap": 5e-4, "change_median_gap": 1.2e-3,
                 "grad_norm_gap": 1e-4, "change_norm_gap": 1e-3, "state_leaf_gap": 1e-4,
                 "state_median_gap": 2e-5},
    "bert_base": {"loss2_gap": 2e-5, "loss3_gap": 2e-5, "grad_leaf_gap": 0.002,
                  "change_leaf_gap": 0.006, "grad_median_gap": 2e-4, "change_median_gap": 2e-4,
                  "change_norm_gap": 1e-4, "grad_largest_turn": 1e-4, "grad_median_turn": 1e-4,
                  "grad_whole_turn": 1e-4},
}


#: every configuration under ``benchmarks/configs``. One that no listed cell
#: uses yet (PERF.md, Open questions) has no cell or traffic file: the tests
#: drive its builder and its reference through the fit driver all the same
CONFIGS = sorted(p.stem for p in (ROOT / "benchmarks" / "configs").glob("*.json"))


def _load(config_name: str) -> harness.Cell:
    manifest = harness.load_manifest()
    for w in manifest["workloads"]:
        if w["config"] == config_name:
            return harness.load_cell(w["name"], manifest)
    config = json.loads((ROOT / "benchmarks" / "configs" / f"{config_name}.json").read_text())
    return harness.Cell(
        name=f"{config_name}_tiny", chips=1, why="no listed cell uses this configuration yet",
        config_name=config_name, config=config, traffic_name="tiny",
        traffic={"driver": "fit", "pool": 4}, limits={},
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"])


def tiny_cell(config_name: str, chips=None) -> harness.Cell:
    """A cell of the configuration cut to a test's size (the listed cell that
    uses it, where there is one), on ``chips`` of the CPU's virtual devices
    where given."""
    cell = copy.deepcopy(_load(config_name))
    if chips is not None:
        cell.chips = chips
    cfg, traffic = cell.config, cell.traffic
    if cell.config_name == "resnet50":
        cfg["builder_args"].update(height=32, width=32, num_classes=10, lr=0.01)
        cfg.update(image_size=32, num_classes=10)
        cfg["updater"]["lr"] = 0.01
        cfg["inputs"]["features"]["shape"] = [32, 32, 3]
        cfg["inputs"]["labels"]["classes"] = 10
        traffic.update(batch=16 * cell.chips)
    elif cell.config_name == "bert_base":
        cfg["builder_args"].update(vocab_size=100, max_len=16, d_model=32, n_layers=2,
                                   n_heads=4, d_ff=64)
        cfg.update(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
                   intermediate_size=64, vocab_size=100, max_position_embeddings=16)
        cfg["inputs"]["features"]["vocab"] = 100
        traffic.update(batch=8 * cell.chips, seq=12)
    else:
        raise ValueError(f"no tiny stand-in for configuration {cell.config_name!r}")
    # float32 at this size: bfloat16 noise on a handful of rows would swamp
    # what the tests look for; the control is then the reference in bfloat16
    cfg["builder_args"]["dtype"] = "float32"
    cfg["compute_dtype"] = "float32"
    traffic.update(trace_after_steps=2, trace_steps=3)
    cell.limits = TINY_LIMITS[cell.config_name]
    return cell


def devices_for(cell) -> list:
    return jax.devices()[:cell.chips]


# ------------------------------------------------------------------- faults
def _broken_step(model, rewrite_batch=None, keep_state=False):
    """The model's own jitted step with a fault planted in front of it."""
    real = model._make_train_step()

    def step(params, state, opt_state, step_no, x, y, *rest):
        if rewrite_batch is not None:
            x, y = jax.tree.map(rewrite_batch, (x, y))
        if not keep_state:
            return real(params, state, opt_state, step_no, x, y, *rest)
        copies = jax.tree.map(jnp.copy, (params, state, opt_state))
        loss = real(*copies, step_no, x, y, *rest)[3]
        return params, state, opt_state, loss

    step._cache_size = real._cache_size
    return step


def _leading_share_repeated(share: float):
    """Every row replaced by one of the leading ``share`` of the batch: the
    step then sees that share alone, its mean taken over it, at the same
    shapes (so the same compiled program)."""
    def rewrite(a):
        n = max(1, int(a.shape[0] * share))
        return jnp.concatenate([a[:n]] * (a.shape[0] // n), axis=0)
    return rewrite


def plant(fault: str, chips: int):
    """``patch(model)`` for ``fit.run``: the timed path broken underneath."""
    def patch(model):
        if fault == "state_unchanged":
            model._jit_cache["train"] = _broken_step(model, keep_state=True)
        elif fault == "half_batch":
            model._jit_cache["train"] = _broken_step(model, _leading_share_repeated(0.5))
        elif fault == "no_exchange":
            model._jit_cache["train"] = _broken_step(model, _leading_share_repeated(1.0 / chips))
        else:
            raise ValueError(fault)
    return patch
