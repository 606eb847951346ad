"""Each configuration's analytic counts against hand-checked constants, the
traffic generator, and the plain reference's optimizers and precisions
against the program's."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_tiny import ROOT
from benchmarks import reference_train as rt
from benchmarks.configs import bert_base, resnet50
from benchmarks.traffic_gen import make_pool

RESNET = json.loads((ROOT / "benchmarks/configs/resnet50.json").read_text())
BERT = json.loads((ROOT / "benchmarks/configs/bert_base.json").read_text())


def _count(module, cfg):
    params, _ = jax.eval_shape(lambda k: module.make_params(k, cfg), jax.random.key(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


@pytest.mark.parametrize("what,got,want", [
    # He et al. 2015, Table 1: 3.8e9 multiply-adds for the 50-layer net with
    # the stride on a block's first 1x1; summed layer by layer by hand: 3.858e9
    ("resnet50 forward MACs", resnet50.forward_macs_per_sample(RESNET), 3.857973248e9),
    ("resnet50 train FLOPs", resnet50.train_flops_per_sample(RESNET, {}), 6 * 3.857973248e9),
    # 25.56 M parameters, the published count
    ("resnet50 parameters", _count(resnet50, RESNET), 25_557_032),
    # 384 tokens x 12 layers x (4 x 768^2 + 2 x 768 x 3072 + 2 x 384 x 768) MACs,
    # + the head, x 6
    ("bert_base train FLOPs", bert_base.train_flops_per_sample(BERT, {"seq": 384, "batch": 32}),
     6 * (384 * 12 * (4 * 768 ** 2 + 2 * 768 * 3072 + 2 * 384 * 768) + 768 * 2)),
    # bert-base-uncased's 109,482,240 less the token-type table and the pooler,
    # plus the final LayerNorm and the two-class head
    ("bert_base parameters", _count(bert_base, BERT),
     109_482_240 - 2 * 768 - (768 * 768 + 768) + 2 * 768 + (768 * 2 + 2)),
])
def test_analytic_count(what, got, want):
    assert got == pytest.approx(want, rel=1e-9), what


def test_resnet50_table_is_the_papers():
    rows = resnet50.conv_table(RESNET)
    assert len(rows) == 1 + 16 * 3 + 4
    assert rows[0] == ("conv1", 7, 3, 64, 2, 224)
    assert rows[-1] == ("s3b2_convc", 1, 512, 2048, 1, 7)
    assert resnet50.bn_after("s1b0_proj") == "s1b0_projbn"


@pytest.mark.parametrize("cfg,traffic,shape,dtype", [
    (RESNET, {"batch": 4, "pool": 3}, (4, 224, 224, 3), "bfloat16"),
    (BERT, {"batch": 4, "pool": 3, "seq": 16}, (4, 16), "int32"),
], ids=["image", "tokens"])
def test_traffic_comes_from_the_seed_alone(cfg, traffic, shape, dtype):
    big = 2 ** 31 + 12345
    a, b, c = (make_pool(cfg["inputs"], traffic, s) for s in (big, big, big + 1))
    assert len(a) == 3 and a[0][0].shape == shape and str(a[0][0].dtype) == dtype
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert not np.array_equal(xa, xc)
        assert ya.sum() == len(ya) and ya.dtype == np.float32
    rows = np.concatenate([x.reshape(len(x), -1)[:, :64] for x, _ in a]).astype(np.float32)
    assert len(np.unique(rows, axis=0)) == len(rows)          # rows all differ
    assert np.isfinite(rows).all()


@pytest.mark.parametrize("spec", [RESNET["updater"], BERT["updater"]], ids=lambda s: s["kind"])
def test_reference_updater_is_the_programs(spec):
    from deeplearning4j_tpu.nn.multilayer import global_norm_clip
    from deeplearning4j_tpu.optimize.schedules import WarmupCosineSchedule
    from deeplearning4j_tpu.optimize.updaters import AdamW, Nesterovs

    if spec["kind"] == "nesterovs":
        theirs = Nesterovs(lr=spec["lr"], momentum=spec["momentum"])
    else:
        sched = spec["schedule"]
        theirs = AdamW(lr=WarmupCosineSchedule(peak_value=spec["lr"],
                                               warmup_steps=sched["warmup_steps"],
                                               total_steps=sched["total_steps"]))
    k1, k2 = jax.random.split(jax.random.key(3))
    params = {"a": jax.random.normal(k1, (5, 7)), "b": jax.random.normal(k2, (7,))}
    mine_p, mine_o = params, rt.init_opt(spec, params)
    their_p, their_o = params, theirs.init_state(params)
    for step in range(3):
        grads = jax.tree.map(lambda p: 3.0 * jnp.sin(p + step), mine_p)
        clip = spec.get("clip_global_norm", 0.0)
        mine_p, mine_o = rt.apply_updater(spec, rt.clip_global_norm(grads, clip), mine_o,
                                          mine_p, step)
        g = global_norm_clip(grads, clip) if clip else grads
        upd, their_o = theirs.update(g, their_o, their_p, step)
        their_p = jax.tree.map(lambda p, d: p - d, their_p, upd)
        if step == 0:       # the first gradient, read back from the state
            key, factor = rt.first_gradient_from_moment(spec)
            got = [factor * float(n) for n in rt.leaf_norms(mine_o[key])]
            want = [float(n) for n in rt.leaf_norms(rt.clip_global_norm(grads, clip))]
            assert got == pytest.approx(want, rel=1e-5)
    for a, b in zip(jax.tree.leaves(mine_p), jax.tree.leaves(their_p)):
        assert np.allclose(a, b, rtol=1e-6, atol=1e-7)


def test_precisions_round_as_they_say():
    x = jnp.linspace(-3.0, 3.0, 257, dtype=jnp.float32)
    cast, product, qa = rt.precision_policy("float32")
    assert cast(x) is x and qa(x) is x
    cast, product, qa = rt.precision_policy("bfloat16")
    assert cast({"w": x})["w"].dtype == jnp.bfloat16 and qa(x) is x
    cast, product, qa = rt.precision_policy("fp8")
    err = float(jnp.max(jnp.abs(qa(x) - x)) / 3.0)
    assert 0.005 < err < 0.07                        # three mantissa bits
    dot = product(lambda a, b: a * b)
    value, grad = jax.value_and_grad(lambda a: dot(a, x).sum())(x)
    assert float(jnp.abs(value - (x * x).sum()) / (x * x).sum()) < 0.05
    assert float(jnp.max(jnp.abs(grad - x))) > 0      # operands rounded in the backward too
    assert rt.CONTROL_PRECISION == {"float32": "bfloat16", "bfloat16": "fp8"}
    with pytest.raises(ValueError):
        rt.precision_policy("int4")


def test_comparison_measures_gaps_of_norms_and_leaves_dead_leaves_out():
    ref = {"losses": [1.0, 2.0, 3.0], "grad_norms": [1.0, 2.0, 4.0, 0.0, 1e-6],
           "change_norms": [0.1, 0.2, 0.4, 0.3, 0.3], "state_norms": [5.0],
           "leaves": list("abcde")}
    same = rt.compare(ref, ref, {"loss1_gap": 1e-6, "grad_leaf_gap": 1e-6})
    assert same["correct"] and same["left_out_of_change"] == 2
    off = dict(ref, losses=[1.01, 2.0, 3.0], grad_norms=[1.0, 2.0, 5.0, 0.0, 1e-6],
               change_norms=[0.1, 0.2, 0.4, 9.0, 9.0], state_norms=[5.5])
    out = rt.compare(off, ref, {"loss1_gap": 0.02, "grad_leaf_gap": 0.1})
    numbers = {**{k: v["value"] for k, v in out["checks"].items()}, **out["unlimited"]}
    assert numbers["loss1_gap"] == pytest.approx(0.01)
    assert numbers["grad_leaf_gap"] == pytest.approx(0.25) and out["worst_leaves"]["grad"] == "c"
    assert numbers["change_leaf_gap"] == 0.0          # the moved leaves are the dead ones
    assert numbers["state_leaf_gap"] == pytest.approx(0.1)
    assert not out["correct"]
    assert not rt.compare(ref, ref, {})["correct"], "no limit, nothing compared: not correct"
    short = dict(ref, losses=[1.0, 2.0])
    assert not rt.compare(short, ref, {"loss1_gap": 1.0})["correct"]
