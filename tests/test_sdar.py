"""SDAR on the CPU at a tiny size: the block-diffusion mask in the flash kernel
(interpret mode) against the dense mask, forward and every gradient, and its
tile tables against an enumeration of the mask; a leaked mask told apart; the
experts' shares against the uncut layer; and ``zoo.SDAR`` through ``fit``
against the plain reference of ``benchmarks/configs/sdar_30b.py`` (the noise's
draws, the loss, every gradient leaf and the state after two steps)."""

import importlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_train
from benchmarks.configs import sdar_30b as reference
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import DecoderBlock, SparseExpertsLayer
from deeplearning4j_tpu.nn.layers.base import LOSS_TERM
from deeplearning4j_tpu.nn.layers.core import block_diffusion_noise
from deeplearning4j_tpu.ops.attention import block_diffusion_mask, dot_product_attention
from deeplearning4j_tpu.zoo import SDAR

flash = importlib.import_module("deeplearning4j_tpu.ops.pallas.flash_attention")
CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"


def dense(q, k, v, mask):
    """Attention under an explicit ``[T, T]`` mask; k / v heads repeated over
    their groups of query heads."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision="highest") / np.sqrt(q.shape[-1])
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(jnp.where(mask, s, -1e30), -1), v,
                      precision="highest")


def leaked(seq, block):
    """The mask with a noisy block seeing the clean block of its own index too:
    the fault that hands the model the tokens it is to predict."""
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    half = seq // 2
    own = (i < half) & (j >= half) & ((j - half) // block == i // block)
    return np.asarray(block_diffusion_mask(seq, block)) | own


def qkv(seed, heads, kv_heads, seq, dim=16, rows=1):
    keys = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(key, (rows, h, seq, dim)) for key, h in
                 zip(keys, (heads, kv_heads, kv_heads)))


# ------------------------------------------------------------------- the mask
def test_the_plain_mask_is_the_references_and_counts_its_pairs():
    for seq, block in ((24, 4), (64, 8), (40, 4)):
        mine = np.asarray(block_diffusion_mask(2 * seq, block))
        i, j = np.arange(2 * seq)[:, None], np.arange(2 * seq)[None, :]
        np.testing.assert_array_equal(mine, np.asarray(reference.visible(i, j, seq, block)))
        assert mine.sum() == reference.visible_pairs(seq, block)
        assert not mine[seq:, :seq].any()                       # no clean query sees a noisy key
        assert mine.diagonal().all()


@pytest.mark.parametrize("seq,bq,bk,block", [
    (96, 32, 32, 4), (96, 32, 16, 8), (200, 64, 64, 4), (40, 16, 32, 4), (256, 64, 128, 8),
    (16384, 512, 512, 4)])
def test_the_kernels_visit_exactly_the_tiles_that_hold_a_visible_pair(seq, bq, bk, block):
    seen = flash.diffusion_tiles(seq, bq, bk, block)
    if seq <= 256:
        nq, nk = seen.shape
        padded = np.zeros((nq * bq, nk * bk), bool)
        padded[:seq, :seq] = np.asarray(block_diffusion_mask(seq, block))
        np.testing.assert_array_equal(seen, padded.reshape(nq, bq, nk, bk).any(axis=(1, 3)))
    else:           # the cell's: 288 of 1,024, against a causal mask's 528
        assert seen.sum() == 288 == reference.tiles_visited(seq // 2, block) and seen.size == 1024
    for q_outer in (True, False):       # the forward and dq; dk / dv
        qs, ks, flags = flash.diffusion_pairs(seq, bq, bk, block, q_outer)
        assert sorted(zip(qs, ks)) == sorted(zip(*np.nonzero(seen)))
        outer, inner = (qs, ks) if q_outer else (ks, qs)
        assert (np.diff(outer) >= 0).all()                     # an outer block's steps together
        for b in np.unique(outer):
            at = np.flatnonzero(outer == b)
            assert [bool(flags[s] & flash.OPENS) for s in at] == [True] + [False] * (len(at) - 1)
            assert [bool(flags[s] & flash.CLOSES) for s in at] == [False] * (len(at) - 1) + [True]
        for b in np.unique(inner):
            at = np.flatnonzero(inner == b)
            assert bool(flags[at[0]] & flash.FIRST_SIGHT) and not any(
                flags[s] & flash.FIRST_SIGHT for s in at[1:])


# ------------------------------------------------------------------- the kernel
@pytest.mark.parametrize("seq,heads,kv_heads,block,bq,bk", [
    (96, 4, 2, 4, 32, 32),          # grouped heads, blocks of 4
    (96, 2, 1, 8, 32, 16),          # blocks of 8, one key-value head for all
    (80, 2, 2, 4, 32, 32),          # 80 positions: no tile divides them
])
def test_the_kernel_matches_the_dense_mask_forward_and_backward(seq, heads, kv_heads, block, bq, bk):
    q, k, v = qkv(seq, heads, kv_heads, seq)
    mask = block_diffusion_mask(seq, block)
    got, got_grads = jax.value_and_grad(lambda *a: (flash.flash_attention(
        *a, block_diffusion=block, block_q=bq, block_k=bk) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    want, want_grads = jax.value_and_grad(lambda *a: (dense(*a, mask) ** 2).sum(),
                                          argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5)
    # the plain lowering the registry falls back on has the same semantics
    plain, plain_grads = jax.value_and_grad(lambda *a: (dot_product_attention(
        *a, block_diffusion=block) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(plain, want, rtol=2e-6)
    for g, w in zip(plain_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_the_fused_backward_equals_the_two_calls_bit_for_bit():
    q, k, v = qkv(3, 4, 2, 96)
    out, lse = flash._flash_forward(q, k, v, causal=False, scale=0.25, block_q=32, block_k=32,
                                    interpret=True, diffusion=4)
    do = jax.random.normal(jax.random.key(4), out.shape)
    delta = (do * out).sum(-1, keepdims=True)
    fused, two = (flash._flash_backward_at(flash.BwdTiles(32, 32, layout), q, k, v, do, lse, delta,
                                           causal=False, scale=0.25, interpret=True, diffusion=4)
                  for layout in (True, False))
    for a, b in zip(fused, two):
        assert bool((a == b).all())


def test_a_leaked_mask_moves_the_gradient_past_the_tolerance():
    """A noisy block that also sees its own clean block: the mistake that makes
    the objective trivial. Its gradient stands far outside the kernel test's
    tolerance of the right one."""
    q, k, v = qkv(5, 2, 1, 96)
    right = jax.grad(lambda q: (flash.flash_attention(q, k, v, block_diffusion=4, block_q=32,
                                                      block_k=32) ** 2).sum())(q)
    wrong = jax.grad(lambda q: (dense(q, k, v, leaked(96, 4)) ** 2).sum())(q)
    assert float(jnp.abs(right - wrong).max()) > 100 * 5e-5


def test_the_registry_takes_the_mask_where_the_kernel_can():
    q, k, v = qkv(0, 4, 2, 2048, dim=64)
    assert flash._flash_requires(q, k, v, block_diffusion=4)
    assert flash._flash_applicable(q, k, v, block_diffusion=4)
    assert not flash._flash_requires(q, k, v, block_diffusion=4, causal=True)
    assert not flash._flash_requires(q, k, v, block_diffusion=3)           # not a power of two
    assert not flash._flash_requires(q, k, v, block_diffusion=4, mask=jnp.ones((1, 2048)))
    assert not flash._flash_requires(q[:, :, :1000], k[:, :, :1000], v[:, :, :1000],
                                     block_diffusion=8)                    # 500 is no whole blocks
    with pytest.raises(ValueError, match="block_diffusion"):
        flash.flash_attention(q, k, v, block_diffusion=4, causal=True)


# ------------------------------------------------------------------- the experts' shares
def test_the_eight_shares_and_what_every_chip_computes_alike_add_up_to_the_uncut_layer():
    """16 experts in 8 shares of 2, as the cell's 128 in 8 of 16: every share
    routes over all 16 and computes its own experts' part; the attention and the
    router are whole on every chip, and the shares' parts add up to the uncut
    reference layer."""
    total, share, top, f, d, rows, seq = 16, 2, 4, 24, 32, 2, 48
    whole = SparseExpertsLayer(n_experts=total, top_k=top, d_expert=f, n_in=d)
    params, _ = whole.init(jax.random.key(0), InputType.recurrent(d, seq))
    keys = iter(jax.random.split(jax.random.key(1), 8))
    params = {name: 0.2 * jax.random.normal(next(keys), p.shape) for name, p in params.items()}
    x = jax.random.normal(jax.random.key(2), (rows * seq, d))
    cfg = {"experts_held_first": 0, "num_experts": total, "router_experts": total,
           "num_experts_per_tok": top}
    uncut, aux, _ = reference.experts(x, params, cfg, lambda g: g)
    parts, terms = [], []
    for first in range(0, total, share):
        layer = SparseExpertsLayer(n_experts=total, top_k=top, d_expert=f, n_in=d,
                                   experts_held=(first, share))
        mine = {**params, **{k: params[k][first:first + share] for k in ("Wg", "Wu", "Wd")}}
        _, state = layer.init(jax.random.key(0), InputType.recurrent(d, seq))
        y, new = layer.apply(mine, state, x.reshape(rows, seq, d))
        parts.append(y.reshape(rows * seq, d))
        terms.append(float(new[LOSS_TERM]))
    np.testing.assert_allclose(sum(parts), uncut, atol=3e-5)
    assert terms == pytest.approx([terms[0]] * 8, rel=1e-6)
    assert terms[0] == pytest.approx(whole.aux_coef * seq * float(aux), rel=1e-5)


# -------------------------------------------------------------------- the model
def tiny_config() -> dict:
    """``sdar_30b.json`` with its tiny sibling merged over it, in float32."""
    def merge(into, over):
        for key, value in over.items():
            if isinstance(value, dict) and isinstance(into.get(key), dict):
                merge(into[key], value)
            else:
                into[key] = value
        return into

    cfg = merge(json.loads((CONFIGS / "sdar_30b.json").read_text()),
                json.loads((CONFIGS / "sdar_30b.tiny.json").read_text())["config"])
    cfg["builder_args"]["dtype"], cfg["compute_dtype"] = "float32", "float32"
    return cfg


def batch(cfg, rows=2, seq=48, seed=0):
    rng = np.random.default_rng(seed)
    vocab = cfg["inputs"]["features"]["vocab"]
    return tuple(jnp.asarray(rng.integers(0, vocab, (rows, seq)), jnp.int32) for _ in range(2))


def test_the_noise_is_the_references_draw_and_fresh_every_step():
    cfg = tiny_config()
    spec, args = cfg["noise"], cfg["builder_args"]
    seed = jnp.int32(2 ** 31 - 77)
    for step in (0, 1, 7):
        masked, weights = block_diffusion_noise(
            jax.random.fold_in(jax.random.key(seed), step), 3, 48, args["block"], args["t_min"])
        want_masked, want_weights = reference.noise(cfg, seed, jnp.int32(step), 3, 48)
        np.testing.assert_array_equal(np.asarray(masked), np.asarray(want_masked))
        np.testing.assert_array_equal(np.asarray(weights), np.asarray(want_weights))
        rates = 1.0 / np.asarray(weights)[np.asarray(masked)]
        assert (rates >= spec["t_min"]).all() and (rates <= 1.0).all()
    first, _ = reference.noise(cfg, seed, jnp.int32(0), 3, 48)
    second, _ = reference.noise(cfg, seed, jnp.int32(1), 3, 48)
    assert (np.asarray(first) != np.asarray(second)).any()


def test_two_seeds_draw_two_noises_on_both_sides():
    """The noise's seed is a leaf of the state, drawn from the run's key by the
    reference and from the model's seed by the program: two seeds, two masks."""
    cfg = tiny_config()
    x, _ = batch(cfg)
    masks = []
    for seed in (5, 6):
        model = SDAR(**cfg["builder_args"], seed=seed).init()
        _, new = model.layers[0].apply(model.params[0], model.state[0], x, train=True)
        masks.append(np.asarray(new["loss_weights"]) > 0)
        assert int(new["noise_seed"]) == int(model.state[0]["noise_seed"])
    assert (masks[0] != masks[1]).any()
    make = jax.jit(lambda k: reference.make_params(k, cfg)[1][0]["noise_seed"])
    seeds = [int(make(jax.random.key(k))) for k in (5, 6)]
    assert seeds[0] != seeds[1]
    draws = [np.asarray(reference.noise(cfg, jnp.int32(s), jnp.int32(0), *x.shape)[0])
             for s in seeds]
    assert (draws[0] != draws[1]).any()


def test_the_held_experts_are_placed_for_the_mask_token():
    """Each router's columns are the drawn ones renumbered so that the experts
    held here are the masked positions' first ``top_k x held / experts`` and
    their last: on a batch of its own, every masked position chooses exactly
    that many held experts in every layer, whatever the seed (with the
    columns as drawn, 0 to ``top_k``)."""
    cfg = tiny_config()
    first, held, total, top = (cfg["experts_held_first"], cfg["num_experts"],
                               cfg["router_experts"], cfg["num_experts_per_tok"])
    assert top * held // total == 1
    angles = reference.rotary(48, cfg["head_dim"], cfg["rope_theta"])

    def as_drawn(key):
        place = reference.place_experts
        reference.place_experts = lambda params, key, cfg: params
        try:
            return reference.make_params(key, cfg)
        finally:
            reference.place_experts = place

    placed_fn, drawn_fn = (jax.jit(lambda k: reference.make_params(k, cfg)), jax.jit(as_drawn))
    routed = jax.jit(lambda x, p: (
        reference._rms_norm(reference.attend(x, p, cfg, angles), p["n3_g"], cfg["rms_norm_eps"])[0]
        @ p["mlp"]["Wr"], reference.block(x, p, cfg, angles)[0]))
    counts = []
    for seed in (1, 2 ** 31 + 9):
        key = jax.random.key(seed)
        params, state = placed_fn(key)
        drawn, _ = drawn_fn(key)
        for mine, theirs in zip(params[1:-2], drawn[1:-2]):
            assert sorted(map(tuple, np.asarray(mine["mlp"]["Wr"]).T)) == sorted(
                map(tuple, np.asarray(theirs["mlp"]["Wr"]).T))
            assert not np.array_equal(np.asarray(mine["mlp"]["Wr"]), np.asarray(theirs["mlp"]["Wr"]))
        x0, _ = batch(cfg, rows=1, seed=seed % 1000)
        masked, _ = reference.noise(cfg, state[0]["noise_seed"], jnp.int32(0), 1, 48)
        x = params[0]["W"][jnp.concatenate([jnp.where(masked, cfg["noise"]["mask_id"], x0), x0],
                                           axis=1)]
        for p in params[1:-2]:
            logits, x = routed(x, p)
            chosen = np.asarray(jax.lax.top_k(logits, top)[1])[:48][np.asarray(masked[0])]
            counts.append(((chosen >= first) & (chosen < first + held)).sum(-1))
    assert (np.concatenate(counts) == 1).all()


def test_the_noised_half_embeds_the_mask_row_where_the_step_masked_and_the_clean_half_x0():
    cfg = tiny_config()
    mask_id = cfg["noise"]["mask_id"]
    model = SDAR(**cfg["builder_args"]).init()
    layer, params, state = model.layers[0], model.params[0], model.state[0]
    assert mask_id == layer.mask_id == params["W"].shape[0] - 1
    x, _ = batch(cfg)
    y, new = layer.apply(params, state, x, train=True)
    masked, weights = reference.noise(cfg, state["noise_seed"], state["noise_step"], *x.shape)
    table = np.asarray(params["W"])
    want = np.concatenate([table[np.where(np.asarray(masked), mask_id, np.asarray(x))],
                           table[np.asarray(x)]], axis=1)
    np.testing.assert_array_equal(np.asarray(y), want)
    np.testing.assert_array_equal(np.asarray(new["loss_weights"]), np.asarray(weights))
    assert int(new["noise_step"]) == int(state["noise_step"]) + 1


def test_the_blocks_positions_repeat_in_the_two_halves():
    block = DecoderBlock(d_model=32, n_heads=2, head_dim=16, block_diffusion=4)
    plain = DecoderBlock(d_model=32, n_heads=2, head_dim=16)
    for mine, half in zip(block.rope_tables(48), plain.rope_tables(24)):
        np.testing.assert_array_equal(np.asarray(mine), np.concatenate([half, half]))
    with pytest.raises(ValueError, match="block-diffusion"):
        DecoderBlock(d_model=32, block_diffusion=4, window=8)


def test_zoo_sdar_through_fit_is_the_reference_loss_gradients_and_state():
    """Seeded random weights from the reference's ``make_params``, two steps
    through ``fit`` against two of the reference's own: each step's loss, every
    leaf of the first (clipped) gradient as the optimizer's first moment holds
    it, and the state and the parameters after the two, the noise's counter one
    on a step and the step's weights gone from the state."""
    cfg = tiny_config()
    spec = cfg["updater"]
    model = SDAR(**cfg["builder_args"]).init()
    params, state = reference.make_params(jax.random.key(3), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(model.params)
    assert jax.tree.structure(state) == jax.tree.structure(model.state)
    x, y = batch(cfg)

    @jax.jit
    def plain_step(p, s, opt, step):
        (loss, s), g = jax.value_and_grad(lambda p: reference.loss_fn(p, s, x, y, cfg),
                                          has_aux=True)(p)
        g = reference_train.clip_global_norm(g, spec["clip_global_norm"])
        p, opt = reference_train.apply_updater(spec, g, opt, p, step)
        return p, s, opt, loss, g

    # the step donates what it is given: the program gets copies
    model.params, model.state = jax.tree.map(jnp.copy, (params, state))
    p, s, opt = params, state, reference_train.init_opt(spec, params)
    for step in range(2):
        model.fit(x, y)
        p, s, opt, loss, g = plain_step(p, s, opt, jnp.int32(step))
        assert model.score_value == pytest.approx(float(loss), rel=1e-5)
        assert int(model.state[0]["noise_step"]) == step + 1
        assert set(model.state[0]) == {"noise_seed", "noise_step"}
        assert int(model.state[0]["noise_seed"]) == int(state[0]["noise_seed"])
        if step == 0:
            moment = [st["m"] if st else st for st in model.opt_state]
            for (path, m), w in zip(jax.tree_util.tree_leaves_with_path(moment), jax.tree.leaves(g)):
                got = m / (1.0 - spec["beta1"])
                assert float(jnp.abs(got - w).max()) <= 2e-4 * float(jnp.abs(w).max()), \
                    jax.tree_util.keystr(path)
    for a, b in zip(jax.tree.leaves(model.state), jax.tree.leaves(s)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-5)
    for (path, got), want, start in zip(jax.tree_util.tree_leaves_with_path(model.params),
                                        jax.tree.leaves(p), jax.tree.leaves(params)):
        moved = float(jnp.abs(want - start).max())
        assert moved > 0 and float(jnp.abs(got - want).max()) <= 1e-2 * moved, \
            jax.tree_util.keystr(path)


def test_the_scopes_a_trace_reader_tells_the_objective_and_the_kernel_by(monkeypatch):
    """The noising layer's and the loss's scopes, and the kernel's (interpreted,
    at 96 positions: the registry's length threshold lowered) under
    ``block_diffusion``, forward and backward."""
    from deeplearning4j_tpu.ops.registry import get_op

    kernel = next(i for i in get_op("dot_product_attention").impls if i.platform == "pallas")
    monkeypatch.setattr(kernel, "predicate", lambda *a, **k: True)
    cfg = tiny_config()
    model = SDAR(**{**cfg["builder_args"], "n_layers": 1}).init()
    x, y = batch(cfg)
    compiled = model._make_train_step().lower(
        model.params, model.state, model.opt_state, jnp.asarray(0, jnp.int32), x, y,
        jax.random.key(0), None, None).compile()
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    inside = lambda *parts: [n for n in names if all(p in n for p in parts)]     # noqa: E731
    assert inside("0.BlockDiffusionNoiseLayer", "/block_diffusion_noise/")
    assert inside("jvp(loss)/block_diffusion_loss/") and inside("transpose(jvp(loss))/block_diffusion_loss/")
    assert inside("transpose(jvp(0.BlockDiffusionNoiseLayer))", "/block_diffusion_noise/scatter-add")
    assert inside("jvp(1.DecoderBlock)", "/flash_attention/block_diffusion/")
    assert inside("transpose(jvp(1.DecoderBlock))", "/flash_attention/block_diffusion/")
