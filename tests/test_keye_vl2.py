"""Keye-VL-2.0's language model on the CPU at a tiny size: the indexer's exact
top-k choice (its tie rule, dense below ``topk``), the sparse-attention mixer
against the plain reference of ``benchmarks/configs/keye_vl2_30b.py`` (output,
the indexer's loss and every leaf's gradient), the indexer's loss reaching the
indexer's leaves alone, M-RoPE, the Pallas kernels in interpret mode against
the plain lowerings, the experts' shares against the uncut layer, the choice
kept under ``remat``, the zoo model through ``fit`` and its gauges."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.configs import keye_vl2_30b as reference
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import DecoderBlock, SparseAttentionLayer, SparseExpertsLayer
from deeplearning4j_tpu.nn.layers.attention import rotary_tables
from deeplearning4j_tpu.nn.layers.base import LOSS_TERM, SELECTED, layer_loss_terms
from deeplearning4j_tpu.nn.layers.mixers import INDEXER_LOSS, SPARSE_TILES
from deeplearning4j_tpu.ops import sparse_attention as plain
from deeplearning4j_tpu.ops.pallas import sparse_attention as kernels
from deeplearning4j_tpu.zoo import KeyeVL2

D, H, KV, DH, HI, DI, R, TOPK, T, B = 32, 4, 2, 16, 4, 16, 8, 24, 80, 2
SECTIONS = (2, 3, 3)
#: the reference's configuration at the tests' size
CFG = {"hidden_size": D, "num_attention_heads": H, "num_key_value_heads": KV, "head_dim": DH,
       "rms_norm_eps": 1e-6, "rope_theta": 1e7, "rope_scaling": {"mrope_section": list(SECTIONS)},
       "sa_config": {"indexer_num_heads": HI, "indexer_head_dim": DI, "topk": TOPK},
       "indexer_rope_dim": R}
MIXER = SparseAttentionLayer(d_model=D, n_heads=H, n_kv_heads=KV, head_dim=DH,
                             mrope_sections=SECTIONS, index_heads=HI, index_head_dim=DI,
                             index_rope_dim=R, topk=TOPK)


def mm(a, w):
    return jnp.dot(a, w, precision="highest")


def seeded(layer, seed=0, scale=0.3):
    """The layer's parameters with every matrix redrawn wide enough to matter."""
    params, state = layer.init(jax.random.key(seed), InputType.recurrent(D, T))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))

    def redraw(path, p):
        if p.ndim >= 2:
            return scale * jax.random.normal(next(keys), p.shape)
        name = jax.tree_util.keystr(path)
        if "k_norm" in name:            # a LayerNorm gain and bias off their start
            return p + 0.2 * jax.random.normal(next(keys), p.shape)
        return p
    return jax.tree_util.tree_map_with_path(redraw, params), state


def inputs(seed=5):
    return jax.random.normal(jax.random.key(seed), (B, T, D))


def reference_ropes(seq=T):
    text = jnp.broadcast_to(jnp.arange(seq), (len(SECTIONS), seq))
    return (reference.mrope_angles(text, DH, 1e7, SECTIONS),
            reference.mrope_angles(text[:1], R, 1e7, [R // 2]))


# ------------------------------------------------------------------ the choice
def test_the_choice_is_the_references_top_k_with_the_lower_position_winning_ties():
    rng = np.random.default_rng(0)
    # a handful of values: every row is full of ties at its threshold
    scores = jnp.asarray(rng.integers(-3, 4, (2, 96, 96)), jnp.float32)
    t = jnp.arange(96)
    scores = jnp.where(t[None, :] <= t[:, None], scores, -jnp.inf)
    mine = plain.select_topk(scores, TOPK)
    for b in range(2):
        want = reference._keep_topk(scores[b], TOPK, 0)
        np.testing.assert_array_equal(np.asarray(mine[b] > 0), np.asarray(want))
    counts = np.asarray(mine.sum(-1))
    np.testing.assert_array_equal(counts[:, :TOPK], np.broadcast_to(np.arange(1, TOPK + 1), (2, TOPK)))
    assert (counts[:, TOPK:] == TOPK).all()          # exactly topk once a row reaches further
    # the tie rule by hand on one row: all above the threshold, then the lowest ties
    row, s = np.asarray(scores[0, 90]), np.asarray(mine[0, 90])
    thr = np.sort(row[np.isfinite(row)])[::-1][TOPK - 1]
    above, ties = np.flatnonzero(row > thr), np.flatnonzero(row == thr)
    assert set(np.flatnonzero(s)) == set(above) | set(ties[:TOPK - len(above)])


def test_the_choice_is_dense_below_topk_and_causal():
    q_i = jax.random.normal(jax.random.key(1), (1, HI, 48, DI))
    k_i = jax.random.normal(jax.random.key(2), (1, 48, DI))
    w = jax.random.normal(jax.random.key(3), (1, 48, HI))
    chosen = plain.indexer_topk(q_i, k_i, w, topk=64)
    np.testing.assert_array_equal(np.asarray(chosen[0]), np.tril(np.ones((48, 48), np.int8)))
    chosen = plain.indexer_topk(q_i, k_i, w, topk=8)
    assert not np.triu(np.asarray(chosen[0]), 1).any()
    assert (np.asarray(chosen[0]).sum(-1) == np.minimum(np.arange(1, 49), 8)).all()


def test_the_tiles_share_counts_causal_tiles_that_hold_a_chosen_pair():
    sel = jnp.tril(jnp.ones((1, 1024, 1024), jnp.int8))
    assert float(plain.tiles_visited_share(sel)) == 1.0
    # the tile of queries 512-1023 and keys 0-511 empty: two causal tiles of three
    sel = sel.at[:, 512:, :512].set(0)
    assert float(plain.tiles_visited_share(sel)) == pytest.approx(2 / 3)
    np.testing.assert_array_equal(np.asarray(plain.visited_tiles(sel)[0]), [[1, 0], [0, 1]])


# ------------------------------------------------------------------ positions
def test_mrope_three_rows_match_the_reference_and_equal_rows_are_the_plain_tables():
    rows = jnp.stack([jnp.arange(T), 3 * jnp.arange(T) % 7, T - jnp.arange(T)])
    cos, sin = rotary_tables(rows, DH, 1e7, sections=SECTIONS)
    angles = reference.mrope_angles(rows, DH, 1e7, SECTIONS)
    np.testing.assert_allclose(cos, jnp.cos(jnp.concatenate([angles, angles], -1)), atol=1e-6)
    np.testing.assert_allclose(sin, jnp.sin(jnp.concatenate([angles, angles], -1)), atol=1e-6)
    same = jnp.broadcast_to(jnp.arange(T), (3, T))
    for mine, theirs in zip(rotary_tables(same, DH, 1e7, sections=SECTIONS), rotary_tables(T, DH, 1e7)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    with pytest.raises(ValueError, match="sections"):
        rotary_tables(rows, DH, 1e7, sections=(2, 2, 2))


# ------------------------------------------------------------------ the mixer
#: an image's grid as M-RoPE numbers it: one temporal step, 8 x 10 patches
GRID = jnp.stack([jnp.zeros(T, jnp.int32), jnp.arange(T) // 10, jnp.arange(T) % 10])


def _program(params, x, state, positions=None):
    y, new = MIXER.apply(params, state, x, train=True, positions=positions)
    return y, new[INDEXER_LOSS], new[SPARSE_TILES]


def _reference(params, x, positions=None):
    if positions is None:
        rope, index_rope = reference_ropes()
    else:
        rope = reference.mrope_angles(positions, DH, 1e7, SECTIONS)
        index_rope = reference.mrope_angles(positions[:1], R, 1e7, [R // 2])
    y, kl, share = jax.vmap(lambda u: reference.mixer(u, params, CFG, rope, index_rope))(x)
    return y, kl.sum() / B, share.mean()


@pytest.mark.parametrize("positions", [None, GRID], ids=["text", "three_rows"])
def test_the_mixer_matches_the_reference_forward_and_gradient(positions):
    params, state = seeded(MIXER)
    x = inputs()
    y, loss, share = _program(params, x, state, positions)
    y_ref, loss_ref, share_ref = _reference(params, x, positions)
    np.testing.assert_allclose(y, y_ref, atol=2e-5 * float(jnp.abs(y_ref).max()))
    assert float(loss) == pytest.approx(float(loss_ref), rel=2e-5) and float(loss) > 0
    assert float(share) == float(share_ref) == 1.0
    weights = jax.random.normal(jax.random.key(9), x.shape)

    def score(fn):
        def f(p, x):
            y, loss, _ = fn(p, x)
            return (y * weights).sum() + loss
        return f

    got = jax.grad(score(lambda p, x: _program(p, x, state, positions)), argnums=(0, 1))(params, x)
    want = jax.grad(score(lambda p, x: _reference(p, x, positions)), argnums=(0, 1))(params, x)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=5e-5 * float(jnp.abs(w).max()) + 1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_indexers_loss_reaches_the_indexers_leaves_alone():
    params, state = seeded(MIXER)
    x = inputs()
    from_loss = jax.grad(lambda p: _program(p, x, state)[1])(params)
    from_output = jax.grad(lambda p: (_program(p, x, state)[0] ** 2).sum())(params)
    for path, g in jax.tree_util.tree_flatten_with_path(from_loss)[0]:
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            assert float(jnp.abs(g).max()) > 0, name
        else:
            assert float(jnp.abs(g).max()) == 0.0, name
    for path, g in jax.tree_util.tree_flatten_with_path(from_output)[0]:
        name = jax.tree_util.keystr(path)
        assert (float(jnp.abs(g).max()) == 0.0) == ("indexer" in name), name
    # nor does the loss reach the block's input: the indexer reads it detached
    d_x = jax.grad(lambda x: _program(params, x, state)[1])(x)
    assert float(jnp.abs(d_x).max()) == 0.0


def test_the_mixers_parameters_and_the_published_count():
    params, state = MIXER.init(jax.random.key(0), InputType.recurrent(D, T))
    assert set(state) == {INDEXER_LOSS, SPARSE_TILES} and INDEXER_LOSS.startswith(LOSS_TERM + "_")
    assert {k: v.shape for k, v in params["indexer"].items()} == {
        "Wq": (D, HI * DI), "Wk": (D, DI), "Ww": (D, HI), "k_norm_g": (DI,), "k_norm_b": (DI,)}
    full = SparseAttentionLayer(d_model=2048)
    shapes = jax.eval_shape(lambda: full.init(jax.random.key(0), InputType.recurrent(2048, 8))[0])
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))    # noqa: E731
    assert count(shapes["indexer"]) == 2_261_120
    assert count(shapes) == 18_874_368 + 2_261_120 + 2 * 128
    with pytest.raises(ValueError, match="no padding mask"):
        MIXER.apply(params, state, inputs(), mask=jnp.ones((B, T)))


def test_a_blocks_terms_are_both_handed_to_the_score():
    block = DecoderBlock(d_model=D, norm="pre", mixer=MIXER,
                         mlp=SparseExpertsLayer(n_experts=8, top_k=2, d_expert=24))
    params, state = block.init(jax.random.key(0), InputType.recurrent(D, T))
    _, new = block.apply(params, state, inputs(), train=True)
    terms = layer_loss_terms([new])
    assert len(terms) == 2 and {float(t) for t in terms} == {
        float(new[LOSS_TERM]), float(new[INDEXER_LOSS])}


def test_the_choice_is_kept_under_remat_by_its_name(capsys):
    """Under the block's checkpoint the choice is a residual the backward reads,
    by its name: the recomputed forward and the backward see the forward's
    choice, and the indexer's scores are not made again for them."""
    block = DecoderBlock(d_model=D, norm="pre", mixer=MIXER)
    params, state = block.init(jax.random.key(0), InputType.recurrent(D, T))
    x = inputs()

    def loss(params):
        y, new = block.apply(params, state, x, train=True, remat=True)
        return (y ** 2).sum() + new[INDEXER_LOSS]

    jax.ad_checkpoint.print_saved_residuals(loss, params)
    saved = capsys.readouterr().out.splitlines()
    kept = [line for line in saved if SELECTED in line]
    assert len(kept) == 1 and kept[0].startswith(f"i8[{B},{T},{T}]"), saved
    # without the name in the policy nothing of T x T would be kept
    assert not [line for line in saved if f"[{B},{T},{T}]" in line and SELECTED not in line]


def test_with_the_kernels_remat_keeps_what_their_backwards_read(capsys, monkeypatch, kernel_calls):
    """The chip's path (the kernels forced onto the CPU, interpreted): the
    block's checkpoint keeps the choice, the attention's output and
    log-sum-exp and the indexer loss's gradients by name, so that no forward
    kernel runs again for the backward, and nothing T x T but the choice; the
    loss's two calls run once, and no call of it is left for the backward."""
    from deeplearning4j_tpu.common.env import env

    monkeypatch.setattr(env, "force_pallas", True)
    seq = 1024
    mixer = SparseAttentionLayer(d_model=D, n_heads=H, n_kv_heads=KV, head_dim=64,
                                 mrope_sections=(8, 12, 12), index_heads=2, index_head_dim=64,
                                 index_rope_dim=R, topk=300)
    block = DecoderBlock(d_model=D, norm="pre", mixer=mixer)
    params, state = block.init(jax.random.key(0), InputType.recurrent(D, seq))
    x = jax.random.normal(jax.random.key(5), (1, seq, D))

    def loss(params):
        y, new = block.apply(params, state, x, train=True, remat=True)
        return (y ** 2).sum() + new[INDEXER_LOSS]

    jax.ad_checkpoint.print_saved_residuals(loss, params)
    saved = capsys.readouterr().out.splitlines()
    named = {line.split("named '")[1].split("'")[0] for line in saved if "named '" in line}
    assert {SELECTED, kernels.SAVED_INDEX_GRADS} <= named
    assert "indexer_loss_lse" not in named
    # dq_i, dk_i and dw, float32
    grads = sorted(line.split(" ")[0] for line in saved if kernels.SAVED_INDEX_GRADS in line)
    assert grads == sorted([f"f32[1,2,{seq},64]", f"f32[1,{seq},64]", f"f32[1,2,{seq}]"]), saved
    # the attention's ``out`` and ``lse``, kept behind the ``reduce_precision``
    # JAX puts on a kept value
    kept = [line.split(" ")[0] for line in saved if line.endswith("(sparse_attention)")]
    assert sorted(kept) == [f"f32[1,{H},{seq},64]", f"f32[1,{H},{seq}]"]
    assert [line for line in saved if f",{seq},{seq}]" in line] == [
        line for line in saved if SELECTED in line]
    calls = kernel_calls(jax.grad(loss), params)
    assert {name: n for name, n in calls.items() if name.startswith("indexer_loss")} == {
        "indexer_loss_lse": 1, "indexer_loss_kl": 1}, calls


# ------------------------------------------------------------ the kernels, interpreted
def _integer_indexer(seq=1024, seed=0):
    """Integer-valued operands: every product and sum is exact, so the kernel
    and the plain lowering score every pair alike, ties and all."""
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(-2, 3, (1, 4, seq, 64)), jnp.bfloat16),
            jnp.asarray(rng.integers(-2, 3, (1, seq, 64)), jnp.bfloat16),
            jnp.asarray(rng.integers(-2, 3, (1, seq, 4)), jnp.float32))


def test_the_choice_kernel_interpreted_is_the_plain_lowerings_choice():
    q_i, k_i, w = _integer_indexer()
    want = plain.indexer_topk(q_i, k_i, w, topk=300)
    got = kernels.indexer_topk(q_i, k_i, w, topk=300)
    assert got.dtype == jnp.int8 and got.shape == (1, 1024, 1024)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(want[0, 700].sum()) == 300 and int(want[0, 200].sum()) == 201


def test_the_attention_kernels_interpreted_match_the_plain_lowering_and_skip_empty_tiles():
    seq = 1024
    keys = jax.random.split(jax.random.key(4), 5)
    q = jax.random.normal(keys[0], (1, 4, seq, 128))
    k = jax.random.normal(keys[1], (1, 2, seq, 128))
    v = jax.random.normal(keys[2], (1, 2, seq, 128))
    t = jnp.arange(seq)
    sel = (jax.random.uniform(keys[3], (1, seq, seq)) < 0.2) | (t[None, :] == t[:, None])
    sel = (sel & (t[None, :] <= t[:, None])).at[:, 512:, :512].set(False).astype(jnp.int8)
    assert int(plain.visited_tiles(sel).sum()) == 2          # one causal tile of three is skipped
    weights = jax.random.normal(keys[4], (1, 4, seq, 128))

    def run(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v, sel)
            return (o * weights).sum(), (o, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, (o, lse)), grads = run(kernels.sparse_attention)
    (_, (o_ref, lse_ref)), grads_ref = run(plain.sparse_attention)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
    for g, w in zip(grads, grads_ref):
        np.testing.assert_allclose(g, w, atol=5e-5 * float(jnp.abs(w).max()))


def _loss_operands(seq=1024):
    """Operands of the indexer's loss, the choice with one causal tile empty,
    and the log-sum-exp of the attention over the choice (so that a query's
    weights sum to one over its chosen keys, as the loss's gradient has it)."""
    keys = jax.random.split(jax.random.key(6), 7)
    q = jax.random.normal(keys[0], (1, 4, seq, 64))
    k = jax.random.normal(keys[1], (1, 2, seq, 64))
    t = jnp.arange(seq)
    sel = (jax.random.uniform(keys[2], (1, seq, seq)) < 0.2) | (t[None, :] == t[:, None])
    sel = (sel & (t[None, :] <= t[:, None])).at[:, 512:, :512].set(False).astype(jnp.int8)
    _, lse = plain.sparse_attention(q, k, jax.random.normal(keys[3], k.shape), sel)
    q_i = jax.random.normal(keys[4], (1, 2, seq, 64))
    k_i = jax.random.normal(keys[5], (1, seq, 64))
    w = jax.random.normal(keys[6], (1, seq, 2))
    return q, k, lse, sel, q_i, k_i, w


def test_the_loss_kernels_interpreted_match_the_plain_lowering_and_skip_empty_tiles(kernel_calls):
    q, k, lse, sel, q_i, k_i, w = operands = _loss_operands()
    assert int(plain.visited_tiles(sel).sum()) == 2          # one causal tile of three is skipped
    want, want_grads = jax.value_and_grad(plain.indexer_loss, argnums=(4, 5, 6))(*operands)
    got, got_grads = jax.value_and_grad(kernels.indexer_loss, argnums=(4, 5, 6))(*operands)
    assert float(got) == pytest.approx(float(want), rel=2e-5) and float(want) > 0
    for g, ref in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, ref, atol=5e-5 * float(jnp.abs(ref).max()))
    # the value alone: no gradient is made
    assert float(kernels.indexer_loss(*operands)) == pytest.approx(float(want), rel=2e-5)
    assert kernel_calls(kernels.indexer_loss, *operands) == {"indexer_loss_lse": 1,
                                                             "indexer_loss_kl": 1}
    # the backward scales the gradient the forward made by the loss's cotangent
    value, backward = jax.vjp(lambda *a: kernels.indexer_loss(q, k, lse, sel, *a), q_i, k_i, w)
    assert float(value) == float(got)
    for g, one in zip(backward(jnp.float32(2.5)), got_grads):
        np.testing.assert_array_equal(g, 2.5 * one)


def test_the_registry_picks_the_kernels_on_the_chip_alone():
    q_i, k_i, w = _integer_indexer()
    from deeplearning4j_tpu.ops.registry import get_op

    assert get_op("indexer_topk").select(q_i, k_i, w, topk=300).platform == "xla"
    impl = next(i for i in get_op("indexer_topk").impls if i.platform == "pallas")
    assert impl.supported(q_i, k_i, w, topk=300) and not impl.applicable(q_i, k_i, w, topk=300)
    assert not impl.supported(q_i[:, :, :100], k_i[:, :100], w[:, :100], topk=300)     # ragged
    attend = next(i for i in get_op("sparse_attention").impls if i.platform == "pallas")
    q = jnp.zeros((1, 4, 1024, 128))
    kv = jnp.zeros((1, 2, 1024, 128))
    sel = jnp.zeros((1, 1024, 1024), jnp.int8)
    assert attend.supported(q, kv, kv, sel) and attend.scope == "sparse_attention"


# ------------------------------------------------------------------ the experts' shares
def test_the_eight_shares_and_what_every_chip_computes_alike_add_up_to_the_uncut_layer():
    """16 experts in 8 shares of 2, as the cell's 128 in 8 of 16: every share
    routes over all 16 and computes its own experts' part; the attention, the
    indexer and the router are whole on every chip (the same on each, counted
    once) and the shares' routed parts add up to the uncut reference layer."""
    total, share, top, f = 16, 2, 4, 24
    whole = SparseExpertsLayer(n_experts=total, top_k=top, d_expert=f, n_in=D)
    params, _ = seeded(whole, scale=0.2)
    x = inputs().reshape(B * T, D)
    cfg = {"experts_held_first": 0, "num_experts": total, "router_experts": total,
           "num_experts_per_tok": top}
    uncut, aux, _ = reference.experts(x, params, cfg, lambda g: g)
    parts, terms, held = [], [], 0.0
    for first in range(0, total, share):
        layer = SparseExpertsLayer(n_experts=total, top_k=top, d_expert=f, n_in=D,
                                   experts_held=(first, share))
        mine = {**params, **{k: params[k][first:first + share] for k in ("Wg", "Wu", "Wd")}}
        _, state = layer.init(jax.random.key(0), InputType.recurrent(D, T))
        y, new = layer.apply(mine, state, x.reshape(B, T, D))
        parts.append(y.reshape(B * T, D))
        terms.append(float(new[LOSS_TERM]))
        held += float(new["moe_stats"][1])
    np.testing.assert_allclose(sum(parts), uncut, atol=3e-5)
    assert held == B * T * top                                  # every pair is some share's
    assert terms == pytest.approx([terms[0]] * 8, rel=1e-6)      # the router is whole everywhere
    assert terms[0] == pytest.approx(whole.aux_coef * T * float(aux), rel=1e-5)
    # the mixer is the same computation on every chip: its part is counted once
    params_m, state_m = seeded(MIXER)
    a = MIXER.apply(params_m, state_m, inputs())[0]
    b = MIXER.apply(params_m, state_m, inputs())[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------------- the model
def tiny_model(**how):
    args = dict(vocab_size=64, d_model=D, n_layers=4, n_heads=H, n_kv_heads=KV, head_dim=DH,
                mrope_sections=SECTIONS, index_heads=HI, index_head_dim=DI, index_rope_dim=R,
                topk=TOPK, n_experts=8, top_k=2, d_expert=24, experts_held=(2, 2), warmup=4,
                dtype="float32")
    return KeyeVL2(**{**args, **how})


def batches(n=3, rows=2, seq=40, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(*(rng.integers(0, vocab, (rows, seq)).astype(np.int32) for _ in range(2)))
            for _ in range(n)]


def test_the_model_trains_through_fit():
    model = tiny_model().init()
    blocks = model.layers[1:5]
    assert all(type(b.mixer).__name__ == "SparseAttentionLayer" for b in blocks)
    assert all(b.mlp.held == (2, 2) and b.norm == "pre" for b in blocks)
    sets = batches()
    model.fit(sets)
    first = model.score_value
    for _ in range(6):
        model.fit(sets)
    assert np.isfinite(model.score_value) and model.score_value < first
    for n in range(1, 5):
        assert float(model.state[n][INDEXER_LOSS]) > 0
        assert float(model.state[n][SPARSE_TILES]) == 1.0


def test_the_scopes_a_trace_reader_tells_the_mixer_by():
    model = tiny_model().init()
    x = jnp.asarray(batches(1)[0].features)
    compiled = model._make_train_step().lower(
        model.params, model.state, model.opt_state, jnp.asarray(0, jnp.int32), x, x,
        jax.random.key(0), None, None).compile()
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    inside = lambda *parts: [n for n in names if all(p in n for p in parts)]     # noqa: E731
    mixer = "/mixer.SparseAttentionLayer/"
    for scope in ("/indexer/", "sparse_attention"):
        assert inside("jvp(1.DecoderBlock)", mixer, scope), scope
        assert inside("transpose(jvp(1.DecoderBlock))", mixer, scope), scope
    assert inside("jvp(1.DecoderBlock)", mixer, "/indexer/", "indexer_topk")
    assert inside("transpose(jvp(2.DecoderBlock))", "checkpoint", mixer)


def test_the_mixers_gauges_with_monitoring_on_only(monitoring_off):
    monitoring = monitoring_off
    model = tiny_model().init()
    sets = batches()
    model.fit(sets)
    assert monitoring.registry().get("dl4j_train_indexer_kl") is None
    monitoring.enable()
    model.fit(sets)
    text = monitoring.metrics_text()
    for layer in ("1", "2", "3", "4"):
        kl = re.search(r'dl4j_train_indexer_kl\{layer="%s"\} (\S+)' % layer, text)
        tiles = re.search(r'dl4j_train_sparse_attn_tiles_visited\{layer="%s"\} (\S+)' % layer, text)
        assert kl and float(kl.group(1)) == pytest.approx(
            float(model.state[int(layer)][INDEXER_LOSS]), rel=1e-6) and float(kl.group(1)) > 0
        assert tiles and float(tiles.group(1)) == 1.0
        assert f'dl4j_train_moe_pairs_held{{layer="{layer}"}}' in text
