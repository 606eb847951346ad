"""The looped decoder on the CPU: ``DecoderBlock`` (rotary positions, sandwich
RMSNorm, gated MLP), ``LoopedStack`` (a run of layers applied several times
with one set of weights), ``LoopExitOutputLayer`` (a loss over every pass's
exit) and ``zoo.Ouro`` through ``fit``; each against the benchmark's plain
reference (``benchmarks/configs/ouro_2p6b.py``, which imports nothing of the
program) or against the same layers applied by hand."""

import hashlib
import importlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for _path in (ROOT / "tests" / "benchmark", ROOT):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import program_trace  # noqa: E402
from benchmarks.configs import ouro_2p6b as reference  # noqa: E402
from deeplearning4j_tpu.common.env import env  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.conf.builders import (  # noqa: E402
    MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.layers import base as layers_base  # noqa: E402
from deeplearning4j_tpu.nn.layers import (  # noqa: E402
    DecoderBlock, EmbeddingSequenceLayer, LoopedStack, LoopExitOutputLayer, RMSNormLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.attention import apply_rotary, rotary_tables  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.optimize.updaters import Sgd  # noqa: E402
from deeplearning4j_tpu.zoo import Ouro  # noqa: E402

# the module: the package's attribute of that name is the function
flash = importlib.import_module("deeplearning4j_tpu.ops.pallas.flash_attention")

VOCAB, D, HEADS, DH, FF, T, B = 96, 64, 2, 32, 80, 16, 4
CFG = {"hidden_size": D, "num_attention_heads": HEADS, "head_dim": DH, "intermediate_size": FF,
       "vocab_size": VOCAB, "rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_hidden_layers": 2,
       "total_ut_steps": 3, "exit_entropy_beta": 0.05, "initializer_range": 0.02,
       "compute_dtype": "float32"}
BLOCK = DecoderBlock(d_model=D, n_heads=HEADS, head_dim=DH, d_ff=FF, rope_theta=1e6)
ITYPE = InputType.recurrent(D, T)


def tiny_ouro(**over):
    args = dict(vocab_size=VOCAB, d_model=D, n_layers=2, n_heads=HEADS, head_dim=DH, d_ff=FF,
                ut_steps=3, dtype="float32")
    return Ouro(**{**args, **over}).init()


def seeded(model, seed=5):
    """The reference's seeded weights in the program's tree (they are keyed alike)."""
    cfg = {**CFG, "num_hidden_layers": len(model.layers[1].layers),
           "total_ut_steps": model.layers[1].times}
    params, state = reference.make_params(jax.random.key(seed), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(model.params)
    # unit gains and a zero bias hide a wrong gain or bias: move them
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(next(keys), p.shape) if p.ndim == 1 else p, params)
    model.params, model.state = params, state
    return cfg


def batch(seed=0, rows=B):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, VOCAB, (rows, T), dtype=np.int32),
            rng.integers(0, VOCAB, (rows, T), dtype=np.int32))


def loss_of(model, x, y, train=True):
    def loss(p):
        return model._loss_terms(p, model.state, jnp.asarray(x), jnp.asarray(y), None, None,
                                 train=train)[0]
    return loss


def loss_and_grad(model, x, y, train=True):
    return jax.value_and_grad(loss_of(model, x, y, train))(model.params)


def assert_trees_close(got, want, rtol=1e-5, atol=1e-6):
    """Leaf by leaf; ``atol`` counts from the leaf's largest element."""
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol * max(1.0, float(jnp.abs(w).max())))


# ------------------------------------------------------------------ the block
def test_block_agrees_with_the_references_block_in_float32():
    params, _ = BLOCK.init(jax.random.key(1), ITYPE)
    params = {k: v + 0.1 * jax.random.normal(jax.random.key(i), v.shape) if v.ndim == 1 else v
              for i, (k, v) in enumerate(sorted(params.items()))}
    x = jax.random.normal(jax.random.key(2), (B, T, D))
    got, _ = BLOCK.apply(params, {}, x)
    angles = reference.rotary_angles(T, DH, 1e6)
    want = jnp.stack([reference.block(row, params, CFG, angles) for row in x])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_block_is_causal_and_has_no_bias():
    params, state = BLOCK.init(jax.random.key(1), ITYPE)
    assert state == {} and sorted(params) == [
        "Wd", "Wg", "Wk", "Wo", "Wq", "Wu", "Wv", "n1_g", "n2_g", "n3_g", "n4_g"]
    x = jax.random.normal(jax.random.key(2), (1, T, D))
    later = x.at[:, 9:].set(0.0)
    np.testing.assert_allclose(BLOCK.apply(params, {}, x)[0][:, :9],
                               BLOCK.apply(params, {}, later)[0][:, :9], atol=1e-6)


def test_rotary_scores_depend_on_the_distance_only_and_match_rotate_half():
    rope = rotary_tables(T, DH, 1e4)
    q, k = (jax.random.normal(jax.random.key(i), (DH,)) for i in (3, 4))
    at_every_position = lambda v: apply_rotary(jnp.broadcast_to(v, (1, 1, T, DH)), rope)[0, 0]  # noqa: E731
    scores = at_every_position(q) @ at_every_position(k).T          # [i, j]
    for distance in (0, 1, 5):
        along = jnp.diagonal(scores, offset=-distance)              # i - j = distance
        np.testing.assert_allclose(along, along[0], rtol=1e-4, atol=1e-4)
    assert abs(float(scores[5, 0] - scores[0, 5])) > 1e-3           # the sign of i - j matters
    # rotate-half: feature i is paired with i + head_dim / 2
    t = jax.random.normal(jax.random.key(5), (2, HEADS, T, DH))
    cos, sin = rope
    turned = jnp.concatenate([-t[..., DH // 2:], t[..., :DH // 2]], axis=-1)
    np.testing.assert_allclose(apply_rotary(t, rope), t * cos + turned * sin, atol=1e-6)
    np.testing.assert_allclose(apply_rotary(t, rope),
                               reference.rotate(t, reference.rotary_angles(T, DH, 1e4)), atol=1e-5)
    assert cos.dtype == sin.dtype == jnp.float32
    assert apply_rotary(t.astype(jnp.bfloat16), rope).dtype == jnp.bfloat16


# ----------------------------------------------------------- the looped stack
def looped(times=3, n=2):
    return LoopedStack(layers=(BLOCK,) * n, times=times, norm=RMSNormLayer(eps=1e-6))


def by_hand(stack, copies, x):
    """The same layers applied by hand; pass t takes ``copies[t]``."""
    states = []
    for p in copies:
        for i, layer in enumerate(stack.layers):
            x, _ = layer.apply(p[str(i)], {}, x)
        x, _ = stack.norm.apply(p["norm"], {}, x)
        states.append(x)
    return jnp.stack(states)


def test_looped_stack_is_its_layers_applied_by_hand_and_holds_each_once():
    stack = looped()
    params, state = stack.init(jax.random.key(7), ITYPE)
    assert state == {} and sorted(params) == ["0", "1", "norm"]
    assert sum(p.size for p in jax.tree.leaves(params)) == 2 * (4 * D * HEADS * DH + 3 * D * FF + 4 * D) + D
    x = jax.random.normal(jax.random.key(8), (B, T, D))
    got, _ = stack.apply(params, {}, x)
    assert got.shape == (3, B, T, D) and stack.layer_applications == 6
    np.testing.assert_allclose(got, by_hand(stack, [params] * 3, x), rtol=1e-5, atol=1e-5)
    assert stack.output_type(ITYPE).shape == ITYPE.shape


def test_a_shared_leafs_gradient_is_the_sum_of_an_unrolled_copys_per_pass_gradients():
    stack = looped()
    params, _ = stack.init(jax.random.key(7), ITYPE)
    x = jax.random.normal(jax.random.key(8), (B, T, D))
    weigh = jax.random.normal(jax.random.key(9), (3, B, T, D))      # every pass's state counts
    shared = jax.grad(lambda p: (stack.apply(p, {}, x)[0] * weigh).sum())(params)
    per_pass = jax.grad(lambda copies: (by_hand(stack, copies, x) * weigh).sum())([params] * 3)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    assert_trees_close(shared, summed, rtol=1e-4, atol=1e-5)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(per_pass))


def test_looped_stack_refuses_a_layer_that_keeps_state():
    from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer

    with pytest.raises(ValueError, match="stateless"):
        LoopedStack(layers=(BatchNormalizationLayer(),), times=2).init(jax.random.key(0), ITYPE)


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("train", [True, False])
def test_remat_on_and_off_give_the_same_loss_and_gradient(train, kernel, monkeypatch, kernel_calls):
    """On the kernel path (forced, interpret mode) the checkpoints keep the
    kernel's output and log-sum-exp: the values a second call would give."""
    monkeypatch.setattr(env, "force_pallas", kernel)
    x, y = batch()
    with_remat, without = tiny_ouro(remat=True), tiny_ouro(remat=False)
    assert with_remat.conf.remat and not without.conf.remat
    seeded(with_remat), seeded(without)
    (la, ga), (lb, gb) = loss_and_grad(with_remat, x, y, train), loss_and_grad(without, x, y, train)
    if kernel:
        assert float(la) == float(lb)
        assert_trees_close(ga, gb, rtol=0, atol=0)
        calls = kernel_calls(jax.grad(loss_of(with_remat, x, y, train)), with_remat.params)
        assert calls["flash_attention_fwd"] == calls["flash_attention_bwd"] == 2
    else:
        np.testing.assert_allclose(la, lb, rtol=1e-6)
        assert_trees_close(ga, gb, rtol=1e-5, atol=1e-7)


def test_under_remat_every_layer_application_is_its_own_checkpoint_never_the_loop():
    remats = re.compile(r"= (?:remat|checkpoint)\w*\[")
    stack = looped()
    params, _ = stack.init(jax.random.key(7), ITYPE)
    x = jnp.zeros((B, T, D))
    alone = str(jax.make_jaxpr(lambda p: stack.apply(p, {}, x, train=True, remat=True)[0])(params))
    assert len(remats.findall(alone)) == 2                      # one a held layer, in one scan body
    assert alone.index("scan[") < remats.search(alone).start()  # inside the scan, not around it
    assert not remats.search(str(jax.make_jaxpr(lambda p: stack.apply(p, {}, x, train=True)[0])(params)))
    model = tiny_ouro(remat=True)
    x, y = batch()
    whole = str(jax.make_jaxpr(lambda p: model._loss_terms(
        p, model.state, jnp.asarray(x), jnp.asarray(y), None, None)[0])(model.params))
    assert len(remats.findall(whole)) == 1 + 2 + 1      # the embedding, the two held layers, one exit


# ------------------------------------- what a layer's checkpoint keeps of the kernel
def plain_net(remat):
    b = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(lr=0.1)).gradient_checkpointing(remat)
         .list().layer(EmbeddingSequenceLayer(n_in=VOCAB, n_out=D)).layer(BLOCK).layer(BLOCK)
         .layer(RnnOutputLayer(n_out=VOCAB, has_bias=False, activation="softmax", loss="sparsemcxent")))
    return MultiLayerNetwork(b.set_input_type(InputType.recurrent(VOCAB, T)).build()).init()


def stack_gradient(remat=True):
    stack = looped()
    params, _ = stack.init(jax.random.key(7), ITYPE)
    x = jax.random.normal(jax.random.key(8), (B, T, D))
    return jax.grad(lambda p: stack.apply(p, {}, x, train=True, remat=remat)[0].sum()), params


def multilayer_gradient(remat=True):
    model = plain_net(remat)
    return jax.grad(loss_of(model, *batch())), model.params


def graph_gradient(remat=True):
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(lr=0.1)).gradient_checkpointing(remat)
            .graph_builder().add_inputs("in").set_input_types(**{"in": InputType.recurrent(D, T)})
            .add_layer("a", BLOCK, "in").add_layer("b", BLOCK, "a")
            .add_layer("out", RnnOutputLayer(n_out=VOCAB, has_bias=False, activation="softmax",
                                             loss="sparsemcxent"), "b")
            .set_outputs("out").build())
    graph = ComputationGraph(conf).init()
    x = jax.random.normal(jax.random.key(8), (B, T, D))
    return jax.grad(lambda p: graph._forward(p, graph.state, {"in": x}, True, None)[0]["b"].sum()), graph.params


GRADIENTS = {"looped_stack": stack_gradient, "multilayer": multilayer_gradient, "graph": graph_gradient}


@pytest.mark.parametrize("which", sorted(GRADIENTS))
def test_under_remat_the_kernels_forward_stands_once_for_each_backward(which, monkeypatch, kernel_calls):
    """Two blocks: the gradient holds the forward kernel twice, as without
    ``remat``; under a checkpoint that keeps nothing by name, four times."""
    monkeypatch.setattr(env, "force_pallas", True)
    kept = kernel_calls(*GRADIENTS[which]())
    assert kept["flash_attention_bwd"] == 2 and len(kept) == 2  # the fused call, no dq / dkv pair
    assert kept["flash_attention_fwd"] == kept["flash_attention_bwd"]
    assert kernel_calls(*GRADIENTS[which](remat=False)) == kept
    monkeypatch.setattr(layers_base, "REMAT_POLICY", None)      # a bare jax.checkpoint
    bare = kernel_calls(*GRADIENTS[which]())
    assert bare["flash_attention_fwd"] == 2 * bare["flash_attention_bwd"] == 4
    assert len(bare) == 2


@pytest.mark.parametrize("which", sorted(GRADIENTS))
def test_the_policy_is_inert_where_xlas_attention_runs(which, monkeypatch, kernel_calls):
    """T = 16 is under the kernel's predicate: nothing in a layer is named, and
    the step under ``remat`` is the bare checkpoint's, to the letter."""
    fn, params = GRADIENTS[which]()
    assert not kernel_calls(fn, params)
    assert not {flash.SAVED_OUT, flash.SAVED_LSE} & set(re.findall(r"name=(\w+)", str(jax.make_jaxpr(fn)(params))))
    kept = jax.jit(fn).lower(params).as_text()
    assert flash.SAVED_OUT not in kept and flash.SAVED_LSE not in kept
    monkeypatch.setattr(layers_base, "REMAT_POLICY", None)
    fn, params = GRADIENTS[which]()
    assert jax.jit(fn).lower(params).as_text() == kept


def test_outside_a_checkpoint_the_kernels_names_lower_to_nothing(monkeypatch):
    monkeypatch.setattr(env, "force_pallas", True)
    fn, params = stack_gradient(remat=False)
    assert {flash.SAVED_OUT, flash.SAVED_LSE} <= set(re.findall(r"name=(\w+)", str(jax.make_jaxpr(fn)(params))))
    lowered = jax.jit(fn).lower(params).as_text()
    assert flash.SAVED_OUT not in lowered and flash.SAVED_LSE not in lowered


# --------------------------------------------------------------- the exits
def test_exit_distribution_sums_to_one_and_agrees_with_the_reference():
    gates = 3.0 * jax.random.normal(jax.random.key(3), (4, B, T))
    log_p = LoopExitOutputLayer.exit_log_probs(gates)
    p = jnp.exp(log_p)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    want, want_log = reference.exit_probabilities(gates)
    np.testing.assert_allclose(p, want, atol=1e-6)
    np.testing.assert_allclose(log_p, want_log, rtol=1e-5, atol=1e-6)
    g = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(p[0], g[0], atol=1e-6)
    np.testing.assert_allclose(p[2], g[2] * (1 - g[0]) * (1 - g[1]), atol=1e-6)
    np.testing.assert_allclose(p[3], (1 - g[0]) * (1 - g[1]) * (1 - g[2]), atol=1e-6)
    # far-out gates stay finite: worked out from log-sigmoids
    assert bool(jnp.isfinite(LoopExitOutputLayer.exit_log_probs(jnp.full((4, 1, 1), 200.0))[0]).all())


def exits_alone(times=3, beta=0.05):
    layer = LoopExitOutputLayer(n_out=VOCAB, times=times, beta=beta)
    params, state = layer.init(jax.random.key(1), InputType.recurrent(D, T))
    assert state["exit_share"].shape == (times,)
    states = jax.random.normal(jax.random.key(2), (times, B, T, D))
    labels = jnp.asarray(batch()[1])
    ce = jnp.stack([-jnp.take_along_axis(jax.nn.log_softmax(z @ params["W"]), labels[..., None], -1)[..., 0]
                    for z in states])
    return layer, params, state, states, labels, ce


def test_a_gate_forced_open_at_pass_one_gives_pass_ones_cross_entropy():
    layer, params, state, states, labels, ce = exits_alone()
    opened = {**params, "Wg": jnp.zeros_like(params["Wg"]), "bg": jnp.full((1,), 40.0)}
    per, new_state = layer.score_from_features(opened, state, labels, states)
    np.testing.assert_allclose(per, ce[0].sum(axis=1), rtol=1e-5)
    np.testing.assert_allclose(new_state["exit_share"], [1.0, 0.0, 0.0], atol=1e-6)
    shut = {**opened, "bg": jnp.full((1,), -40.0)}          # every gate shut: the last pass takes all
    per, new_state = layer.score_from_features(shut, state, labels, states)
    np.testing.assert_allclose(per, ce[2].sum(axis=1), rtol=1e-5)
    np.testing.assert_allclose(new_state["exit_share"], [0.0, 0.0, 1.0], atol=1e-6)


def test_exits_refuse_another_number_of_passes_than_their_own():
    layer, params, state, states, labels, _ = exits_alone(times=3)
    with pytest.raises(ValueError, match="times=3.*handed 2 passes"):
        layer.score_from_features(params, state, labels, states[:2])


def test_the_entropy_term_is_subtracted_and_the_score_is_the_references():
    layer, params, state, states, labels, ce = exits_alone(beta=0.0)
    gates = jnp.stack([z @ params["Wg"] + params["bg"] for z in states])
    plain, _ = layer.score_from_features(params, state, labels, states)
    with_entropy, new_state = LoopExitOutputLayer(n_out=VOCAB, times=3, beta=0.5).score_from_features(
        params, state, labels, states)
    p, log_p = reference.exit_probabilities(gates)
    entropy = -(p * log_p).sum(0)
    assert float(entropy.min()) > 0
    np.testing.assert_allclose(with_entropy, plain - 0.5 * entropy.sum(axis=1), rtol=1e-5)
    assert bool((with_entropy < plain).all())               # a spread-out exit is rewarded
    want, want_p = reference.position_loss(ce, gates, 0.5)
    np.testing.assert_allclose(with_entropy, want.sum(axis=1), rtol=1e-5)
    np.testing.assert_allclose(new_state["exit_share"], want_p.mean(axis=(1, 2)), atol=1e-6)
    # a masked position counts for nothing
    mask = jnp.ones((B, T)).at[:, 5:].set(0.0)
    masked, _ = layer.score_from_features(params, state, labels, states, mask)
    np.testing.assert_allclose(masked, reference.position_loss(ce, gates, 0.0)[0][:, :5].sum(axis=1),
                               rtol=1e-5)


def test_one_pass_with_the_gate_shut_is_a_plain_stack_with_rnn_output_layer():
    def net(*layers):
        b = NeuralNetConfiguration.builder().seed(3).updater(Sgd(lr=0.1)).list()
        for layer in layers:
            b = b.layer(layer)
        return MultiLayerNetwork(b.set_input_type(InputType.recurrent(VOCAB, T)).build()).init()

    embed = EmbeddingSequenceLayer(n_in=VOCAB, n_out=D)
    once = net(embed, looped(times=1), LoopExitOutputLayer(n_out=VOCAB, times=1))
    plain = net(embed, BLOCK, BLOCK, RMSNormLayer(eps=1e-6),
                RnnOutputLayer(n_out=VOCAB, has_bias=False, activation="softmax", loss="sparsemcxent"))
    seeded(once)
    table, stack, exits = once.params
    once.params[2] = {**exits, "bg": jnp.full((1,), -40.0)}
    plain.params = [table, stack["0"], stack["1"], stack["norm"], {"W": exits["W"]}]
    x, y = batch()
    (la, ga), (lb, gb) = loss_and_grad(once, x, y), loss_and_grad(plain, x, y)
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    assert_trees_close([ga[0], ga[1]["0"], ga[1]["1"], ga[1]["norm"], ga[2]["W"]],
                       [gb[0], gb[1], gb[2], gb[3], gb[4]["W"]], rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(ga[2]["Wg"]).max()) == 0.0     # one pass: the gate decides nothing
    np.testing.assert_allclose(once.output(x), jnp.log(plain.output(x)) + jax.nn.logsumexp(
        once.output(x), axis=-1, keepdims=True), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- the model through fit
def test_the_models_loss_and_gradient_are_the_references():
    model = tiny_ouro()
    cfg = seeded(model)
    x, y = batch()
    loss, grads = loss_and_grad(model, x, y)
    (want, new_state), want_grads = jax.value_and_grad(
        lambda p: reference.loss_fn(p, model.state, jnp.asarray(x), jnp.asarray(y), cfg), has_aux=True)(
        model.params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert float(loss) == pytest.approx(T * np.log(VOCAB), rel=0.05)    # summed over a row, mean of rows
    assert_trees_close(grads, want_grads, rtol=2e-4, atol=1e-6)
    _, states, _ = model._loss_terms(model.params, model.state, jnp.asarray(x), jnp.asarray(y), None, None)
    np.testing.assert_allclose(states[-1]["exit_share"], new_state[-1]["exit_share"], atol=1e-6)


def test_ouro_trains_through_fit_with_the_prefetch_iterator_and_counts_each_layer_once():
    from deeplearning4j_tpu.datasets.iterators import AsyncPrefetchIterator, ListDataSetIterator

    model = tiny_ouro()
    per_layer = 4 * D * HEADS * DH + 3 * D * FF + 4 * D
    assert model.num_params() == 2 * per_layer + 2 * VOCAB * D + D + D + 1
    assert [type(layer).__name__ for layer in model.layers] == [
        "EmbeddingSequenceLayer", "LoopedStack", "LoopExitOutputLayer"]
    sets = [DataSet(*batch(seed)) for seed in range(4)]
    first = model.score(sets[0])
    model.fit(AsyncPrefetchIterator(ListDataSetIterator(sets)), epochs=4)
    assert model.score(sets[0]) < first
    assert model._jit_cache["train"]._cache_size() == 1        # the exits' state keeps its shape
    share = np.asarray(model.state[-1]["exit_share"])
    assert share.shape == (3,) and share.sum() == pytest.approx(1.0, abs=1e-5)
    logits = model.output(sets[0].features)
    assert logits.shape == (B, T, VOCAB)                        # the last pass's logits
    assert len(model.feed_forward(sets[0].features)) == 4


def test_exit_shares_and_loop_gauges_are_recorded_with_monitoring_on_only(monitoring_off):
    monitoring = monitoring_off
    model = tiny_ouro()
    sets = [DataSet(*batch(seed)) for seed in range(3)]
    model.fit(sets)
    assert monitoring.registry().get("dl4j_train_exit_share") is None
    monitoring.enable()
    model.fit(sets)
    text = monitoring.metrics_text()
    shares = [float(m.group(1)) for m in re.finditer(r'dl4j_train_exit_share\{pass="\d"\} (\S+)', text)]
    np.testing.assert_allclose(shares, model.state[-1]["exit_share"], atol=1e-6)


@pytest.mark.parametrize("remat", [True, False])
def test_the_gauge_of_kernel_keeping_applications_follows_remat_and_leaves_the_step_alone(
        remat, monitoring_off):
    monitoring = monitoring_off
    model = tiny_ouro(remat=remat)
    args = (model.params, model.state, model.opt_state, jnp.asarray(0, jnp.int32),
            *map(jnp.asarray, batch()), jax.random.key(0), None)
    off = model._make_train_step().lower(*args).as_text()
    monitoring.enable()
    model.fit([DataSet(*batch())])
    # the step monitoring fits is the step it lowers with monitoring off
    assert tiny_ouro(remat=remat)._make_train_step().lower(*args).as_text() == off


# ------------------------------------------------------------------- round trips
def test_json_and_save_load_round_trips(tmp_path):
    model = tiny_ouro()
    conf = MultiLayerConfiguration.from_json(model.conf.to_json())
    assert conf.layers == model.conf.layers and conf.remat and conf.to_json() == model.conf.to_json()
    stack = conf.layers[1]
    assert isinstance(stack.layers, tuple) and stack.layers[0] == BLOCK.__class__(
        d_model=D, n_heads=HEADS, head_dim=DH, d_ff=FF, rope_theta=1e6, rms_eps=1e-6)
    assert isinstance(stack.norm, RMSNormLayer) and stack.times == 3
    x, y = batch()
    model.fit_batch((x, y))
    model.save(str(tmp_path / "ouro.zip"))
    back = MultiLayerNetwork.load(str(tmp_path / "ouro.zip"))
    assert jax.tree.structure(back.params) == jax.tree.structure(model.params)
    assert_trees_close(back.params, model.params, rtol=0, atol=0)
    assert_trees_close(back.opt_state, model.opt_state, rtol=0, atol=0)
    np.testing.assert_allclose(back.output(x), model.output(x), rtol=1e-6)
    assert float(back.fit_batch((x, y))) == pytest.approx(float(model.fit_batch((x, y))), rel=1e-6)


# ------------------------------------------------------ scopes of the compiled step
def test_the_compiled_steps_op_names_carry_the_loops_the_blocks_and_the_exits_scopes():
    model = tiny_ouro()
    x, y = batch()
    compiled = model._make_train_step().lower(
        model.params, model.state, model.opt_state, jnp.asarray(0, jnp.int32), jnp.asarray(x),
        jnp.asarray(y), jax.random.key(0), None).compile()
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    inside = lambda *parts: [n for n in names if all(p in n for p in parts)]  # noqa: E731
    assert inside("jvp(1.LoopedStack)/while/body/", "/0.DecoderBlock/")
    assert inside("jvp(1.LoopedStack)/while/body/", "/1.DecoderBlock/")
    assert inside("transpose(jvp(1.LoopedStack))/while/body/", "/1.DecoderBlock/", "checkpoint")
    assert inside("jvp(1.LoopedStack)", "norm.RMSNormLayer")
    assert inside("jvp(loss)/", "/exit/") and inside("transpose(jvp(loss))/", "/exit/")
    assert [n for n in inside("jvp(loss)/") if "/exit/" not in n]       # what mixes the passes
    assert not inside("2.LoopExitOutputLayer")      # the pre-output is not worked out in training
    # the scope readers' own test of "this step has layer scopes"
    scoped = [n for n in names if program_trace.LAYER_SCOPE.search(n)]
    assert any("jvp(1.LoopedStack)" in n for n in scoped)
    assert any("jvp(0.EmbeddingSequenceLayer)" in n for n in scoped)


def test_bert_bases_lowered_train_step_is_text_identical_to_the_parents():
    """The decoder block is a layer of its own and ``_forward`` learned two
    things (a container that checkpoints inside itself, an output layer that
    scores its input): neither may change the step of a model that has
    neither. The digest is the parent commit's (c0e05d2), from the same code."""
    from benchmark_tiny import tiny_cell
    from benchmarks.drivers import fit

    cell = tiny_cell("bert_base")
    model = fit.build_model(cell.config)
    x = jnp.zeros((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    y = jnp.zeros((cell.traffic["batch"], 2), jnp.float32)
    text = model._make_train_step().lower(
        model.params, model.state, model.opt_state, jnp.asarray(0, jnp.int32), x, y,
        jax.random.key(0), None, None).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "9d0dfcd7984fbe9587789b2f2df5156d1335bfab7fb39a9f7bbde9b6e2ca3321")


def test_the_looped_decoders_lowered_train_step_is_text_identical_to_the_parents():
    """PR 34 gave ``_loss_terms`` a channel for a layer's own term of the score,
    ``DecoderBlock`` grouped heads, a window, a second norm placement and an
    expert MLP, and the attention ops a ``window``: none may change the step of
    a model that uses none of them. The digest is the parent commit's (e480ce2),
    from the same lines."""
    from benchmark_tiny import tiny_cell
    from benchmarks.drivers import fit
    from deeplearning4j_tpu.nn.layers.base import layer_loss_terms

    cell = tiny_cell("ouro_2p6b")
    model = fit.build_model(cell.config)
    x = jnp.zeros((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    text = model._make_train_step().lower(
        model.params, model.state, model.opt_state, jnp.asarray(0, jnp.int32), x, x,
        jax.random.key(0), None, None).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "615b59a498f5cb3e5e78ce39780cf3127978d9dc5912a3ee65503d778d3cc500")
    assert layer_loss_terms(model.state) == []
