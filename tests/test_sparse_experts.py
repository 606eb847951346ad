"""Sparse experts as a layer, the loss term a layer hands back, grouped
key-value heads, a causal window and YaRN tables in ``DecoderBlock``, and
``zoo.Mellum2`` through ``fit``, on the CPU at a small size; each against the
benchmark's plain reference (``benchmarks/configs/mellum2_12b.py``, which
imports nothing of the program)."""

import math
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

from benchmarks.configs import mellum2_12b as reference  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.conf.builders import (  # noqa: E402
    MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.layers import (  # noqa: E402
    DecoderBlock, DenseLayer, EmbeddingSequenceLayer, OutputLayer, RnnOutputLayer,
    SparseExpertsLayer,
)
from deeplearning4j_tpu.nn.layers import experts as experts_module  # noqa: E402
from deeplearning4j_tpu.nn.layers.attention import rotary_tables, yarn_inv_freq  # noqa: E402
from deeplearning4j_tpu.nn.layers.base import LOSS_TERM, layer_loss_terms  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.optimize.updaters import Sgd  # noqa: E402
from deeplearning4j_tpu.zoo import Mellum2, Ouro  # noqa: E402

D, F, E, K, T, B = 32, 24, 8, 2, 10, 3
ITYPE = InputType.recurrent(D, T)
YARN = (16.0, 8, 32.0, 1.0, 1.2772588722239782)


def cfg_for(layer: SparseExpertsLayer) -> dict:
    first, held = layer.held
    return {"experts_held_first": first, "num_experts": held, "router_experts": layer.n_experts,
            "num_experts_per_tok": layer.top_k}


def layer_and_reference(layer, params, x):
    """((y, term, stats), (y, term, stats)) of the layer and of the plain
    reference on the same weights; the term as the score takes it."""
    y, state = layer.apply(params, layer.init(jax.random.key(0), ITYPE)[1], x)
    want_y, aux, load = reference.experts(x.reshape(-1, x.shape[-1]), params, cfg_for(layer),
                                          lambda f: f)
    return ((y, state[LOSS_TERM], state["moe_stats"]),
            (want_y.reshape(x.shape), layer.aux_coef * x.shape[1] * aux, load))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------------- the layer
#: a size at which the row-gather kernels' shape contracts hold (256 tokens of
#: 128 float32, 512 pairs), so the registry takes them (interpreted here)
TILED = (2, 128, 128)


def platforms(layer, x):
    """The implementation the registry takes for each of the layer's row ops."""
    tokens, d = x.shape[0] * x.shape[1], x.shape[-1]
    pairs = tokens * layer.top_k
    xt, rows = jnp.zeros((tokens, d), x.dtype), jnp.zeros((pairs, d), x.dtype)
    index, place = jnp.zeros((pairs,), jnp.int32), jnp.zeros((tokens, layer.top_k), jnp.int32)
    n, scale = jnp.int32(0), jnp.zeros((pairs,))
    return {experts_module.op("gather_rows").select(xt, index, n).platform,
            experts_module.op("gather_sum_rows").select(rows, place, n).platform,
            experts_module.op("gather_rows_dot").select(xt, index, n, scale, rows).platform}


@pytest.mark.parametrize("held,size", [
    (None, None), ((2, 2), None), ((0, 1), None), ((6, 2), None), (None, TILED), ((2, 2), TILED)],
    ids=str)
def test_the_layer_matches_the_reference_forward_and_gradient(held, size):
    B, T, D = size or (3, 10, 32)
    ITYPE = InputType.recurrent(D, T)
    layer = SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, experts_held=held, aux_coef=0.01)
    params, _ = layer.init(jax.random.key(1), ITYPE)
    x = jax.random.normal(jax.random.key(2), (B, T, D))
    assert platforms(layer, x) == ({"pallas"} if size else {"xla"})

    def program(params, x):
        y, state = layer.apply(params, {}, x)
        return (y ** 2).sum() + state[LOSS_TERM]

    def plain(params, x):
        y, aux, _ = reference.experts(x.reshape(-1, D), params, cfg_for(layer), lambda f: f)
        return (y ** 2).sum() + layer.aux_coef * T * aux

    got, got_grads = jax.value_and_grad(program, argnums=(0, 1))(params, x)
    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        # of the leaf's largest where that passes 1: at D 128 the router's gradient reaches 300
        np.testing.assert_allclose(g, w, atol=2e-5 * max(1.0, float(jnp.abs(w).max())))
    y, state = layer.apply(params, layer.init(jax.random.key(0), ITYPE)[1], x)
    _, aux, load = reference.experts(x.reshape(-1, D), params, cfg_for(layer), lambda f: f)
    np.testing.assert_allclose(state[LOSS_TERM], layer.aux_coef * T * aux, rtol=1e-6)
    np.testing.assert_allclose(state["moe_stats"], load, rtol=1e-6)


@pytest.mark.parametrize("top_k", [1, 4])
def test_every_token_on_one_expert_drops_nothing_and_pads_nothing(top_k):
    """All ``tokens x top_k`` pairs in one group (with ``top_k`` 4: on the four
    experts every token prefers, a quarter of the pairs each, the other four
    experts empty): no capacity, so every pair is served."""
    layer = SparseExpertsLayer(n_experts=E, top_k=top_k, d_expert=F)
    params, _ = layer.init(jax.random.key(3), ITYPE)
    x = jnp.abs(jax.random.normal(jax.random.key(4), (B, T, D))) + 0.1
    favoured = jnp.arange(E) < top_k
    params["Wr"] = jnp.where(favoured[None, :], 1.0 + 0.01 * jnp.arange(E), -1.0) * jnp.ones((D, E))
    (y, _, stats), (want_y, _, _) = layer_and_reference(layer, params, x)
    np.testing.assert_allclose(y, want_y, atol=2e-6)
    assert stats[1] == B * T * top_k and stats[2] == 0          # pairs held, tokens unserved
    assert stats[0] == pytest.approx(E / top_k)                  # the largest group over the mean
    chosen, weights, order, place, sizes, sorted_weights = experts_module.route(
        jax.nn.softmax(x.reshape(-1, D) @ params["Wr"]), top_k, 0, E)
    assert sizes.tolist() == [B * T] * top_k + [0] * (E - top_k)
    assert sorted(order.tolist()) == list(range(B * T * top_k))
    assert (order[place] == jnp.arange(B * T * top_k)).all()
    assert (sorted_weights == weights.reshape(-1)[order]).all()


@pytest.mark.parametrize("size", [None, TILED], ids=["plain", "kernels"])
def test_rows_past_the_held_pairs_never_reach_a_sum(size):
    """What a grouped product leaves in the rows no group holds is unspecified:
    with NaN there, the result and every gradient stay finite and unchanged."""
    B, T, D = size or (3, 10, 32)
    layer = SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, experts_held=(2, 2))
    params, _ = layer.init(jax.random.key(5), InputType.recurrent(D, T))
    x = jax.random.normal(jax.random.key(6), (B, T, D))
    assert platforms(layer, x) == ({"pallas"} if size else {"xla"})

    def poisoned(lhs, rhs, sizes):
        rows = jnp.arange(lhs.shape[0])[:, None]
        return jnp.where(rows < sizes.sum(), jax.lax.ragged_dot(lhs, rhs, sizes), jnp.nan)

    def loss(params, x):
        return (layer.apply(params, {}, x)[0] ** 2).sum()

    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    impl = experts_module.op("grouped_matmul").xla
    real, impl.fn = impl.fn, poisoned
    try:
        got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    finally:
        impl.fn = real
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=1e-6)


def test_the_pallas_grouped_product_agrees_with_the_plain_lowering_on_the_held_rows():
    """The registry takes jax's Pallas kernels where the rows are whole tiles
    (interpret mode here); over the rows the groups hold they give what
    ``jax.lax.ragged_dot`` gives, forward and both gradients, an empty group
    among them."""
    from deeplearning4j_tpu.ops.pallas import grouped_matmul as pallas_impl

    grouped = experts_module.op("grouped_matmul")
    x = jax.random.normal(jax.random.key(11), (4096, 256))
    w = jax.random.normal(jax.random.key(12), (4, 256, 128))
    sizes = jnp.asarray([1000, 0, 2000, 500], jnp.int32)
    assert grouped.select(x, w, sizes).platform == "pallas"
    assert grouped.select(x[:1000], w, sizes).platform == "xla"
    assert grouped.select(x.astype(jnp.bfloat16), w, sizes).platform == "xla"      # mixed types

    def loss(impl):
        return jax.value_and_grad(lambda x, w: (impl(x, w, sizes)[:3500] ** 2).sum(),
                                  argnums=(0, 1))(x, w)

    got, (got_x, got_w) = loss(pallas_impl.grouped_matmul)
    want, (want_x, want_w) = loss(grouped.xla.fn)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_x[:3500], want_x[:3500], atol=1e-3)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-4, atol=1e-2)
    assert float(jnp.abs(got_w[1]).max()) == 0.0            # the empty group's weights


def test_the_shares_add_up_to_the_uncut_layer_and_their_terms_are_equal():
    """The share test: four layers that hold experts 0-1, 2-3, 4-5, 6-7 of 8
    (top-2), each with the whole router and its own experts' weights, give
    parts that add up to the uncut reference's layer; every share's term of the
    score is the whole router's."""
    whole = SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, aux_coef=0.01)
    params, _ = whole.init(jax.random.key(7), ITYPE)
    x = jax.random.normal(jax.random.key(8), (B, T, D))
    want_y, aux, _ = reference.experts(x.reshape(-1, D), params, cfg_for(whole), lambda f: f)
    parts, terms, unserved = [], [], []
    for first in range(0, E, 2):
        share = SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, experts_held=(first, 2),
                                   aux_coef=0.01)
        own = {"Wr": params["Wr"], **{k: params[k][first:first + 2] for k in ("Wg", "Wu", "Wd")}}
        y, state = share.apply(own, {}, x)
        parts.append(y)
        terms.append(float(state[LOSS_TERM]))
        unserved.append(int(state["moe_stats"][2]))
    np.testing.assert_allclose(sum(parts), want_y.reshape(x.shape), atol=2e-6)
    assert terms == [pytest.approx(0.01 * T * float(aux), rel=1e-6)] * 4
    assert min(unserved) > 0            # some token has neither of its two experts in a share


def test_the_layer_round_trips_and_says_which_experts_it_holds():
    layer = SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, experts_held=(4, 2), aux_coef=0.02)
    again = SparseExpertsLayer.from_dict(layer.to_dict())
    assert again == layer and again.experts_held == (4, 2) and again.held == (4, 2)
    assert SparseExpertsLayer(n_experts=E).held == (0, E)
    with pytest.raises(ValueError, match="no run"):
        SparseExpertsLayer(n_experts=E, experts_held=(6, 4)).held
    params, state = layer.init(jax.random.key(0), ITYPE)
    assert {k: v.shape for k, v in params.items()} == {
        "Wr": (D, E), "Wg": (2, D, F), "Wu": (2, D, F), "Wd": (2, F, D)}
    assert sorted(state) == [LOSS_TERM, "moe_stats"]


@pytest.mark.parametrize("size", [None, TILED], ids=["plain", "kernels"])
def test_the_scopes_a_trace_reader_splits_the_layer_by(size):
    B, T, D = size or (3, 10, 32)
    layer = SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F)
    params, state = layer.init(jax.random.key(0), InputType.recurrent(D, T))
    x = jax.random.normal(jax.random.key(1), (B, T, D))
    text = jax.jit(jax.grad(lambda p, x: (layer.apply(p, state, x)[0] ** 2).sum(),
                            argnums=(0, 1))).lower(params, x).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("router", "route", "dispatch", "expert_matmul", "combine"):
        inside = [n for n in names if re.search(rf"[(/]{scope}[)/]", n)]
        assert inside and any("transpose(" in n for n in inside), scope     # forward and backward
    if size:        # the kernels' calls keep the scope they were written under
        for scope, kernel in (("dispatch", "gather_rows"), ("dispatch", "gather_sum_rows"),
                              ("combine", "gather_sum_rows"), ("combine", "gather_rows_dot")):
            assert any(re.search(rf"[(/]{scope}\)*/{kernel}/", n) for n in names), (scope, kernel)


# ----------------------------------------------------------------- the row ops
ROW_T, ROW_K = 256, 4           # 1,024 pairs; the gather's tile is 256 rows, the sum's 32 tokens
HELD = {"none": 0, "one_row": 1, "ragged": 300, "a_quarter": 256, "all": 1024}


def row_case(dtype, d, n):
    """A permutation of the pairs with, past the ``n`` counted rows, NaN in the
    sorted rows and indices far out of range."""
    pairs = ROW_T * ROW_K
    keys = jax.random.split(jax.random.key(n + d), 6)
    order = jax.random.permutation(keys[0], pairs).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(jnp.arange(pairs, dtype=jnp.int32))
    counted = jnp.arange(pairs) < n
    return dict(
        table=jax.random.normal(keys[1], (ROW_T, d)).astype(dtype),
        rows=jnp.where(counted[:, None], jax.random.normal(keys[2], (pairs, d)), jnp.nan).astype(dtype),
        index=jnp.where(counted, order // ROW_K, 2 ** 30), order=order,
        place=place.reshape(ROW_T, ROW_K), scale=jax.random.normal(keys[3], (pairs,)),
        weights=jax.random.normal(keys[4], (ROW_T, ROW_K)), n=jnp.int32(n), counted=counted)


def both(name, *args):
    """(the Pallas kernel's, the plain lowering's) result of op ``name``."""
    chosen = experts_module.op(name)
    assert chosen.select(*args).platform == "pallas"
    return chosen(*args), chosen.xla.fn(*args)


def counted_rows(c, *arrays):
    return [np.asarray(jnp.where(c["counted"].reshape((-1,) + (1,) * (a.ndim - 1)), a, 0),
                       np.float32) for a in arrays]


@pytest.mark.parametrize("dtype,d", [(jnp.float32, 128), (jnp.bfloat16, 256)], ids=["f32", "bf16"])
@pytest.mark.parametrize("held", list(HELD))
def test_gather_rows_moves_the_counted_rows_and_reads_no_index_past_them(held, dtype, d):
    c = row_case(dtype, d, HELD[held])
    for scale in (None, c["scale"]):
        got, want = counted_rows(c, *both("gather_rows", c["table"], c["index"], c["n"], scale))
        np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("dtype,d", [(jnp.float32, 128), (jnp.bfloat16, 256)], ids=["f32", "bf16"])
@pytest.mark.parametrize("held", list(HELD))
def test_gather_rows_dot_gives_both_results_from_one_gather(held, dtype, d):
    c = row_case(dtype, d, HELD[held])
    (got, got_dots), (want, want_dots) = both("gather_rows_dot", c["table"], c["index"], c["n"],
                                             c["scale"], c["rows"])
    np.testing.assert_allclose(*counted_rows(c, got, want), rtol=1e-6)
    np.testing.assert_allclose(*counted_rows(c, got_dots, want_dots), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,d", [(jnp.float32, 128), (jnp.bfloat16, 256)], ids=["f32", "bf16"])
@pytest.mark.parametrize("held", list(HELD))
def test_gather_sum_rows_sums_the_held_slots_and_no_nan_past_them(held, dtype, d):
    c = row_case(dtype, d, HELD[held])
    tight = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=1e-2, atol=1e-2)
    more = jnp.where(c["counted"][:, None], 1 - 0.5 * jnp.nan_to_num(c["rows"]), jnp.nan).astype(dtype)
    for weights, more in ((None, None), (c["weights"], None), (None, more)):
        got, want = both("gather_sum_rows", c["rows"], c["place"], c["n"], weights, more)
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **tight)
    if HELD[held] == 0:
        assert float(jnp.abs(got.astype(jnp.float32)).max()) == 0.0


@pytest.mark.parametrize("held", list(HELD))
def test_dispatch_and_combine_are_differentiated_by_the_kernels_as_by_the_plain_ops(
        held, monkeypatch):
    """Value and every gradient of the two permutations, kernels against plain
    lowerings, on the rows that count (``d_rows`` past them is unspecified)."""
    from deeplearning4j_tpu.common.env import env

    c = row_case(jnp.float32, 128, HELD[held])
    order, place = c["order"], c["place"].reshape(-1)
    rows = jnp.nan_to_num(c["rows"])
    g_rows = jax.random.normal(jax.random.key(7), rows.shape)
    g_y = jax.random.normal(jax.random.key(8), c["table"].shape)

    def run():
        (sent, again), pull = jax.vjp(
            lambda xt: experts_module.dispatch(xt, order, place, c["n"], ROW_K), c["table"])
        assert again is sent
        y, back = jax.vjp(lambda r, w: experts_module.combine(
            r, w, c["weights"].reshape(-1)[order], order, place, c["n"]), rows, c["weights"])
        d_rows, d_weights = back(g_y)
        poisoned = jnp.where(c["counted"][:, None], g_rows, jnp.nan)
        return counted_rows(c, sent, d_rows) + [pull((poisoned, poisoned[:, ::-1]))[0], y, d_weights]

    got = run()
    monkeypatch.setattr(env, "disable_pallas", True)
    for g, w in zip(got, run()):
        assert bool(np.isfinite(g).all())
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


# ----------------------------------------------------- a loss term from a layer
def expert_net(remat=False, with_experts=True):
    builder = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(lr=0.1)).data_type("float32")
               .gradient_checkpointing(remat).list()
               .layer(EmbeddingSequenceLayer(n_in=40, n_out=D)))
    if with_experts:
        builder = builder.layer(SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, aux_coef=0.5))
    return MultiLayerNetwork(
        builder.layer(RnnOutputLayer(n_out=40, has_bias=False, loss="sparsemcxent"))
        .set_input_type(InputType.recurrent(40, None)).build()).init()


@pytest.mark.parametrize("remat", [False, True])
def test_the_layers_term_enters_the_score_and_is_differentiated(remat):
    net = expert_net(remat)
    rng = np.random.default_rng(0)
    x, y = (rng.integers(0, 40, (B, T)).astype(np.int32) for _ in range(2))
    score = net.score(DataSet(x, y))

    def by_hand(params):
        h = params[0]["W"][x]
        z, state = net.layers[1].apply(params[1], net.state[1], h)
        logp = jax.nn.log_softmax(z @ params[2]["W"])
        ce = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0].sum(axis=1).mean()
        return ce + state[LOSS_TERM], state[LOSS_TERM]

    (want, term), want_grads = jax.value_and_grad(by_hand, has_aux=True)(net.params)
    assert float(term) > 0.5 * T * 0.9          # coef x positions x a term of about 1
    assert score == pytest.approx(float(want), rel=1e-5)
    before = jax.tree.map(np.asarray, net.params)
    net.fit_batch(DataSet(x, y))
    for new, old, g in zip(jax.tree.leaves(net.params), jax.tree.leaves(before),
                           jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(new, old - 0.1 * g, atol=2e-6)
    assert float(net.state[1][LOSS_TERM]) == pytest.approx(float(term), rel=1e-5)
    assert layer_loss_terms(net.state) == [net.state[1][LOSS_TERM]]


def test_a_graph_takes_a_layers_term_too():
    graph = ComputationGraph(
        NeuralNetConfiguration.builder().seed(3).updater(Sgd(lr=0.1)).data_type("float32")
        .graph_builder().add_inputs("in")
        .add_layer("emb", EmbeddingSequenceLayer(n_in=40, n_out=D), "in")
        .add_layer("moe", SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F, aux_coef=0.5), "emb")
        .add_layer("out", RnnOutputLayer(n_out=40, has_bias=False, loss="sparsemcxent"), "moe")
        .set_outputs("out").set_input_types(**{"in": InputType.recurrent(40, T)}).build()).init()
    rng = np.random.default_rng(0)
    x, y = (rng.integers(0, 40, (B, T)).astype(np.int32) for _ in range(2))
    with_term = graph.score(DataSet(x, y))
    graph.fit_batch(DataSet(x, y))
    term = float(graph.state["moe"][LOSS_TERM])
    assert term > 0.5 * T * 0.9 and with_term > term


def test_a_plain_dense_network_has_no_terms():
    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).updater(Sgd(lr=0.1)).list()
        .layer(DenseLayer(n_out=4)).layer(OutputLayer(n_out=2))
        .set_input_type(InputType.feed_forward(3)).build()).init()
    assert layer_loss_terms(net.state) == [] and net._moe_states() == {}


# -------------------------------------------------------------------- the gauges
def test_the_loads_are_gauges_with_monitoring_on_only(monitoring_off):
    monitoring = monitoring_off
    net = expert_net()
    rng = np.random.default_rng(1)
    sets = [DataSet(*(rng.integers(0, 40, (B, T)).astype(np.int32) for _ in range(2)))
            for _ in range(3)]
    args = (net.params, net.state, net.opt_state, jnp.asarray(0, jnp.int32),
            jnp.asarray(sets[0].features), jnp.asarray(sets[0].labels), jax.random.key(0), None)
    off = net._make_train_step().lower(*args).as_text()
    net.fit(sets)
    assert monitoring.registry().get("dl4j_train_moe_pairs_held") is None
    monitoring.enable()
    net.fit(sets)
    text = monitoring.metrics_text()
    stats = np.asarray(net.state[1]["moe_stats"])
    for name, want in zip(("load_max_over_mean", "pairs_held", "tokens_unserved"), stats):
        got = float(re.search(rf'dl4j_train_moe_{name}\{{layer="1"\}} (\S+)', text).group(1))
        assert got == pytest.approx(float(want), rel=1e-6)
    assert float(re.search(r"^dl4j_train_moe_aux_loss (\S+)", text, re.M).group(1)) == pytest.approx(
        float(net.state[1][LOSS_TERM]), rel=1e-6)
    assert stats[1] == B * T * K and stats[2] == 0 and 1.0 <= stats[0] <= E
    # the step's program is the same with monitoring on and off
    assert expert_net()._make_train_step().lower(*args).as_text() == off


# --------------------------------------------------------------------- the block
def block(**over):
    args = dict(d_model=D, n_heads=4, n_kv_heads=2, head_dim=8, rope_theta=5e5, norm="pre",
                qk_norm=True, window=4, mlp=SparseExpertsLayer(n_experts=E, top_k=K, d_expert=F,
                                                               experts_held=(2, 2)))
    return DecoderBlock(**{**args, **over})


REF_CFG = {"hidden_size": D, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
           "moe_intermediate_size": F, "num_experts": 2, "router_experts": E,
           "experts_held_first": 2, "num_experts_per_tok": K, "sliding_window": 4,
           "rms_norm_eps": 1e-6,
           "rope_parameters": {
               "sliding_attention": {"rope_type": "default", "rope_theta": 5e5},
               "full_attention": {"rope_type": "yarn", "rope_theta": 5e5, "factor": 16,
                                  "original_max_position_embeddings": 8, "beta_fast": 32,
                                  "beta_slow": 1, "attention_factor": YARN[4]}}}


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_block_matches_the_reference_block(kind):
    sliding = kind == "sliding_attention"
    layer = block(window=4 if sliding else None, rope_yarn=None if sliding else YARN)
    params, state = layer.init(jax.random.key(9), ITYPE)
    params = jax.tree.map(lambda p: p + 0.1 * jax.random.normal(jax.random.key(p.size), p.shape),
                          params)         # gains off 1, so that a forgotten norm shows
    x = jax.random.normal(jax.random.key(10), (B, T, D))
    rope = reference.rotary(T, 8, REF_CFG["rope_parameters"][kind])

    def program(params, x):
        y, new_state = layer.apply(params, state, x)
        return (y ** 2).sum() + new_state[LOSS_TERM], new_state

    def plain(params, x):
        y, aux, load = reference.block(x, params, REF_CFG, kind, rope)
        return (y ** 2).sum() + 0.001 * T * aux, load

    (got, new_state), got_grads = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(params, x)
    (want, load), want_grads = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(params, x)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(new_state["moe_stats"], load, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=1e-4)


def test_the_blocks_parameters_by_its_options():
    sandwich = DecoderBlock(d_model=D, n_heads=4, head_dim=8, d_ff=F)
    assert sorted(sandwich.init(jax.random.key(0), ITYPE)[0]) == [
        "Wd", "Wg", "Wk", "Wo", "Wq", "Wu", "Wv", "n1_g", "n2_g", "n3_g", "n4_g"]
    params, state = block().init(jax.random.key(0), ITYPE)
    assert sorted(params) == ["Wk", "Wo", "Wq", "Wv", "k_g", "mlp", "n1_g", "n3_g", "q_g"]
    assert params["Wk"].shape == (D, 16) and params["Wq"].shape == (D, 32)
    assert sorted(state) == [LOSS_TERM, "moe_stats"]
    again = DecoderBlock.from_dict(block(rope_yarn=YARN).to_dict())
    assert again == block(rope_yarn=YARN) and again.rope_yarn == YARN and again.mlp.held == (2, 2)
    with pytest.raises(ValueError, match="sandwich"):
        DecoderBlock(d_model=D, norm="post").init(jax.random.key(0), ITYPE)


def test_a_looped_decoder_saved_before_this_change_loads_and_gives_its_logits():
    """``Ouro``'s arguments and parameter tree are what they were: a tiny one
    saved by the parent commit (e480ce2) loads, and its logits are the ones
    that commit computed."""
    fixtures = ROOT / "tests" / "fixtures"
    model = MultiLayerNetwork.load(str(fixtures / "ouro_tiny_saved_by_pr33.zip"))
    fresh = Ouro(vocab_size=96, d_model=64, n_layers=2, n_heads=2, head_dim=32, d_ff=80,
                 ut_steps=3, dtype="float32", seed=5).init()
    assert model.conf.layers == fresh.conf.layers
    assert jax.tree.structure(model.params) == jax.tree.structure(fresh.params)
    x = np.random.default_rng(3).integers(0, 96, (4, 16)).astype(np.int32)
    np.testing.assert_allclose(model.output(x),
                               np.load(fixtures / "ouro_tiny_saved_by_pr33_logits.npy"), atol=1e-5)


# ----------------------------------------------------------------------- YaRN
@pytest.mark.parametrize("head_dim,original", [(128, 8192), (16, 8)])
def test_yarn_tables_against_the_formula(head_dim, original):
    theta, factor, fast, slow, attention = 5e5, 16.0, 32.0, 1.0, 1.2772588722239782

    def correction(turns):
        return head_dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = min(max(math.floor(correction(fast)), 0), head_dim - 1)
    high = min(max(math.ceil(correction(slow)), 0), head_dim - 1)
    i = np.arange(head_dim // 2, dtype=np.float64)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = (1 - m) / (factor * theta ** (2 * i / head_dim)) + m / theta ** (2 * i / head_dim)
    if head_dim == 128:
        assert (low, high) == (18, 35)     # 18.08 and 34.99 before the floor and the ceiling
        assert want[0] == 1.0 and want[-1] == pytest.approx(theta ** (-126 / 128) / 16)
    got = yarn_inv_freq(head_dim, theta, factor, original, fast, slow)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(reference.yarn_inv_freq(head_dim, {
        "rope_theta": theta, "factor": factor, "original_max_position_embeddings": original,
        "beta_fast": fast, "beta_slow": slow}), want, rtol=2e-6)
    cos, sin = rotary_tables(12, head_dim, theta, (factor, original, fast, slow, attention))
    angles = np.arange(12)[:, None] * np.concatenate([want, want])[None, :]
    np.testing.assert_allclose(cos, attention * np.cos(angles), atol=1e-5)
    np.testing.assert_allclose(sin, attention * np.sin(angles), atol=1e-5)
    plain_cos, _ = rotary_tables(12, head_dim, theta)
    np.testing.assert_allclose(plain_cos[:, 0], np.cos(np.arange(12)), atol=1e-6)


# ------------------------------------------------------------------------ the zoo
def tiny_mellum(**over):
    args = dict(vocab_size=96, d_model=D, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=8,
                n_experts=E, top_k=K, d_expert=F, experts_held=(2, 2), window=4, rope_yarn=YARN[:1]
                + (8,) + YARN[2:], dtype="float32")
    return Mellum2(**{**args, **over}).init()


def test_mellum2_builds_its_layers_by_type_and_trains_through_fit():
    from deeplearning4j_tpu.datasets.iterators import AsyncPrefetchIterator, ListDataSetIterator

    model = tiny_mellum()
    assert [type(l).__name__ for l in model.layers] == [
        "EmbeddingSequenceLayer", *["DecoderBlock"] * 4, "RMSNormLayer", "RnnOutputLayer"]
    assert [l.window for l in model.layers[1:5]] == [4, 4, 4, None]
    assert [l.rope_yarn is None for l in model.layers[1:5]] == [True, True, True, False]
    assert all(l.norm == "pre" and l.qk_norm and l.kv_heads == 2 and l.mlp.held == (2, 2)
               for l in model.layers[1:5])
    layer = D * 32 + 2 * D * 16 + 32 * D + 2 * D + 2 * 8 + D * E + 2 * 3 * D * F
    assert model.num_params() == 4 * layer + 2 * 96 * D + D
    rng = np.random.default_rng(2)
    sets = [DataSet(*(rng.integers(0, 96, (B, T)).astype(np.int32) for _ in range(2)))
            for _ in range(4)]
    first = model.score(sets[0])
    model.fit(AsyncPrefetchIterator(ListDataSetIterator(sets)), epochs=4)
    assert model.score(sets[0]) < first
    assert model._jit_cache["train"]._cache_size() == 1
    assert len(layer_loss_terms(model.state)) == 4
    assert model.output(sets[0].features).shape == (B, T, 96)
    published = Mellum2()
    assert (published.vocab_size, published.d_model, published.n_layers, published.n_heads,
            published.n_kv_heads, published.head_dim, published.n_experts, published.top_k,
            published.d_expert, published.window, published.rope_theta) == (
        98304, 2304, 28, 32, 4, 128, 64, 8, 896, 1024, 5e5)
    types = [("sliding" if l.window else "full") for l in published.conf().layers[1:29]]
    assert types == (["sliding"] * 3 + ["full"]) * 7
    # a configuration's own list wins over the published period
    given = Mellum2(n_layers=3, layer_types=["full_attention", "sliding_attention", "full_attention"])
    assert [l.window for l in given.conf().layers[1:4]] == [None, 1024, None]
    with pytest.raises(ValueError, match="layer_types"):
        Mellum2(n_layers=2, layer_types=["full_attention", "dense"]).conf()


def test_mellum2_json_and_save_load_round_trips(tmp_path):
    model = tiny_mellum()
    conf = MultiLayerConfiguration.from_json(model.conf.to_json())
    assert conf.layers == model.conf.layers and conf.remat and conf.to_json() == model.conf.to_json()
    x = np.random.default_rng(4).integers(0, 96, (B, T)).astype(np.int32)
    model.fit_batch(DataSet(x, x))
    model.save(str(tmp_path / "m.zip"))
    again = MultiLayerNetwork.load(str(tmp_path / "m.zip"))
    assert again.conf.layers == model.conf.layers
    np.testing.assert_allclose(again.output(x), model.output(x), atol=1e-6)
    np.testing.assert_allclose(again.state[1]["moe_stats"], model.state[1]["moe_stats"])
