"""BERT-base encoder with a sequence-classification head: the plain float32
reference and the analytic operation counts.

Imports nothing of the program. The parameter tree is a list in the order of
the zoo model's layers (token embedding, positions, LayerNorm, the encoder
blocks, final LayerNorm, pooling (no parameters), head), each a dict keyed as
the program keys it, so the driver can hand the same seeded weights to the
program and compare leaf by leaf.

Widths are those of ``google-bert/bert-base-uncased``; the departures the zoo
model has from the published architecture are listed in ``bert_base.json``
and reproduced here, since the reference has to state what the program is
meant to compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference_train import precision_policy

HIGHEST = lax.Precision.HIGHEST
MATRICES = ("Wq", "Wk", "Wv", "Wo", "W1", "W2")


def make_params(key, cfg: dict):
    """(params, state) in float32 from one key."""
    d, ff, std = cfg["hidden_size"], cfg["intermediate_size"], cfg["initializer_range"]
    n = cfg["num_hidden_layers"]
    keys = iter(jax.random.split(key, 3 + 6 * n))

    def normal(shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def ln():
        return {"gamma": jnp.ones((d,), jnp.float32), "beta": jnp.zeros((d,), jnp.float32)}

    params = [{"W": normal((cfg["vocab_size"], d))},
              {"P": normal((cfg["max_position_embeddings"], d))}, ln()]
    for _ in range(n):
        shapes = {"Wq": (d, d), "Wk": (d, d), "Wv": (d, d), "Wo": (d, d),
                  "W1": (d, ff), "W2": (ff, d)}
        block = {k: normal(s) for k, s in shapes.items()}
        for b, size in (("bq", d), ("bk", d), ("bv", d), ("bo", d), ("b1", ff), ("b2", d)):
            block[b] = jnp.zeros((size,), jnp.float32)
        for g in ("ln1_g", "ln2_g"):
            block[g] = jnp.ones((d,), jnp.float32)
        for b in ("ln1_b", "ln2_b"):
            block[b] = jnp.zeros((d,), jnp.float32)
        params.append(block)
    params += [ln(), {}, {"W": normal((d, cfg["num_labels"])),
                          "b": jnp.zeros((cfg["num_labels"],), jnp.float32)}]
    return params, [{} for _ in params]


def _layer_norm(x, gamma, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _block(x, p, heads: int, eps: float, product, qa):
    """Pre-norm encoder block: x + MHA(LN(x)), then x + MLP(LN(x))."""
    def mm(a, w):
        return product(lambda a, w: jnp.dot(a, w, precision=HIGHEST))(a, w)

    b, t, d = x.shape
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)

    def split(a):
        return a.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)

    qh, kh, vh = (split(mm(h, p[w]) + p[c]) for w, c in
                  (("Wq", "bq"), ("Wk", "bk"), ("Wv", "bv")))
    scores = product(lambda a, b: jnp.einsum("bntd,bnsd->bnts", a, b, precision=HIGHEST))(
        qh, kh) / (d // heads) ** 0.5
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = product(lambda a, b: jnp.einsum("bnts,bnsd->bntd", a, b, precision=HIGHEST))(probs, vh)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = qa(x + mm(ctx, p["Wo"]) + p["bo"])
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    m = jax.nn.gelu(mm(h, p["W1"]) + p["b1"], approximate=True)
    return qa(x + mm(m, p["W2"]) + p["b2"])


def loss_fn(params, state, features, labels, cfg: dict, precision: str = "float32"):
    """Mean softmax cross-entropy of the two-class head over the batch.
    ``precision`` is ``float32`` (matrix units at ``highest``) or a lower one
    for a control, see ``reference_train.precision_policy``."""
    cast, product, qa = precision_policy(precision)
    params = cast(params)
    n, eps = cfg["num_hidden_layers"], cfg["layer_norm_eps"]
    tokens = features.astype(jnp.int32)
    x = params[0]["W"][tokens] + params[1]["P"][: tokens.shape[1]]
    x = qa(_layer_norm(x, params[2]["gamma"], params[2]["beta"], eps))
    # only a block's input is kept for the backward pass, so that float32
    # scores of the whole batch are live for one block at a time
    block = jax.checkpoint(lambda x, p: _block(x, p, cfg["num_attention_heads"], eps, product, qa))
    for p in params[3:3 + n]:
        x = block(x, p)
    x = _layer_norm(x, params[3 + n]["gamma"], params[3 + n]["beta"], eps)
    head = params[5 + n]
    logits = product(lambda a, b: jnp.dot(a, b, precision=HIGHEST))(x.mean(1), head["W"]) + head["b"]
    loss = -(labels * jax.nn.log_softmax(logits.astype(jnp.float32))).sum(-1).mean()
    return loss, state


# ---------------------------------------------------------- analytic counts
def _macs_per_token_per_layer(cfg: dict, seq: int) -> float:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 2 * d * ff + 2 * seq * d      # projections, MLP, QK^T and PV


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs: 3 x 2 x the multiply-
    accumulates of the matrix products (the embedding gather has none).
    Recomputation and the optimizer do not count."""
    seq = traffic["seq"]
    macs = seq * cfg["num_hidden_layers"] * _macs_per_token_per_layer(cfg, seq)
    macs += cfg["hidden_size"] * cfg["num_labels"]
    return 3 * 2 * float(macs)
