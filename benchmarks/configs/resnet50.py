"""ResNet-50: the plain float32 reference and the analytic operation counts.

Imports nothing of the program. The parameter tree is keyed like the zoo
model's vertices (``conv1``, ``bn1``, ``s0b0_conva`` ...), so the driver can
hand the same seeded weights to the program and compare leaf by leaf; the
names are the only thing the two share.

Follows He et al. 2015, Table 1 (50-layer column): 7x7/2 stem, 3x3/2 max
pool, bottleneck stages [3, 4, 6, 3] of widths 64..512 (x4), BatchNorm after
every convolution, global average pool, 1000-way softmax. Departures are
listed in ``resnet50.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference_train import precision_policy

HIGHEST = lax.Precision.HIGHEST


# ----------------------------------------------------------------- the shape
def conv_table(cfg: dict) -> list:
    """Every convolution as (name, kernel, c_in, c_out, stride, h_in)."""
    size, widths, blocks = cfg["image_size"], cfg["stage_widths"], cfg["stage_blocks"]
    exp = cfg["expansion"]
    rows = [("conv1", 7, cfg["channels"], cfg["stem_width"], 2, size)]
    h = -(-size // 2)          # stem, SAME padding
    h = -(-h // 2)             # max pool
    c_in = cfg["stem_width"]
    for si, (w, n) in enumerate(zip(widths, blocks)):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            rows.append((f"{name}_conva", 1, c_in, w, stride, h))
            h_out = -(-h // stride)
            rows.append((f"{name}_convb", 3, w, w, 1, h_out))
            rows.append((f"{name}_convc", 1, w, w * exp, 1, h_out))
            if bi == 0:
                rows.append((f"{name}_proj", 1, c_in, w * exp, stride, h))
            c_in, h = w * exp, h_out
    return rows


def bn_after(conv_name: str) -> str:
    if conv_name == "conv1":
        return "bn1"
    if conv_name.endswith("_proj"):
        return conv_name + "bn"
    return conv_name.replace("_conv", "_bn")


# ------------------------------------------------------------------- weights
def make_params(key, cfg: dict):
    """(params, state) in float32 from one key. He-normal convolutions,
    BatchNorm gamma 1 (``residual_last_gain`` for a block's last) / beta 0 /
    mean 0 / var 1, head normal / sqrt(fan_in)."""
    rows = conv_table(cfg)
    keys = jax.random.split(key, len(rows) + 1)
    params, state = {}, {}
    for k, (name, ksz, cin, cout, _, _) in zip(keys, rows):
        std = (2.0 / (ksz * ksz * cin)) ** 0.5
        params[name] = {"W": std * jax.random.normal(k, (ksz, ksz, cin, cout), jnp.float32)}
        # the last BatchNorm of a residual block starts with a small gain:
        # small enough that 16 random blocks do not amplify rounding into the
        # first stages' gradients, and not 0, so that every leaf has a first
        # gradient and is compared from step 1 (resnet50.json, ``assumed``)
        gain = cfg["residual_last_gain"] if name.endswith("_convc") else 1.0
        params[bn_after(name)] = {"gamma": jnp.full((cout,), gain, jnp.float32),
                                  "beta": jnp.zeros((cout,), jnp.float32)}
        state[bn_after(name)] = {"mean": jnp.zeros((cout,), jnp.float32),
                                 "var": jnp.ones((cout,), jnp.float32)}
    feat = cfg["stage_widths"][-1] * cfg["expansion"]
    params["output"] = {
        "W": jax.random.normal(keys[-1], (feat, cfg["num_classes"]), jnp.float32) / feat ** 0.5,
        "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return params, state


# ------------------------------------------------------------------- forward
def _conv_bn(product, qa, x, w, bn_p, bn_s, stride, eps, decay):
    """Convolution (SAME padding, no bias) then training-mode BatchNorm.
    Returns the activation and the running statistics carried forward."""
    y = product(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST))(x, w)
    mean = y.mean((0, 1, 2))
    var = jnp.maximum((y * y).mean((0, 1, 2)) - mean * mean, 0.0)
    carried = {"mean": decay * bn_s["mean"] + (1 - decay) * mean,
               "var": decay * bn_s["var"] + (1 - decay) * var}
    return qa((y - mean) * lax.rsqrt(var + eps) * bn_p["gamma"] + bn_p["beta"]), carried


def loss_fn(params, state, features, labels, cfg: dict, precision: str = "float32"):
    """Mean softmax cross-entropy of a training-mode forward pass, and the
    BatchNorm state it carries forward. ``precision`` is ``float32`` (matrix
    units at ``highest``) or a lower one for a control, see
    ``reference_train.precision_policy``."""
    cast, product, qa = precision_policy(precision)
    params, features = cast(params), cast(features.astype(jnp.float32))
    eps, decay = cfg["batch_norm"]["eps"], cfg["batch_norm"]["decay"]

    def cb(x, p, s, name, stride, carried):
        bn = bn_after(name)
        y, carried[bn] = _conv_bn(product, qa, x, p[name]["W"], p[bn], s[bn], stride, eps, decay)
        return y

    new_state = {}
    x = jax.nn.relu(cb(features, params, state, "conv1", 2, new_state))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, n in enumerate(cfg["stage_blocks"]):
        for bi in range(n):
            name, stride = f"s{si}b{bi}", 2 if (bi == 0 and si > 0) else 1
            mine = [k for k in params if k.startswith(name + "_")]

            # only a block's input is kept for the backward pass: the float32
            # activations of a whole batch would not fit beside each other
            @jax.checkpoint
            def block(x, p, s, name=name, stride=stride, project=(bi == 0)):
                carried = {}
                y = jax.nn.relu(cb(x, p, s, f"{name}_conva", stride, carried))
                y = jax.nn.relu(cb(y, p, s, f"{name}_convb", 1, carried))
                y = cb(y, p, s, f"{name}_convc", 1, carried)
                short = cb(x, p, s, f"{name}_proj", stride, carried) if project else x
                return qa(jax.nn.relu(y + short)), carried

            x, carried = block(x, {k: params[k] for k in mine},
                               {k: state[k] for k in mine if k in state})
            new_state.update(carried)
    pooled = x.mean((1, 2))
    head = params["output"]
    logits = product(lambda a, b: jnp.dot(a, b, precision=HIGHEST))(pooled, head["W"]) + head["b"]
    loss = -(labels * jax.nn.log_softmax(logits.astype(jnp.float32))).sum(-1).mean()
    return loss, new_state


# ---------------------------------------------------------- analytic counts
def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """Forward + backward operations one sample needs: 3 x 2 x the multiply-
    accumulates of the convolutions and the head (backward is two products
    per forward product). Recomputation and the optimizer do not count."""
    return 3 * 2 * forward_macs_per_sample(cfg)


def forward_macs_per_sample(cfg: dict) -> float:
    macs = 0
    for _, ksz, cin, cout, stride, h in conv_table(cfg):
        h_out = -(-h // stride)
        macs += h_out * h_out * ksz * ksz * cin * cout
    macs += cfg["stage_widths"][-1] * cfg["expansion"] * cfg["num_classes"]
    return float(macs)
