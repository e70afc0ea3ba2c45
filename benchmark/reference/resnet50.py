"""Plain reference: ResNet-50 (He et al., arXiv:1512.03385, Table 1,
50-layer column) as dl4j-zoo's `ResNet50.java` builds it, and three steps
of its training, in straightforward float32 `jax.numpy`.

Written from the paper and the zoo model's published description; imports
nothing of the program. Departures of the zoo model from the paper, kept
because the configuration is the zoo's:
  * the first bottleneck of stage 2 strides by 2 (the paper's does not),
    so stages 2..5 run at 28, 14, 7 and 4 pixels for a 224 input;
  * the stride of a down-sampling block sits on its first 1x1 convolution
    and on the projection shortcut (ResNet v1, as published);
  * the stem max-pool is 3x3/2 without padding (112 -> 55);
  * every convolution has a bias; weights are drawn normal(0, 0.5);
  * loss = mean negative log-likelihood + l1 1e-7 * sum|W| +
    0.5 * l2 5e-5 * sum W^2 over convolution and dense weights (no
    penalty on biases, gamma, beta);
  * RmsProp: g2 <- 0.96 g2 + 0.04 g^2 ; p <- p - lr g / (sqrt(g2) + 1e-3).
BatchNorm (Ioffe & Szegedy, arXiv:1502.03167): batch mean and biased
variance over N, H, W; eps 1e-5; running <- 0.9 running + 0.1 batch.

All matrix work runs at `precision=highest`: on a TPU a float32
convolution otherwise runs as bf16 passes.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

STAGES = (("2", (64, 64, 256), "abc"), ("3", (128, 128, 512), "abcd"),
          ("4", (256, 256, 1024), "abcdef"), ("5", (512, 512, 2048), "abc"))
BN_EPS, BN_DECAY = 1e-5, 0.9
L1, L2 = 1e-7, 5e-5
RMS_DECAY, RMS_EPS = 0.96, 1e-3
INIT_STD = 0.5
HI = lax.Precision.HIGHEST


def conv_specs(image=(224, 224, 3), labels: int = 1000) -> List[dict]:
    """Every convolution (and the dense head, as a 1x1 on a 1x1 map) with
    its shapes: name, kernel, cin, cout, stride, in_hw, out_hw, pad."""
    h, w, c = image
    specs = []

    def add(name, k, cin, cout, stride, hw, pad):
        if pad == "same":
            out = (-(-hw[0] // stride), -(-hw[1] // stride))
        else:
            out = ((hw[0] - k) // stride + 1, (hw[1] - k) // stride + 1)
        specs.append(dict(name=name, k=k, cin=cin, cout=cout, stride=stride,
                          in_hw=hw, out_hw=out, pad=pad))
        return out

    hw = add("stem-cnn1", 7, c, 64, 2, (h + 6, w + 6), "valid")
    hw = ((hw[0] - 3) // 2 + 1, (hw[1] - 3) // 2 + 1)      # max-pool 3x3/2
    cin = 64
    for stage, (f1, f2, f3), blocks in STAGES:
        for b in blocks:
            base = f"res{stage}{b}_branch"
            stride = 2 if b == "a" else 1
            if b == "a":
                add(base + "1", 1, cin, f3, stride, hw, "valid")
            mid = add(base + "2a", 1, cin, f1, stride, hw, "valid")
            add(base + "2b", 3, f1, f2, 1, mid, "same")
            add(base + "2c", 1, f2, f3, 1, mid, "valid")
            hw, cin = mid, f3
    specs.append(dict(name="output", k=1, cin=cin, cout=labels, stride=1,
                      in_hw=(1, 1), out_hw=(1, 1), pad="valid", dense=True))
    return specs


def bn_names() -> List[Tuple[str, int]]:
    out = [("bnstem1", 64)]
    for stage, (f1, f2, f3), blocks in STAGES:
        for b in blocks:
            base = f"bn{stage}{b}_branch"
            if b == "a":
                out.append((base + "1", f3))
            out += [(base + "2a", f1), (base + "2b", f2), (base + "2c", f3)]
    return out


def init_weights(key, image=(224, 224, 3), labels: int = 1000,
                 dtype=jnp.bfloat16) -> Dict[str, Dict[str, jax.Array]]:
    """The cell's initial weights from a key: normal(0, 0.5) weights, zero
    biases, gamma 1, beta 0, in the type the configuration serves them.
    One call, meant to be jitted whole. Layer names are the zoo model's."""
    p = {}
    for i, s in enumerate(conv_specs(image, labels)):
        shape = ((s["cin"], s["cout"]) if s.get("dense")
                 else (s["k"], s["k"], s["cin"], s["cout"]))
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * INIT_STD
        p[s["name"]] = {"W": w.astype(dtype),
                        "b": jnp.zeros((s["cout"],), dtype)}
    for name, n in bn_names():
        p[name] = {"gamma": jnp.ones((n,), dtype),
                   "beta": jnp.zeros((n,), dtype)}
    return p


def init_state() -> Dict[str, Dict[str, jax.Array]]:
    return {name: {"mean": jnp.zeros((n,), jnp.float32),
                   "var": jnp.ones((n,), jnp.float32)}
            for name, n in bn_names()}


def _round(x, dtype):
    """The control's lower precision: the operands of every matrix
    product, and on the way back their cotangents, cast to `dtype` and
    back (products still accumulate in float32). A plain cast, as a PR
    that swapped the type would write it: in float8 it flushes most
    cotangents to zero, which is what makes the control fail. With the
    cotangents kept in float32 the numbers compared - gaps of norms and a
    mean loss - move only in second order (PERF.md section 6, PR 24)."""
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _conv(x, p, stride, pad, lowp):
    y = lax.conv_general_dilated(
        _round(x, lowp), _round(p["W"], lowp), (stride, stride), pad.upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    return y + p["b"]


def _bn(x, p, st):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]
    new = {"mean": BN_DECAY * st["mean"] + (1 - BN_DECAY) * mean,
           "var": BN_DECAY * st["var"] + (1 - BN_DECAY) * var}
    return y, new


def _block(params, state, x, stage, b, lowp):
    base, bn = f"res{stage}{b}_branch", f"bn{stage}{b}_branch"
    stride = 2 if b == "a" else 1
    new = {}
    y = _conv(x, params[base + "2a"], stride, "valid", lowp)
    y, new[bn + "2a"] = _bn(y, params[bn + "2a"], state[bn + "2a"])
    y = _conv(jax.nn.relu(y), params[base + "2b"], 1, "same", lowp)
    y, new[bn + "2b"] = _bn(y, params[bn + "2b"], state[bn + "2b"])
    y = _conv(jax.nn.relu(y), params[base + "2c"], 1, "valid", lowp)
    y, new[bn + "2c"] = _bn(y, params[bn + "2c"], state[bn + "2c"])
    if b == "a":
        x = _conv(x, params[base + "1"], stride, "valid", lowp)
        x, new[bn + "1"] = _bn(x, params[bn + "1"], state[bn + "1"])
    return jax.nn.relu(y + x), new


def _stem(params, state, x, lowp):
    x = jnp.pad(x, ((0, 0), (3, 3), (3, 3), (0, 0)))
    y = _conv(x, params["stem-cnn1"], 2, "valid", lowp)
    y, new = _bn(y, params["bnstem1"], state["bnstem1"])
    y = lax.reduce_window(jax.nn.relu(y), -jnp.inf, lax.max,
                          (1, 3, 3, 1), (1, 2, 2, 1), "VALID")
    return y, {"bnstem1": new}


def loss_fn(params, state, x, y, lowp=None):
    """(loss, new BatchNorm state). Each block is rematerialised in the
    backward pass so that 256 rows of float32 fit beside nothing else."""
    new_state = {}
    h, st = jax.checkpoint(lambda p, s, a: _stem(p, s, a, lowp))(
        params, state, x)
    new_state.update(st)
    for stage, _, blocks in STAGES:
        for b in blocks:
            h, st = jax.checkpoint(
                lambda p, s, a, stage=stage, b=b: _block(p, s, a, stage, b,
                                                         lowp))(
                params, state, h)
            new_state.update(st)
    h = jnp.mean(h, (1, 2))
    out = params["output"]
    z = jnp.dot(_round(h, lowp), _round(out["W"], lowp), precision=HI) \
        + out["b"]
    nll = -jnp.mean(jnp.sum(y * jax.nn.log_softmax(z), axis=-1))
    reg = 0.0
    for p in params.values():
        if "W" in p:
            reg = reg + L1 * jnp.sum(jnp.abs(p["W"])) \
                + 0.5 * L2 * jnp.sum(jnp.square(p["W"]))
    return nll + reg, new_state


def train_step(params, g2, state, x, y, lr, lowp=None):
    """One RmsProp step. Returns (params, g2, state, loss, grads)."""
    (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, state, x, y, lowp)
    g2 = jax.tree_util.tree_map(
        lambda s, g: RMS_DECAY * s + (1 - RMS_DECAY) * g * g, g2, grads)
    params = jax.tree_util.tree_map(
        lambda p, g, s: p - lr * g / (jnp.sqrt(s) + RMS_EPS),
        params, grads, g2)
    return params, g2, new_state, loss, grads


_STEP = jax.jit(train_step, static_argnames=("lowp",), donate_argnums=(1,))


def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {f"{layer}/{leaf}": jnp.sqrt(jnp.sum(jnp.square(
                v.astype(jnp.float32))))
            for layer, d in sorted(tree.items())
            for leaf, v in sorted(d.items())}


def follow(params0, batches, lr, lowp=None, rows: Optional[slice] = None,
           store=None):
    """Follow the first len(batches) steps from `params0` (any float
    type; taken to float32). Returns per-step losses, per-leaf norms of
    the first gradient, per-leaf norms of the parameters' change, and
    per-leaf norms of the change of BatchNorm's running mean and variance.
    `lowp` and `rows` are for the control and the planted faults; `store`
    keeps the parameters and the RmsProp state in that type between steps
    (the builder's look at what bfloat16 storage alone does to a leaf)."""
    p0 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params0)
    g2 = jax.tree_util.tree_map(jnp.zeros_like, p0)
    params, state, losses, grad1 = p0, init_state(), [], None
    for i, (x, y) in enumerate(batches):
        if rows is not None:
            x, y = x[rows], y[rows]
        params, g2, state, loss, grads = _STEP(
            params, g2, state, jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.float32), lr, lowp=lowp)
        if store is not None:
            params, g2 = jax.tree_util.tree_map(
                lambda a: _round(a, store), (params, g2))
        losses.append(loss)
        if i == 0:
            grad1 = jax.jit(leaf_norms)(grads)
        del grads
    moved = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
        lambda u, v: u - v, a, b)))
    as_floats = lambda d: {k: float(v) for k, v in d.items()}
    return ([float(v) for v in losses], as_floats(grad1),
            as_floats(moved(params, p0)),
            as_floats(moved(state, init_state())))
