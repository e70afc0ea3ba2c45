"""Plain reference: a pre-LayerNorm causal transformer decoder with ReLU
feed-forward and tied input/output embedding, the block of OPT (Zhang et
al., arXiv:2205.01068, section 2; Vaswani et al., arXiv:1706.03762 for the
attention and the sinusoidal positions), in straightforward float32
`jax.numpy` at `precision=highest`. One full forward over a whole
sequence: no cache, no packing, no batching, no kernels. Imports nothing
of the program.

Departures from OPT, which the configuration file lists under `assumed`
because the program's `TransformerDecoder` computes this and not OPT:
sinusoidal positions (first half sines, second half cosines, frequencies
10000^(-i/half)) where OPT learns a table; no bias on the four attention
projections; float32 weights.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5


def leaf_shapes(cfg: dict) -> Dict[str, tuple]:
    d, f = cfg["hidden_size"], cfg["ffn_dim"]
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "w1": (d, f), "w2": (f, d)}


def init_weights(key, cfg: dict):
    """The cell's weights from a key, float32, normal(0, init_std) for
    matrices and the embedding, ones and zeros for LayerNorm and biases.
    One call, meant to be jitted whole; the tree is the decoder's."""
    d, f, std = cfg["hidden_size"], cfg["ffn_dim"], cfg["init_std"]
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * std
    ones, zeros = jnp.ones((d,), jnp.float32), jnp.zeros((d,), jnp.float32)
    layers = []
    for li in range(cfg["num_hidden_layers"]):
        lk = jax.random.fold_in(key, li + 1)
        lp = {name: n(jax.random.fold_in(lk, j), shape)
              for j, (name, shape) in enumerate(leaf_shapes(cfg).items())}
        lp.update(ln1_s=ones, ln1_b=zeros, ln2_s=ones, ln2_b=zeros,
                  b1=jnp.zeros((f,), jnp.float32), b2=zeros)
        layers.append(lp)
    return {"emb": n(jax.random.fold_in(key, 0), (cfg["vocab_size"], d)),
            "lnf_s": ones, "lnf_b": zeros, "layers": layers}


def _ln(x, s, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * s + b


def _sinusoid(t: int, d: int):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = jnp.arange(t)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _lowp(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def forward(params, tokens, heads: int, lowp=None):
    """tokens [T] -> logits [T, vocab]; row t chooses token t+1. `lowp`
    is the control: every matrix operand, the residual stream and the
    logits rounded to that type."""
    t = tokens.shape[0]
    d = params["emb"].shape[1]
    dh = d // heads
    mm = lambda a, b: jnp.dot(_lowp(a, lowp), _lowp(b, lowp), precision=HI)
    x = params["emb"][tokens] + _sinusoid(t, d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lp in params["layers"]:
        h = _ln(x, lp["ln1_s"], lp["ln1_b"])
        q = mm(h, lp["wq"]).reshape(t, heads, dh)
        k = mm(h, lp["wk"]).reshape(t, heads, dh)
        v = mm(h, lp["wv"]).reshape(t, heads, dh)
        s = jnp.einsum("qhd,khd->hqk", _lowp(q, lowp), _lowp(k, lowp),
                       precision=HI) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        a = jnp.einsum("hqk,khd->qhd", _lowp(p, lowp), _lowp(v, lowp),
                       precision=HI).reshape(t, d)
        x = _lowp(x + mm(a, lp["wo"]), lowp)
        h = _ln(x, lp["ln2_s"], lp["ln2_b"])
        x = _lowp(x + mm(jax.nn.relu(mm(h, lp["w1"]) + lp["b1"]), lp["w2"])
                  + lp["b2"], lowp)
    x = _ln(x, params["lnf_s"], params["lnf_b"])
    return _lowp(mm(x, params["emb"].T), lowp)


def served_gaps(params, heads: int, sequences: List[tuple], pad_to: int,
                lowp=None):
    """For each (prompt, served tokens): the reference's logits over the
    whole sequence, once. Returns per sequence the gaps by which each
    served token's logit lies below the reference's best and, when `lowp`
    is given, the gaps of the token the lower precision puts first at the
    same positions. Sequences are padded to `pad_to` on the right (a
    causal model never reads what follows), so one program serves all."""
    fwd = jax.jit(lambda p, tok: forward(p, tok, heads))
    low = None
    if lowp is not None:
        lowp = jnp.dtype(lowp)
        low = jax.jit(lambda p, tok: forward(p, tok, heads, lowp))
    out = []
    for prompt, served in sequences:
        seq = list(prompt) + list(served)
        tok = jnp.zeros((pad_to,), jnp.int32).at[:len(seq)].set(
            jnp.asarray(seq, jnp.int32))
        rows = slice(len(prompt) - 1, len(seq) - 1)
        ref = fwd(params, tok)[rows]
        best = ref.max(-1)
        served_gap = best - jnp.take_along_axis(
            ref, jnp.asarray(served, jnp.int32)[:, None], -1)[:, 0]
        ctrl_gap = None
        if low is not None:
            pick = low(params, tok)[rows].argmax(-1)
            ctrl_gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        out.append((jax.device_get(served_gap),
                    None if ctrl_gap is None else jax.device_get(ctrl_gap)))
    return out
