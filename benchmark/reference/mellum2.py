"""Plain reference: the decoder block of Mellum2-12B-A2.5B-Instruct
(JetBrains; `model_type` `mellum`; the layer's six equations are written
out in the configuration's issue and below), in straightforward float32
`jax.numpy` at `precision=highest`. One full forward over a whole
sequence: no cache, no chunks, no packing, no kernels, no grouping of
tokens by expert (each held expert is applied to every token under its
routing weight, which is zero where the token did not choose it).
Imports nothing of the program.

    h = rms(x; g1);  q, k, v = h Wq, h Wk, h Wv  (32, 4, 4 heads of 128)
    rotary on q and k, rotate-half; per kind of layer its own table:
      sliding: theta 500000, plain;  full: YaRN (factor 16 over 8,192,
      beta 32 and 1), cos and sin times attention_factor
    head n reads KV head n // 8; position i sees j <= i, and on a sliding
      layer also i - j < 1024; softmax in float32;  x += concat(heads) Wo
    h2 = rms(x; g2);  p = softmax(h2 Wr) over all 64;  E = top 8 (ties to
      the lower index);  w_e = p_e / sum_E p;  x += sum_E w_e
      (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
    logits = rms(x; gf) W_head

The weights are the bfloat16 values the program holds, cast up. Eight
layers in float32 are 15 GB, so a layer is made and held at a time, from
`fold_in(key, layer)` as the builder makes it, and every sequence goes
through it before the next is made; attention runs in blocks of queries.
`experts_held` (configuration key; default all) gives the reference the
same share of the experts as the program. `lowp` is the control: every
matrix operand, the residual stream and the logits rounded to that type.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
Q_BLOCK = 512           # queries a block of the reference's attention
PAD_TO = 1024           # sequences are padded to a multiple (few programs)
OUTER = 1 << 20         # fold-in numbers of the embedding and the head


def layer_kinds(cfg: dict) -> List[str]:
    """`full` or `sliding` for each layer that is run."""
    return [t.split("_")[0]
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", range(cfg["num_experts"])))


def leaf_shapes(cfg: dict) -> Dict[str, tuple]:
    d, f, dh = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    n = len(held(cfg))
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "wr": (d, cfg["num_experts"]), "wg": (n, d, f), "wu": (n, d, f),
            "wd": (n, f, d)}


def _draw(key, shape, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["init_std"]).astype(jnp.bfloat16)


def init_layer(key, layer, cfg: dict):
    """Layer `layer`'s weights from `fold_in(key, layer)`: bfloat16,
    normal(0, init_std) matrices (a held expert's from its own id, so a
    share holds the same values as the whole), norms at one."""
    lk = jax.random.fold_in(key, layer)
    ones = jnp.ones((cfg["hidden_size"],), jnp.bfloat16)
    lp = {"ln1_s": ones, "ln2_s": ones}
    ids = jnp.asarray(held(cfg))
    for j, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(lk, j)
        if name in ("wg", "wu", "wd"):
            lp[name] = jax.vmap(lambda e: _draw(jax.random.fold_in(k, e),
                                                shape[1:], cfg))(ids)
        else:
            lp[name] = _draw(k, shape, cfg)
    return lp


def init_outer(key, cfg: dict):
    """The embedding, the untied head and the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"emb": _draw(jax.random.fold_in(key, OUTER), (v, d), cfg),
            "head": _draw(jax.random.fold_in(key, OUTER + 1), (d, v), cfg),
            "lnf_s": jnp.ones((d,), jnp.bfloat16)}


def rope_table(cfg: dict, kind: str):
    """(inv_freq float32 [head_dim / 2], factor on cos and sin) of a kind
    of layer, from the published `rope_parameters`."""
    rp = cfg["rope_parameters"][kind + "_attention"]
    dim, theta = cfg["head_dim"], float(rp["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / dim)
    if rp["rope_type"] == "default":
        return inv, 1.0
    s, l0 = float(rp["factor"]), rp["original_max_position_embeddings"]
    turn = lambda b: dim * math.log(l0 / (2 * math.pi * b)) \
        / (2 * math.log(theta))
    low = max(math.floor(turn(rp["beta_fast"])), 0)
    high = min(math.ceil(turn(rp["beta_slow"])), dim - 1)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return inv * (1 - r) + inv / s * r, float(rp["attention_factor"])


def _rope(a, table):
    """a [t, heads, head_dim] at positions 0..t-1, rotate-half."""
    inv, factor = table
    ang = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * factor
    lo, hi = jnp.split(a, 2, -1)
    return a * cos + jnp.concatenate([-hi, lo], -1) * sin


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _lowp(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def layer_forward(x, lp, cfg: dict, kind: str, lowp=None):
    """One layer on x [t, hidden], float32."""
    t = x.shape[0]
    hh, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, top = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    w = {k: v.astype(jnp.float32) for k, v in lp.items()}
    mm = lambda a, b: jnp.dot(_lowp(a, lowp), _lowp(b, lowp), precision=HI)
    table = rope_table(cfg, kind)
    h = _rms(x, w["ln1_s"], eps)
    q = _rope(mm(h, w["wq"]).reshape(t, hh, dh), table)
    k = _rope(mm(h, w["wk"]).reshape(t, kvh, dh), table)
    v = mm(h, w["wv"]).reshape(t, kvh, dh)
    k, v = (jnp.repeat(a, hh // kvh, axis=1) for a in (k, v))
    at = jnp.arange(t)
    blocks = []
    for lo in range(0, t, Q_BLOCK):
        i = at[lo:lo + Q_BLOCK, None]
        see = at[None, :] <= i
        if kind == "sliding":
            see &= i - at[None, :] < cfg["sliding_window"]
        s = jnp.einsum("qhd,khd->hqk", _lowp(q[lo:lo + Q_BLOCK], lowp),
                       _lowp(k, lowp), precision=HI) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), -1)
        blocks.append(jnp.einsum("hqk,khd->qhd", _lowp(p, lowp),
                                 _lowp(v, lowp), precision=HI))
    a = jnp.concatenate(blocks).reshape(t, hh * dh)
    x = _lowp(x + mm(a, w["wo"]), lowp)
    h = _rms(x, w["ln2_s"], eps)
    p = jax.nn.softmax(jnp.dot(h, w["wr"], precision=HI), -1)
    best, idx = lax.top_k(p, top)               # ties to the lower index
    best = best / jnp.sum(best, -1, keepdims=True)
    ids = jnp.asarray(held(cfg))

    def one(y, e):          # held expert e on every token, under its weight
        we = jnp.sum(jnp.where(idx == ids[e], best, 0.0), -1)
        out = mm(jax.nn.silu(mm(h, w["wg"][e])) * mm(h, w["wu"][e]),
                 w["wd"][e])
        return y + we[:, None] * out, None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(len(held(cfg))))
    return _lowp(x + y, lowp)


def logits_of(outer, x, cfg: dict, lowp=None):
    h = _rms(x, outer["lnf_s"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _lowp(jnp.dot(_lowp(h, lowp),
                         _lowp(outer["head"].astype(jnp.float32), lowp),
                         precision=HI), lowp)


def forward_all(key, cfg: dict, tokens: List[jax.Array], rows: List[slice],
                lowp=None) -> List[jax.Array]:
    """The logits of each sequence's `rows`, float32. Layer by layer: one
    layer's weights exist at a time, and every sequence passes them."""
    lowp = None if lowp is None else jnp.dtype(lowp)
    outer = jax.jit(lambda k: init_outer(k, cfg))(key)
    make = jax.jit(lambda k, li: init_layer(k, li, cfg))
    xs = [outer["emb"][t].astype(jnp.float32) for t in tokens]
    fwd = {kind: jax.jit(lambda x, lp, kind=kind: layer_forward(
        x, lp, cfg, kind, lowp)) for kind in ("full", "sliding")}
    for li, kind in enumerate(layer_kinds(cfg)):
        lp = make(key, li)
        xs = [fwd[kind](x, lp) for x in xs]
        del lp
    head = jax.jit(lambda x: logits_of(outer, x, cfg, lowp))
    return [head(x[r]) for x, r in zip(xs, rows)]


def served_gaps(weights, heads: int, sequences: List[tuple], pad_to: int,
                lowp: Optional[str] = None):
    """For each (prompt, served tokens): the gaps by which each served
    token's logit lies below the reference's best and, when `lowp` is
    given, the gaps of the token the lower precision puts first at the
    same positions. `weights` is what the builder's `make_weights` gives:
    the key and the configuration (the reference draws each layer again;
    `heads` is the configuration's own and `pad_to` its limit, both taken
    from it). Sequences are padded on the right to a multiple of PAD_TO
    (a causal model never reads what follows)."""
    key, cfg = weights["key"], weights["cfg"]
    toks, rows = [], []
    for prompt, served in sequences:
        seq = list(prompt) + list(served)
        n = -(-len(seq) // PAD_TO) * PAD_TO
        toks.append(jnp.zeros((n,), jnp.int32).at[:len(seq)].set(
            jnp.asarray(seq, jnp.int32)))
        rows.append(slice(len(prompt) - 1, len(seq) - 1))
    ref = forward_all(key, cfg, toks, rows)
    low = None if lowp is None else forward_all(key, cfg, toks, rows, lowp)
    out = []
    for i, (_, served) in enumerate(sequences):
        best = ref[i].max(-1)
        gap = lambda pick: jax.device_get(best - jnp.take_along_axis(
            ref[i], pick[:, None], -1)[:, 0])
        out.append((gap(jnp.asarray(served, jnp.int32)),
                    None if low is None else gap(low[i].argmax(-1))))
    return out
