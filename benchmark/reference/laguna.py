"""Plain reference: the decoder block of Laguna-S-2.1 (poolside;
`model_type` `laguna`; the layer's equations are written out in the
configuration's issue and below), in straightforward float32 `jax.numpy`
at `precision=highest`. One full forward over a whole sequence: no cache,
no chunks, no packing, no kernels, no grouping of tokens by expert (each
held expert is applied to every token under its routing weight, which is
zero where the token did not choose it). Imports nothing of the program.

    x the residual stream, K the layer's kind, H_K = 48 (full) | 72
    (sliding) query heads over 8 KV heads of 128 (group 6 | 9)

    h = rms(x; g1);  q = h Wq_K [H_K x 128];  k, v = h Wk, h Wv [8 x 128]
    rotary (rotate-half) on the first r_K values of each head of q and k,
      the rest pass as they are:
      sliding: r = 128, inv_freq = 10000^(-2i/128), factor 1
      full:    r = 64, YaRN's table over 64 dims (theta 500000, factor
               128 over 8192, beta 32 and 1), cos and sin x 1.4852...
    head n reads KV head n // (H_K / 8); position i sees j <= i, and on
      a sliding layer also i - j < 512; scores x 128^-0.5, softmax -> a_n
    gate = sigmoid(h Wgate_K) [H_K];  a_n <- gate_n * a_n      (ASSUMED a)
    x += concat_n(a_n) Wo_K
    h2 = rms(x; g2)
    layer 0:    x += (silu(h2 Wg) * (h2 Wu)) Wd               (12,288 wide)
    layers 1..: p = softmax(h2 Wr) over all 256               (ASSUMED b)
                E = top 10 (ties to the lower index)
                w_e = 2.5 * p_e / sum_E p
                x += sum_{e in E, held here} w_e SwiGLU_e(h2)
                     + SwiGLU_shared(h2)                      (ASSUMED c)
    logits = rms(x; gf) W_head        (over the vocabulary rows held here)

Three forms the published config does not state (each is an entry of the
configuration file's `assumed`, with the other reading beside it):
(a) the gate is a sigmoid of a linear map of the layer's normed input,
one output a query head, multiplied into that head's attention output
before Wo (`gating: "per-head"` says per head and nothing more);
(b) the router scores by softmax over all its outputs (the config's keys
are the Qwen-MoE family's; there is no `scoring_func`); (c) the shared
expert is added ungated. Also assumed: rotate-half, no norm on q or k.

The weights are the bfloat16 values the program holds, cast up. A sparse
layer in float32 is 2.7 GB, so a layer is made and held at a time, from
`fold_in(key, layer)` as the builder makes it, and every sequence goes
through it before the next is made; attention runs in blocks of queries.
`experts_held` gives the reference the same share of the routed experts
as the program (the key `num_experts` counts them; the router's width is
`num_experts_published`), and `vocab_size` is the slice of the vocabulary
held here; the shared expert is every share's. `lowp` is the control:
every matrix operand, the residual stream and the logits rounded to that
type.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
Q_BLOCK = 256           # queries a block of the reference's attention
PAD_TO = 1024           # sequences are padded to a multiple (few programs)
OUTER = 1 << 20         # fold-in numbers of the embedding and the head


def layer_kinds(cfg: dict) -> List[str]:
    """`full` or `sliding` for each layer that is run."""
    return [t.split("_")[0]
            for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def ffn_kinds(cfg: dict) -> List[str]:
    """`dense` or `moe` for each layer that is run."""
    return ["dense" if t == "dense" else "moe"
            for t in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]]]


def heads_by_kind(cfg: dict) -> Dict[str, int]:
    """Query heads of a full and of a sliding layer, from the published
    list a layer; a kind has one count."""
    out: Dict[str, int] = {}
    for kind, n in zip(layer_kinds(cfg),
                       cfg["num_attention_heads_per_layer"]):
        if out.setdefault(kind, n) != n:
            raise ValueError(f"{kind} layers of {out[kind]} and {n} heads")
    return out


def router_width(cfg: dict) -> int:
    """The router's outputs: the published count of experts, whatever
    share of them is held here."""
    return cfg.get("num_experts_published", cfg["num_experts"])


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", range(router_width(cfg))))


def leaf_shapes(cfg: dict, kind: str, ffn: str) -> Dict[str, tuple]:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hh, kv = heads_by_kind(cfg)[kind], cfg["num_key_value_heads"] * dh
    shapes = {"wq": (d, hh * dh), "wk": (d, kv), "wv": (d, kv),
              "wo": (hh * dh, d), "wgate": (d, hh)}
    if ffn == "dense":
        f = cfg["intermediate_size"]
        shapes.update(wg=(d, f), wu=(d, f), wd=(f, d))
    else:
        f, n = cfg["moe_intermediate_size"], len(held(cfg))
        sf = cfg["shared_expert_intermediate_size"]
        shapes.update(wr=(d, router_width(cfg)), wg=(n, d, f), wu=(n, d, f),
                      wd=(n, f, d), sg=(d, sf), su=(d, sf), sd=(sf, d))
    return shapes


def _draw(key, shape, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["init_std"]).astype(jnp.bfloat16)


def init_layer(key, layer, cfg: dict, kinds: Optional[Tuple[str, str]]
               = None):
    """Layer `layer`'s weights from `fold_in(key, layer)`: bfloat16,
    normal(0, init_std) matrices (a held expert's from its own id, so a
    share holds the same values as the whole), norms at one. With
    `kinds` = (attention kind, feed-forward kind) given, `layer` may be
    traced: one program a pair of kinds."""
    lk = jax.random.fold_in(key, layer)
    kind, ffn = kinds or (layer_kinds(cfg)[layer], ffn_kinds(cfg)[layer])
    ones = jnp.ones((cfg["hidden_size"],), jnp.bfloat16)
    lp = {"ln1_s": ones, "ln2_s": ones}
    ids = jnp.asarray(held(cfg))
    for j, (name, shape) in enumerate(leaf_shapes(cfg, kind, ffn).items()):
        k = jax.random.fold_in(lk, j)
        if len(shape) == 3:
            lp[name] = jax.vmap(lambda e: _draw(jax.random.fold_in(k, e),
                                                shape[1:], cfg))(ids)
        else:
            lp[name] = _draw(k, shape, cfg)
    return lp


def init_outer(key, cfg: dict):
    """The embedding, the untied head and the final norm, over the rows
    of the vocabulary held here."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"emb": _draw(jax.random.fold_in(key, OUTER), (v, d), cfg),
            "head": _draw(jax.random.fold_in(key, OUTER + 1), (d, v), cfg),
            "lnf_s": jnp.ones((d,), jnp.bfloat16)}


def rope_table(cfg: dict, kind: str):
    """(rotated width r, inv_freq float32 [r / 2], factor on cos and sin)
    of a kind of layer, from the published `rope_parameters`: the table
    is over the ROTATED width, `head_dim * partial_rotary_factor`, and
    YaRN's correction dimensions are counted in it."""
    rp = cfg["rope_parameters"][kind + "_attention"]
    dim = int(cfg["head_dim"] * rp.get("partial_rotary_factor", 1.0))
    theta = float(rp["rope_theta"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / dim)
    if rp["rope_type"] == "default":
        return dim, inv, 1.0
    s, l0 = float(rp["factor"]), rp["original_max_position_embeddings"]
    turn = lambda b: dim * math.log(l0 / (2 * math.pi * b)) \
        / (2 * math.log(theta))
    low = max(math.floor(turn(rp["beta_fast"])), 0)
    high = min(math.ceil(turn(rp["beta_slow"])), dim - 1)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return dim, inv * (1 - r) + inv / s * r, float(rp["attention_factor"])


def _rope(a, table):
    """a [t, heads, head_dim] at positions 0..t-1: rotate-half on the
    first r values of each head, the rest pass."""
    r, inv, factor = table
    ang = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * factor
    turned, rest = a[..., :r], a[..., r:]
    lo, hi = jnp.split(turned, 2, -1)
    return jnp.concatenate(
        [turned * cos + jnp.concatenate([-hi, lo], -1) * sin, rest], -1)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _lowp(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def route(h, wr, cfg: dict):
    """h [t, d] float32 -> (weights [t, 10], expert ids [t, 10]):
    softmax over all router outputs, the largest (ties to the lower
    index), renormalised, times the routed scale."""
    p = jax.nn.softmax(jnp.dot(h, wr, precision=HI), -1)
    best, idx = lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        best = best / jnp.sum(best, -1, keepdims=True)
    return best * cfg["moe_routed_scaling_factor"], idx


def layer_forward(x, lp, cfg: dict, kind: str, ffn: str, lowp=None):
    """One layer on x [t, hidden], float32."""
    t = x.shape[0]
    hh, kvh = heads_by_kind(cfg)[kind], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    w = {k: v.astype(jnp.float32) for k, v in lp.items()}
    mm = lambda a, b: jnp.dot(_lowp(a, lowp), _lowp(b, lowp), precision=HI)
    table = rope_table(cfg, kind)
    h = _rms(x, w["ln1_s"], eps)
    q = _rope(mm(h, w["wq"]).reshape(t, hh, dh), table)
    k = _rope(mm(h, w["wk"]).reshape(t, kvh, dh), table)
    v = mm(h, w["wv"]).reshape(t, kvh, dh)
    k, v = (jnp.repeat(a, hh // kvh, axis=1) for a in (k, v))
    at, nq = jnp.arange(t), min(Q_BLOCK, t)   # t a multiple, or one block

    def block(lo):
        i = (lo + jnp.arange(nq))[:, None]
        see = at[None, :] <= i
        if kind == "sliding":
            see &= i - at[None, :] < cfg["sliding_window"]
        s = jnp.einsum("qhd,khd->hqk",
                       _lowp(lax.dynamic_slice_in_dim(q, lo, nq), lowp),
                       _lowp(k, lowp), precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", _lowp(p, lowp), _lowp(v, lowp),
                          precision=HI)

    a = lax.map(block, jnp.arange(0, t, nq)).reshape(t, hh, dh)
    # ASSUMED (a): one sigmoid a head a token, from the layer's normed
    # input, on the head's output before Wo
    gate = jax.nn.sigmoid(mm(h, w["wgate"]))
    a = (a * gate[:, :, None]).reshape(t, hh * dh)
    x = _lowp(x + mm(a, w["wo"]), lowp)
    h = _rms(x, w["ln2_s"], eps)
    swiglu = lambda g, u, d: mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)
    if ffn == "dense":
        return _lowp(x + swiglu(w["wg"], w["wu"], w["wd"]), lowp)
    best, idx = route(h, w["wr"], cfg)          # ASSUMED (b): softmax
    ids = jnp.asarray(held(cfg))

    def one(y, e):          # held expert e on every token, under its weight
        we = jnp.sum(jnp.where(idx == ids[e], best, 0.0), -1)
        return y + we[:, None] * swiglu(w["wg"][e], w["wu"][e],
                                        w["wd"][e]), None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(len(held(cfg))))
    # ASSUMED (c): the shared expert ungated, once, whatever share is held
    return _lowp(x + y + swiglu(w["sg"], w["su"], w["sd"]), lowp)


def logits_of(outer, x, cfg: dict, lowp=None):
    h = _rms(x, outer["lnf_s"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _lowp(jnp.dot(_lowp(h, lowp),
                         _lowp(outer["head"].astype(jnp.float32), lowp),
                         precision=HI), lowp)


def forward_all(key, cfg: dict, tokens: List[jax.Array], rows: List[slice],
                lowp=None) -> List[jax.Array]:
    """The logits of each sequence's `rows`, float32. Layer by layer: one
    layer's weights exist at a time, and every sequence passes them.
    Each sequence's length is a multiple of Q_BLOCK, or under it."""
    lowp = None if lowp is None else jnp.dtype(lowp)
    outer = jax.jit(lambda k: init_outer(k, cfg))(key)
    pairs = sorted(set(zip(layer_kinds(cfg), ffn_kinds(cfg))))
    make = {p: jax.jit(lambda k, li, p=p: init_layer(k, li, cfg, p))
            for p in pairs}
    fwd = {p: jax.jit(lambda x, lp, p=p: layer_forward(x, lp, cfg, *p,
                                                       lowp=lowp))
           for p in pairs}
    xs = [outer["emb"][t].astype(jnp.float32) for t in tokens]
    for li, p in enumerate(zip(layer_kinds(cfg), ffn_kinds(cfg))):
        lp = make[p](key, li)
        xs = [fwd[p](x, lp) for x in xs]
        del lp
    head = jax.jit(lambda o, x: logits_of(o, x, cfg, lowp))
    return [head(outer, x[r]) for x, r in zip(xs, rows)]


def served_gaps(weights, heads: int, sequences: List[tuple], pad_to: int,
                lowp: Optional[str] = None):
    """For each (prompt, served tokens): the gaps by which each served
    token's logit lies below the reference's best and, when `lowp` is
    given, the gaps of the token the lower precision puts first at the
    same positions. `weights` is what the builder's `make_weights` gives:
    the key and the configuration (the reference draws each layer again;
    `heads` is the configuration's scalar and `pad_to` its limit: the
    heads a kind and the lengths are taken from the configuration and the
    sequences). Sequences are padded on the right to a multiple of
    PAD_TO (a causal model never reads what follows; a length is a
    program to compile)."""
    key, cfg = weights["key"], weights["cfg"]
    toks, rows = [], []
    for prompt, served in sequences:
        seq = list(prompt) + list(served)
        n = -(-len(seq) // PAD_TO) * PAD_TO
        toks.append(jnp.zeros((n,), jnp.int32).at[:len(seq)].set(
            jnp.asarray(seq, jnp.int32)))
        rows.append(slice(len(prompt) - 1, len(seq) - 1))
    with jax.default_matmul_precision("highest"):
        ref = forward_all(key, cfg, toks, rows)
        low = None if lowp is None else forward_all(key, cfg, toks, rows,
                                                    lowp)
    out = []
    for i, (_, served) in enumerate(sequences):
        best = ref[i].max(-1)
        gap = lambda pick: jax.device_get(best - jnp.take_along_axis(
            ref[i], pick[:, None], -1)[:, 0])
        out.append((gap(jnp.asarray(served, jnp.int32)),
                    None if low is None else gap(low[i].argmax(-1))))
    return out
