"""Plain reference: the decoder block of kanana-2-30b-a3b-instruct-2601
(kakaocorp; `model_type` `deepseek_v3`; the equations are written out in
the configuration's issue and below), in straightforward float32
`jax.numpy` at `precision=highest`. One full forward over a whole
sequence in the PUBLISHED form of latent attention: every position's
latent is expanded to keys and values per head, rotary turns interleaved
pairs; no cache, no chunks, no packing, no kernels, no absorbed
products, no grouping of tokens by expert (each held expert is applied
to every token under its routing weight, which is zero where the token
did not choose it). Imports nothing of the program.

    h = rms(x; g1);  q = h Wq -> [t, 32, 192] = q_nope 128 | q_pe 64
    h Wkv_a -> [t, 576] = c 512 | k_pe 64;  c = rms(c; g_kv)
    c Wkv_b -> [t, 32, 256] = k_nope 128 | v 128
    rotary (theta 1e6, no scaling) on q_pe per head and on k_pe, which
      all heads share: pair (2i, 2i+1) turns by pos * theta^(-2i/64)
    scores [q_nope | q_pe] . [k_nope | k_pe] * 192^-0.5, causal softmax
      in float32;  x += concat_heads(p v) Wo
    h2 = rms(x; g2)
    layer 0:  x += (silu(h2 Wg) * (h2 Wu)) Wd            (width 6,144)
    layers 1..:  s = sigmoid(h2 Wr) over all 128;  E = the 6 largest of
      s + bias (ties to the lower index);  w_e = s_e / (sum_E s + 1e-20)
      * 2.448;  x += sum_E w_e SwiGLU_e(h2) + SwiGLU_shared(h2)
    logits = rms(x; gf) W_head

The weights are the bfloat16 values the program holds, cast up (the
selection bias is float32). A sparse layer in float32 is 2.5 GB, so a
layer is made and held at a time, from `fold_in(key, layer)` as the
builder makes it, and every sequence goes through it before the next is
made; attention runs in blocks of queries. `experts_held` (configuration
key; default all) gives the reference the same share of the routed
experts as the program; the shared expert is every share's. `lowp` is
the control: every matrix operand, the residual stream and the logits
rounded to that type.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
Q_BLOCK = 256           # queries a block of the reference's attention
PAD_TO = 1024           # sequences are padded to a multiple (few programs),
PAD_LONG = 4096         # and past that length to a multiple of this one
OUTER = 1 << 20         # fold-in numbers of the embedding and the head
BIAS_RANGE = 0.1        # the selection bias is drawn uniform in +-this


def ffn_kinds(cfg: dict) -> List[str]:
    """`dense` or `moe` for each layer that is run."""
    return ["dense" if li < cfg["first_k_dense_replace"] else "moe"
            for li in range(cfg["num_hidden_layers"])]


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", range(cfg["n_routed_experts"])))


def leaf_shapes(cfg: dict, kind: str) -> Dict[str, tuple]:
    d, hh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    shapes = {"wq": (d, hh * (nope + rope)), "wkv_a": (d, rank + rope),
              "wkv_b": (rank, hh * (nope + vd)), "wo": (hh * vd, d)}
    if kind == "dense":
        f = cfg["intermediate_size"]
        shapes.update(wg=(d, f), wu=(d, f), wd=(f, d))
    else:
        f, n = cfg["moe_intermediate_size"], len(held(cfg))
        sf = cfg["n_shared_experts"] * f
        shapes.update(wr=(d, cfg["n_routed_experts"]), wg=(n, d, f),
                      wu=(n, d, f), wd=(n, f, d), sg=(d, sf), su=(d, sf),
                      sd=(sf, d))
    return shapes


def _draw(key, shape, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["init_std"]).astype(jnp.bfloat16)


def init_layer(key, layer, cfg: dict, kind=None):
    """Layer `layer`'s weights from `fold_in(key, layer)`: bfloat16,
    normal(0, init_std) matrices (a held expert's from its own id, so a
    share holds the same values as the whole), norms at one, the
    router's selection bias float32 uniform in +-BIAS_RANGE. With
    `kind` given, `layer` may be traced: one program a kind of layer."""
    lk = jax.random.fold_in(key, layer)
    kind = kind or ffn_kinds(cfg)[layer]
    ones = jnp.ones((cfg["hidden_size"],), jnp.bfloat16)
    lp = {"ln1_s": ones, "ln2_s": ones,
          "kv_ln_s": jnp.ones((cfg["kv_lora_rank"],), jnp.bfloat16)}
    ids = jnp.asarray(held(cfg))
    for j, (name, shape) in enumerate(leaf_shapes(cfg, kind).items()):
        k = jax.random.fold_in(lk, j)
        if len(shape) == 3:
            lp[name] = jax.vmap(lambda e: _draw(jax.random.fold_in(k, e),
                                                shape[1:], cfg))(ids)
        else:
            lp[name] = _draw(k, shape, cfg)
    if kind == "moe":
        lp["rb"] = jax.random.uniform(
            jax.random.fold_in(lk, 99), (cfg["n_routed_experts"],),
            jnp.float32, -BIAS_RANGE, BIAS_RANGE)
    return lp


def init_outer(key, cfg: dict):
    """The embedding, the untied head and the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"emb": _draw(jax.random.fold_in(key, OUTER), (v, d), cfg),
            "head": _draw(jax.random.fold_in(key, OUTER + 1), (d, v), cfg),
            "lnf_s": jnp.ones((d,), jnp.bfloat16)}


def _rope(a, cfg: dict):
    """a [t, heads, rope_dim] at positions 0..t-1, interleaved pairs."""
    dim = a.shape[-1]
    inv = float(cfg["rope_theta"]) ** (
        -2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = a[..., 0::2], a[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(a.shape)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _lowp(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def route(h, wr, bias, cfg: dict):
    """h [t, d] float32 -> (weights [t, 6], expert ids [t, 6])."""
    s = jax.nn.sigmoid(jnp.dot(h, wr, precision=HI))
    _, idx = lax.top_k(s + bias, cfg["num_experts_per_tok"])    # ties low
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], idx


def layer_forward(x, lp, cfg: dict, kind: str, lowp=None):
    """One layer on x [t, hidden], float32."""
    t = x.shape[0]
    hh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    dq = nope + cfg["qk_rope_head_dim"]
    eps = cfg["rms_norm_eps"]
    w = {k: v.astype(jnp.float32) for k, v in lp.items()}
    mm = lambda a, b: jnp.dot(_lowp(a, lowp), _lowp(b, lowp), precision=HI)
    h = _rms(x, w["ln1_s"], eps)
    q = mm(h, w["wq"]).reshape(t, hh, dq)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    ckv = mm(h, w["wkv_a"])
    c = _rms(ckv[:, :rank], w["kv_ln_s"], eps)
    k_pe = _rope(ckv[:, None, rank:], cfg)
    kv = mm(c, w["wkv_b"]).reshape(t, hh, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (t, hh, dq - nope))], -1)
    v = kv[..., nope:]
    at, nq = jnp.arange(t), min(Q_BLOCK, t)   # t a multiple, or one block

    def block(lo):
        qb = lax.dynamic_slice_in_dim(q, lo, nq)
        see = at[None, :] <= (lo + jnp.arange(nq))[:, None]
        s = jnp.einsum("qhd,khd->hqk", _lowp(qb, lowp), _lowp(k, lowp),
                       precision=HI) * dq ** -0.5
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", _lowp(p, lowp), _lowp(v, lowp),
                          precision=HI)

    a = lax.map(block, jnp.arange(0, t, nq)).reshape(t, hh * vd)
    x = _lowp(x + mm(a, w["wo"]), lowp)
    h = _rms(x, w["ln2_s"], eps)
    swiglu = lambda g, u, d: mm(jax.nn.silu(mm(h, g)) * mm(h, u), d)
    if kind == "dense":
        return _lowp(x + swiglu(w["wg"], w["wu"], w["wd"]), lowp)
    best, idx = route(h, w["wr"], w["rb"], cfg)
    ids = jnp.asarray(held(cfg))

    def one(y, e):          # held expert e on every token, under its weight
        we = jnp.sum(jnp.where(idx == ids[e], best, 0.0), -1)
        return y + we[:, None] * swiglu(w["wg"][e], w["wu"][e],
                                        w["wd"][e]), None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(len(held(cfg))))
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
    Each sequence's length is a multiple of Q_BLOCK."""
    lowp = None if lowp is None else jnp.dtype(lowp)
    outer = jax.jit(lambda k: init_outer(k, cfg))(key)
    make = {kind: jax.jit(lambda k, li, kind=kind: init_layer(
        k, li, cfg, kind)) for kind in ("dense", "moe")}
    xs = [outer["emb"][t].astype(jnp.float32) for t in tokens]
    fwd = {kind: jax.jit(lambda x, lp, kind=kind: layer_forward(
        x, lp, cfg, kind, lowp)) for kind in ("dense", "moe")}
    for li, kind in enumerate(ffn_kinds(cfg)):
        lp = make[kind](key, li)
        xs = [fwd[kind](x, lp) for x in xs]
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
    `heads` is the configuration's own and `pad_to` its limit, both taken
    from it). Sequences are padded on the right to a multiple of PAD_TO,
    long ones of PAD_LONG (a causal model never reads what follows; a
    length is a program to compile, and the mix's sixteen sizes then
    make seven)."""
    key, cfg = weights["key"], weights["cfg"]
    toks, rows = [], []
    for prompt, served in sequences:
        seq = list(prompt) + list(served)
        unit = PAD_TO if len(seq) <= PAD_LONG else PAD_LONG
        n = -(-len(seq) // unit) * unit
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
