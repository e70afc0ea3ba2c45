"""A layer of sparse experts that is told which experts it holds.

Routing is over every expert of the layer, in float32, by one of two
rules: softmax over all router outputs, the `top_k` largest, their
weights renormalised; or a sigmoid an expert, the `top_k` largest of
score + a selection-only bias, weighted by the unbiased scores,
renormalised and scaled. A shared expert that every token passes is no
part of this layer: the caller computes it once, whatever share of the
experts is held here.
The product is over the experts held here only. Tokens are grouped by
expert with a stable sort, with no capacity, so no token is ever
dropped, and multiplied by a grouped product that reads an expert's
weights only if the expert has tokens: on a TPU jax's own Pallas
kernel for it (`megablox.gmm`, `pallas_call` name `gmm`), elsewhere
`jax.lax.ragged_dot`. What the absent experts would have added is left
out: the caller's exchange adds it, where there is one. On one chip
that holds all experts the result is the whole layer.

The rows are sorted end to end by expert, and gmm is tiled by the
call's static shape alone, the rows a held expert has on average
(`m / held`, m = tokens x experts a token):
  * a decode step's few (the served steps have 1.5-10): `(tm <= 256,
    <= 1,024, <= 1,024)`; at 256 rows a step it reads the experts at
    76-87% of the memory's rate;
  * a prefill chunk's many (96-320 in the served chunks): gmm's grid
    runs its contraction innermost, so with the contraction split in
    tiles an expert's weights crossed from memory again on every row
    tile it ran. A chunk's contraction is whole (`tk = k`, `tn` as wide
    as gmm's 16 MiB of fast memory allows double-buffered), so a
    group's consecutive row tiles keep one weight block and its weights
    cross once a call. On a v5e that is 4.7% off a layer's `expert_ffn`
    at each served chunk shape (Mellum 2,048 x 8 of 64: 4.51 -> 4.30
    ms). Row tiles of 128 were within a call's noise of 256 (4.18 and
    3.82 against 4.30 and 3.78), so the row tile is the step's rule.
    Laying each expert's rows out in whole tiles of their own, so that
    no tile runs for two experts, was slower at every served chunk
    shape (4.76 ms there): the scatter, the padded gather and the
    elementwise pass over the padded length cost more than the tiles
    it saved (chip runs, PR 38).
A step keeps its split contraction: a served step's rows lie in one to
five row tiles, so an expert's weights cross once a visit already, and
again only where a group straddles a tile's edge.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__traced__ = ("route", "expert_ffn")

_LANE = 128
# a call is a chunk's where a held expert has this many rows on average
CHUNK_ROWS_PER_EXPERT = 32
# gmm's `pallas_call` passes no limit: Mosaic's scoped default on a v5e
GMM_VMEM_BYTES = 16 << 20


def grouped_kernel_available() -> bool:
    """The Pallas grouped product exists for the TPU only."""
    return jax.default_backend() == "tpu"


def _tiles(dim: int, cap: int = 1024) -> list:
    """The lane multiples that divide `dim` and are at most `cap`,
    largest first; `[dim]` where there is none."""
    return [t for t in range(min(dim, cap) // _LANE * _LANE, 0, -_LANE)
            if dim % t == 0] or [dim]


def _tile(dim: int, cap: int = 1024) -> int:
    """The largest lane multiple that divides `dim` and is at most
    `cap`; `dim` itself where there is none."""
    return _tiles(dim, cap)[0]


def chunk_form(m: int, held: int) -> bool:
    """Whether a product of `m` rows over `held` experts takes the
    chunk's form: the rows a held expert has on average."""
    return m >= CHUNK_ROWS_PER_EXPERT * held


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """gmm's fast memory at a tiling: its row, weight and float32 output
    blocks, each double-buffered, and its float32 accumulator."""
    return 2 * (tm * tk * itemsize + tk * tn * itemsize + tm * tn * 4) \
        + tm * tn * 4


def grouped_tiling(m: int, held: int, k: int, n: int,
                   itemsize: int = 2) -> Tuple[int, int, int]:
    """gmm's `(tm, tk, tn)` for `m` rows of width `k` over `held` experts
    of [k, n]: the largest row tile of 256 or less that divides m (0
    where none does: `ragged_dot` then, on any backend). A step: `k` and
    `n` in lane tiles of 1,024 or less. A chunk (:func:`chunk_form`): the
    whole `k` and the widest `n` tile whose blocks fit GMM_VMEM_BYTES, or
    the step's tiles where none does."""
    tm = next((t for t in (256, 128, 64, 32, 16, 8) if m % t == 0), 0)
    for tn in _tiles(n) if chunk_form(m, held) and tm else ():
        if gmm_vmem_bytes(tm, k, tn, itemsize) <= GMM_VMEM_BYTES:
            return tm, k, tn
    return tm, _tile(k), _tile(n)


def rows_computed(sizes, tm: int):
    """Rows gmm's kernel computes at row tile `tm` for groups of `sizes`
    laid end to end: every tile a group touches, once for each group in
    it; the rows themselves where tm is 0."""
    if not tm:
        return jnp.sum(sizes)
    ends = jnp.cumsum(sizes)
    tiles = -(-ends // tm) - (ends - sizes) // tm
    return jnp.sum(jnp.where(sizes > 0, tiles, 0)) * tm


def grouped_dot(a, w, sizes, tiling: Tuple[int, int, int]):
    """``a[rows of group g] @ w[g]`` for each group, rows in group order,
    float32: a [m, k], w [groups, k, n], sizes int32 [groups], gmm's
    `tiling` from :func:`grouped_tiling`. Rows past the groups' sum hold
    whatever the product left there."""
    if not (tiling[0] and grouped_kernel_available()):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm(a, w, sizes, preferred_element_type=jnp.float32,
               tiling=tiling)


def route(h, wr, top_k: int, *, scoring: str = "softmax", select_bias=None,
          scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """h [t, d], wr [d, experts] -> (weights float32 [t, top_k], expert
    ids int32 [t, top_k]), all in float32, ties to the lower index.
    `scoring="softmax"`: softmax over all experts, the `top_k` largest,
    renormalised to sum 1. `"sigmoid"`: each expert's own sigmoid; the
    `top_k` largest of ``score + select_bias`` (float32 [experts]: it
    moves the choice and never the weight) weighted by their unbiased
    scores over their sum + 1e-20. Either way times `scale`."""
    logits = jnp.dot(h, wr, preferred_element_type=jnp.float32)
    if scoring == "softmax":
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    elif scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            s if select_bias is None
            else s + select_bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    return w * scale if scale != 1.0 else w, idx.astype(jnp.int32)


def expert_ffn(h, weights, experts, wg, wu, wd, *, n_experts: int,
               experts_held: Optional[Sequence[int]] = None, valid=None
               ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of ``sum_e w_e (silu(h Wg_e) * (h Wu_e))
    Wd_e``.

      h             [t, d]
      weights, experts  [t, k] from :func:`route`
      wg, wu        [held, d, f]; wd [held, f, d]: the held experts'
                    matrices, in the order of `experts_held`
      experts_held  the layer's expert ids held here (default: all
                    `n_experts`, in order)
      valid         bool [t]: rows that are real tokens; the others are
                    given to no expert and come back zero

    -> (y [t, d] in h's type, int32 [4]: assignments computed here,
    experts with at least one token, the fullest expert's tokens, rows
    the gate and up products run on a TPU (:func:`rows_computed`))."""
    t, k = experts.shape
    held = tuple(range(n_experts)) if experts_held is None \
        else tuple(int(e) for e in experts_held)
    n = len(held)
    if wg.shape[0] != n:
        raise ValueError(f"{wg.shape[0]} expert matrices for {n} held")
    slot_of = np.full((n_experts,), n, np.int32)     # n: not held here
    slot_of[list(held)] = np.arange(n, dtype=np.int32)
    slot = jnp.asarray(slot_of)[experts]             # [t, k]
    if valid is not None:
        slot = jnp.where(valid[:, None], slot, n)
    flat = slot.reshape(-1)
    order = jnp.argsort(flat, stable=True)           # grouped by slot
    sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    m, (d, f) = t * k, wg.shape[1:]
    item = max(h.dtype.itemsize, wg.dtype.itemsize)
    up = grouped_tiling(m, n, d, f, item)
    xs = h[order // k]                               # [t*k, d]
    act = (jax.nn.silu(grouped_dot(xs, wg, sizes, up))
           * grouped_dot(xs, wu, sizes, up)).astype(h.dtype)
    ys = grouped_dot(act, wd, sizes, grouped_tiling(m, n, f, d, item))
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(m, dtype=order.dtype))
    y = ys[back].reshape(t, k, -1)
    # rows past the held groups are whatever the product left there
    y = jnp.where((slot < n)[..., None], y * weights[..., None], 0.0)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes),
                       rows_computed(sizes, up[0])])
    return jnp.sum(y, axis=1).astype(h.dtype), stats.astype(jnp.int32)
