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
kernel for it (`megablox.gmm`, `pallas_call` name `gmm`; on a v5e it
runs a decode step's 256 rows at 87% of the memory's rate and a chunk's
16,384 at 3.6 times the speed of the compiler's `ragged_dot`: chip
runs, PR 29), elsewhere `jax.lax.ragged_dot`. What the absent experts would
have added is left out: the caller's exchange adds it, where there is
one. On one chip that holds all experts the result is the whole layer.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__traced__ = ("route", "expert_ffn")

_LANE = 128


def grouped_kernel_available() -> bool:
    """The Pallas grouped product exists for the TPU only."""
    return jax.default_backend() == "tpu"


def _tile(dim: int, cap: int = 1024) -> int:
    """The largest lane multiple that divides `dim` and is at most
    `cap`; `dim` itself where there is none."""
    best = [t for t in range(_LANE, min(dim, cap) + 1, _LANE)
            if dim % t == 0]
    return best[-1] if best else dim


def grouped_dot(a, w, sizes):
    """``a[rows of group g] @ w[g]`` for each group, rows in group order,
    float32: a [m, k], w [groups, k, n], sizes int32 [groups]. Rows past
    the groups' sum hold whatever the product left there."""
    m = a.shape[0]
    tm = next((t for t in (256, 128, 64, 32, 16, 8) if m % t == 0), 0)
    if not (tm and grouped_kernel_available()):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm(a, w, sizes, preferred_element_type=jnp.float32,
               tiling=(tm, _tile(w.shape[1]), _tile(w.shape[2])))


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

    -> (y [t, d] in h's type, int32 [3]: assignments computed here,
    experts with at least one token, the fullest expert's tokens)."""
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
    xs = h[order // k]                               # [t*k, d]
    act = (jax.nn.silu(grouped_dot(xs, wg, sizes))
           * grouped_dot(xs, wu, sizes)).astype(h.dtype)
    ys = grouped_dot(act, wd, sizes)                 # [t*k, d] float32
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    y = ys[back].reshape(t, k, -1)
    # rows past the held groups are whatever the product left there
    y = jnp.where((slot < n)[..., None], y * weights[..., None], 0.0)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes)])
    return jnp.sum(y, axis=1).astype(h.dtype), stats.astype(jnp.int32)
