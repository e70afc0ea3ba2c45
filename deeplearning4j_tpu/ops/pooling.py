"""Spatial pooling implementations with measured dispatch.

The round-5 GoogLeNet profile (docs/perf_googlenet.md) put 9.5 ms/step —
18% of on-device time — in XLA's select-and-scatter emitter, the VJP it
generates for `lax.reduce_window(max)`, running at 2.1× its byte bound.
S&S is the one HLO in the step with no MXU/VPU-friendly lowering: it
walks windows serially per output element. This module provides the
alternatives and the selector that decides between them, mirroring
`select_attention_impl` (ops/attention.py): static trace-time choice
and a `pooling_impl_selected_total{impl=}` counter in the PR-2
registry. Every impl is portable lax; a lowering failure fails the
step's compile rather than selecting another impl.

Max pool:
  * "sns"  — `lax.reduce_window(max)`; autodiff emits select-and-scatter
    for the backward (XLA's default, the round-5 measured baseline).
  * "mask" — same forward under a custom_vjp whose backward is the
    argmax-equality-mask recompute: per window offset (p,q) compare the
    strided view of x against the broadcast pooled output, divide the
    cotangent by the per-window tie count, and scatter each offset's
    share back with `lax.pad` interior dilation —
    dx = Σ_{(p,q)} dilate(g · (x_pq == out) / ties). Pure
    pad/slice/compare/add (no S&S anywhere in fwd or bwd), so every
    piece is fusible elementwise work.

    Tie semantics differ deliberately: S&S routes the whole cotangent to
    the first maximal element of a window; "mask" splits it equally
    among ties (the mathematically symmetric subgradient; both preserve
    the cotangent sum). Identical whenever window maxima are unique.

Avg pool:
  * "window" — sum reduce_window / count reduce_window, divisor counting
    only in-bounds elements (the layer's historical path; backward is
    the pad+reduce_window transpose of reduce_window-sum).
  * "conv"   — depthwise `conv_general_dilated` with a ones kernel
    (feature_group_count = C) divided by the same in-bounds count; the
    backward is then a transposed conv — an MXU op instead of
    reduce_window. Same count-exclude-pad semantics.

SUM / PNORM stay on reduce_window in the layer (no alternative emitter
worth having: their backwards are already pad+reduce_window / pure
elementwise chains).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

# Implementation inventory, per pooling family. "auto"/None resolve via
# the measured rule in select_pooling_impl.
MAX_IMPLS = ("sns", "mask")
AVG_IMPLS = ("window", "conv")

Pads2D = Tuple[Tuple[int, int], Tuple[int, int]]


def _window4(window, strides, pads: Pads2D):
    return ((1, window[0], window[1], 1), (1, strides[0], strides[1], 1),
            ((0, 0), pads[0], pads[1], (0, 0)))


def _reduce_max(x: Array, window, strides, pads: Pads2D) -> Array:
    w4, s4, p4 = _window4(window, strides, pads)
    return lax.reduce_window(x, -jnp.inf, lax.max, w4, s4, p4)


def _reduce_sum(x: Array, window, strides, pads: Pads2D) -> Array:
    w4, s4, p4 = _window4(window, strides, pads)
    return lax.reduce_window(x, 0.0, lax.add, w4, s4, p4)


def inbounds_count(x: Array, window, strides, pads: Pads2D) -> Array:
    """Per-output-window count of in-bounds input elements (the
    count-exclude-pad divisor of the reference average pool). Constant
    given static shapes — XLA folds it at compile time."""
    return _reduce_sum(jnp.ones_like(x), window, strides, pads)


# ---------------------------------------------------------------------------
# Mask-backward max pool
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _max_pool_mask(x: Array, window, strides, pads: Pads2D) -> Array:
    return _reduce_max(x, window, strides, pads)


def _max_pool_mask_fwd(x, window, strides, pads):
    y = _reduce_max(x, window, strides, pads)
    return y, (x, y)


def _max_pool_mask_bwd(window, strides, pads, res, g):
    x, y = res
    kh, kw = window
    sh, sw = strides
    (pt, pb), (pl, pr) = pads
    B, H, W, C = x.shape
    OH, OW = y.shape[1], y.shape[2]
    # Padded extents must cover the furthest window: offset (kh-1, kw-1)
    # of the last output position, which can exceed H+pt+pb when the
    # high pad is smaller than the window reach (VALID with truncation).
    hp = max(H + pt + pb, (OH - 1) * sh + kh)
    wp = max(W + pl + pr, (OW - 1) * sw + kw)
    # -inf fill: a padding cell can only compare equal to y where the
    # whole window is padding (y == -inf there too); that cotangent share
    # lands in the pad margin and is sliced away below.
    xp = jnp.pad(x, ((0, 0), (pt, hp - H - pt), (pl, wp - W - pl), (0, 0)),
                 constant_values=-jnp.inf)
    # Pass 1 — per-window tie count: for each window offset, the strided
    # view of xp aligned to the output grid equals y exactly where that
    # offset holds a window max (y is a copy of some window element, so
    # equality is exact in every dtype).
    offsets = [(p, q) for p in range(kh) for q in range(kw)]
    eqs = []
    ties = None
    for p, q in offsets:
        xo = lax.slice(xp, (0, p, q, 0),
                       (B, p + (OH - 1) * sh + 1, q + (OW - 1) * sw + 1, C),
                       (1, sh, sw, 1))
        eq = (xo == y)
        eqs.append(eq)
        e = eq.astype(g.dtype)
        ties = e if ties is None else ties + e
    share = g / ties
    # Pass 2 — scatter each offset's share back onto the padded input
    # grid: interior dilation (stride-1 zeros) + low/high edge pads place
    # the output-grid array at exactly the input cells that offset
    # touches. lax.pad is the same primitive the reduce_window-sum
    # transpose lowers to — fusible, no select-and-scatter.
    zero = jnp.zeros((), g.dtype)
    dxp = None
    for (p, q), eq in zip(offsets, eqs):
        contrib = share * eq.astype(g.dtype)
        placed = lax.pad(
            contrib, zero,
            ((0, 0, 0),
             (p, hp - p - (OH - 1) * sh - 1, sh - 1),
             (q, wp - q - (OW - 1) * sw - 1, sw - 1),
             (0, 0, 0)))
        dxp = placed if dxp is None else dxp + placed
    dx = lax.slice(dxp, (0, pt, pl, 0), (B, pt + H, pl + W, C))
    return (dx.astype(x.dtype),)


_max_pool_mask.defvjp(_max_pool_mask_fwd, _max_pool_mask_bwd)


def max_pool(x: Array, window, strides, pads: Pads2D, *,
             impl: str = "sns") -> Array:
    """NHWC max pool with explicit spatial pads ((top,bottom),(left,right)).
    impl: "sns" (XLA select-and-scatter backward) | "mask" (argmax-
    equality-mask backward; see module docstring)."""
    if impl == "sns":
        return _reduce_max(x, window, strides, pads)
    if impl == "mask":
        return _max_pool_mask(x, tuple(window), tuple(strides),
                              (tuple(pads[0]), tuple(pads[1])))
    raise ValueError(f"max_pool impl {impl!r} not in {MAX_IMPLS}")


# ---------------------------------------------------------------------------
# Avg pool
# ---------------------------------------------------------------------------

def avg_pool(x: Array, window, strides, pads: Pads2D, *,
             impl: str = "window") -> Array:
    """NHWC average pool, divisor counting in-bounds elements only.
    impl: "window" (reduce_window sum) | "conv" (depthwise ones-kernel
    conv; backward is a transposed conv)."""
    cnt = inbounds_count(x, window, strides, pads)
    if impl == "window":
        return _reduce_sum(x, window, strides, pads) / cnt
    if impl == "conv":
        kh, kw = window
        c = x.shape[-1]
        ones = jnp.ones((kh, kw, 1, c), x.dtype)
        s = lax.conv_general_dilated(
            x, ones, window_strides=tuple(strides),
            padding=(tuple(pads[0]), tuple(pads[1])),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c)
        return s.astype(x.dtype) / cnt
    raise ValueError(f"avg_pool impl {impl!r} not in {AVG_IMPLS}")


# ---------------------------------------------------------------------------
# Measured dispatch (the select_attention_impl pattern)
# ---------------------------------------------------------------------------

def _count_pooling_impl(impl: str) -> None:
    from ..optimize.metrics import registry
    registry().counter(
        "pooling_impl_selected_total",
        "Pooling implementations chosen at dispatch (trace) time",
    ).labels(impl=impl).inc()


def select_pooling_impl(pooling_type: str, window, strides, *,
                        requested: Optional[str] = None) -> str:
    """Pick the implementation for one pooling call, increment
    `pooling_impl_selected_total{impl=}`, and return the choice. Runs at
    TRACE time (static shapes), so the counter counts selections, not
    per-step executions — same contract as select_attention_impl.

    Rule (measured A/B, docs/perf_googlenet.md round 6), per backend
    like the attention rule:

      * max on CPU → "mask": 3.4-4x faster than the S&S expansion at
        GoogLeNet's pool geometries op-level, +5% whole-model
        (85.7 -> 81.5 s/step, b8 bf16, 2026-08-05).
      * max on TPU → "sns": the round-5 profiled baseline; "mask" is
        UNMEASURED on TPU: a chip run that measures a win there flips
        this default.
      * avg → "window" everywhere: the depthwise-conv formulation lost
        its CPU A/B by 270x (XLA:CPU's grouped conv; numbers in the
        round-6 doc) and is untested on TPU.

    The alternatives stay selectable per layer (pooling_impl="mask" /
    "conv"). "mask" is portable lax: if it ever fails to lower, the
    step's compile fails — nothing swaps in "sns" behind it."""
    if pooling_type == "max":
        impls = MAX_IMPLS
        default = "mask" if jax.default_backend() == "cpu" else "sns"
    elif pooling_type == "avg":
        impls, default = AVG_IMPLS, "window"
    else:
        raise ValueError(f"no impl dispatch for pooling type "
                         f"{pooling_type!r}")
    req = None if requested in (None, "auto") else requested
    if req is not None and req not in impls:
        raise ValueError(f"pooling impl {requested!r} not in "
                         f"{impls + ('auto',)} for {pooling_type} pooling")
    choice = req or default
    _count_pooling_impl(f"{pooling_type}_{choice}")
    return choice


def register_metrics() -> None:
    """Pre-register the pooling dispatch counter family so a scrape
    BEFORE the first trace already exposes every label at 0."""
    from ..optimize.metrics import registry
    fam = registry().counter(
        "pooling_impl_selected_total",
        "Pooling implementations chosen at dispatch (trace) time")
    for pt, impls in (("max", MAX_IMPLS), ("avg", AVG_IMPLS)):
        for impl in impls:
            fam.labels(impl=f"{pt}_{impl}")
