"""Fused flash-attention Pallas kernel — forward AND backward.

Why: blockwise attention (ops/attention.py) topped out at ~0.200 est-MFU
at seq 16k (docs/perf_attention.md): the lax-scan
online softmax round-trips m/l/acc through HBM between small block
matmuls and leaves the MXU idle. The FlashAttention formulation (Dao et
al., 2022) keeps the whole QK^T → online softmax → PV chain for one
query block in VMEM across the entire KV sweep; the backward
(recompute-based, Dao et al. Alg. 4) never materializes the [Tq, Tk]
probability matrix either. A/B numbers live in docs/perf_attention.md;
the dispatch rule that consumes them lives in
ops/attention.py:select_attention_impl.

Layout: the public wrapper takes [batch, time, heads, head_dim] like
dense_attention, folds (batch, heads) into one grid axis, and pads
head_dim to the 128-lane multiple (pad/slice sit OUTSIDE the
custom_vjp, so autodiff handles them). Grid is (batch*heads, q_blocks,
kv_blocks) with KV innermost; m/l/acc live in VMEM scratch and persist
across the KV sweep (TPU grids iterate the last axis innermost).

Positions are passed as int32 ARRAYS, not static python ints: the ring
path (ring_self_attention) offsets KV positions by a TRACED
`axis_index`, so causal masking must compare data, not trace-time
constants. Causal block-skipping still works — `@pl.when` predicates
the whole inner block on `min(kv_pos) <= max(q_pos)`, which on TPU
skips the MXU work for strictly-upper blocks.

The kernel also returns the log-sum-exp per query row (NEG sentinel for
fully-masked rows, matching dense_attention's zero-output convention),
and the custom_vjp accepts a cotangent FOR the lse output: the ring
composition differentiates through the per-hop softmax merge
o = (o1*w1 + o2*w2)/(w1+w2), which reads lse. The lse cotangent folds
into ds = p * (dp - di + g_lse) in the backward kernels.

Autodiff: pallas_call is not differentiable, so `_flash` carries a
custom_vjp (the `lrn` precedent in pallas_kernels.py); forward residuals
are (inputs, o, lse) and the backward runs two more Pallas kernels —
dk/dv with the KV axis as the parallel grid dim, then dq with the Q
axis parallel — both recomputing s and p blockwise from the lse
residual. di = rowsum(o * do) is precomputed outside the kernels.

Gating mirrors lrn: `interpret=True` runs the same kernels on CPU for
tests; the TPU fast path is guarded by flash_attention_supported
(geometry/VMEM) + flash_attention_available (the backend is a TPU).
There is no compile probe and no fallback: a geometry the gate admits
and Mosaic refuses fails the caller's compile with Mosaic's message.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .pallas_kernels import pad_axis_to

# Cross-file trace surface (analysis/boundaries.py): the serving
# kernels are dispatched inside the decoder's jitted step and prefill
# (serving/decode.py _step_pure, _prefill_pure), so the JL0xx/JL2xx
# purity rules must treat them as traced roots here.
__traced__ = ("paged_decode_attention", "prefill_attention",
              "latent_decode_attention")

NEG = -1e30  # mask sentinel; matches ops/attention.py (finite: -inf NaNs grads)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
_LANE = 128          # TPU lane width: head_dim padded to a multiple
_VMEM_BUDGET = 8 * 1024 * 1024  # conservative half of ~16MB/core


def pick_kernel_block(t: int, want: int) -> int:
    """Largest divisor of t that is <= want (t >= 1). Exact tiling keeps
    the kernels free of per-block bounds masking."""
    b = max(1, min(want, t))
    while t % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# Kernels. Shared ref order: positions, mask, tensors. Blocks are
# [1, qb, d] / [1, kb, d] (leading grid axis folded batch*heads);
# q positions are a [tq, 1] column and kv positions a [1, tk] row so the
# causal compare broadcasts to [qb, kb] without an in-kernel transpose.
# ---------------------------------------------------------------------------

def _scores(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref, ks_ref, scale,
            causal, use_mask, use_segs, window=None):
    """s = scale * q @ k^T with causal/key/segment masking applied. f32.

    Segment masking reuses the position-array layout: q segments are a
    [qb, 1] column block and kv segments a [1, kb] row block, so the
    equality compare broadcasts to [qb, kb] without a transpose — the
    varlen/packed-batch mask (multiple documents per row; cross-segment
    attention forbidden)."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where(kp_ref[:] <= qp_ref[:], s, NEG)
    if window is not None:
        s = jnp.where(qp_ref[:] - kp_ref[:] < window, s, NEG)
    if use_mask:
        s = jnp.where(km_ref[0] > 0, s, NEG)
    if use_segs:
        s = jnp.where(qs_ref[0] == ks_ref[0], s, NEG)
    return s


def _skip_when(causal, use_segs, qp_ref, kp_ref, qs_ref, ks_ref, q_block,
               body, window=None):
    """Run `body` — under a block-skip predicate when causal and/or
    segment-masked. Causal: the whole KV block is strictly above the
    diagonal iff min(kv_pos) > max(q_pos); positions are traced data, so
    this is a runtime `pl.when`, not a trace-time grid trim (the ring
    path's offsets are traced). Segments: a tile contributes nothing
    when the q tile's segment-id RANGE cannot intersect the kv tile's —
    conservative for arbitrary ids, exact for the packed case (ids
    monotone within a row), and it skips every fully-cross-segment tile
    of a packed batch. Window: the whole KV block lies behind the window
    of every query of the tile iff the tile's first query is `window` or
    more past the block's last key (positions monotone within a block)."""
    from jax.experimental import pallas as pl

    pred = None
    if causal:
        pred = kp_ref[0, 0] <= qp_ref[q_block - 1, 0]
    if use_segs:
        qs, ks = qs_ref[0], ks_ref[0]
        seg_pred = (jnp.min(ks) <= jnp.max(qs)) & \
            (jnp.max(ks) >= jnp.min(qs))
        pred = seg_pred if pred is None else pred & seg_pred
    if window is not None:
        near = qp_ref[0, 0] - kp_ref[0, kp_ref.shape[1] - 1] < window
        pred = near if pred is None else pred & near
    if pred is not None:
        @pl.when(pred)
        def _():
            body()
    else:
        body()


def _fwd_kernel(qp_ref, kp_ref, km_ref, qs_ref, ks_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale, causal,
                use_mask, use_segs, nk, window=None):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)  # kv block index (innermost)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        l_ref[:] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[:] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def compute():
        s = _scores(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref, ks_ref,
                    scale, causal, use_mask, use_segs, window)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        # Fully-masked so far → m_next == NEG → force p to 0 (exp(0)=1
        # otherwise, counting masked entries into l).
        p = jnp.where(m_next <= NEG / 2, 0.0, jnp.exp(s - m_next))
        l_ref[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_next
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv

    _skip_when(causal, use_segs, qp_ref, kp_ref, qs_ref, ks_ref,
               q_ref.shape[1], compute, window)

    @pl.when(j == nk - 1)
    def _():
        l, m = l_ref[:], m_ref[:]
        safe = jnp.where(l > 0, l, 1.0)
        # Fully-masked rows: zero output (dense_attention convention) and
        # an lse of NEG so the ring merge treats the hop as weight-0.
        o_ref[0] = (acc_ref[:] * jnp.where(l > 0, 1.0 / safe, 0.0)).astype(
            o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m + jnp.log(safe), NEG)


def _recompute_p(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref, ks_ref,
                 lse_ref, scale, causal, use_mask, use_segs):
    """Rebuild the probability block from the lse residual; guard
    fully-masked rows (lse == NEG sentinel) to exact zeros."""
    s = _scores(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref, ks_ref,
                scale, causal, use_mask, use_segs)
    lse = lse_ref[0]  # [qb, 1]
    p = jnp.where(lse <= NEG / 2, 0.0, jnp.exp(s - lse))
    return p


def _bwd_dkv_kernel(qp_ref, kp_ref, km_ref, qs_ref, ks_ref, q_ref, k_ref,
                    v_ref, do_ref, lse_ref, di_ref, gl_ref, dk_ref, dv_ref,
                    dk_acc, dv_acc, *, scale, causal, use_mask, use_segs,
                    nq, acc_dtype):
    from jax.experimental import pallas as pl

    jq = pl.program_id(2)  # q block index (innermost; KV block is parallel)

    @pl.when(jq == 0)
    def _():
        dk_acc[:] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[:] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    def compute():
        p = _recompute_p(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref,
                         ks_ref, lse_ref, scale, causal, use_mask,
                         use_segs)
        do = do_ref[0]
        # acc_dtype is the bwd accumulate knob (f32 default; the bf16
        # study in docs/perf_attention.md measures the drift/speed
        # trade): both the running scratch and the per-block matmul
        # accumulate in it.
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype).astype(dv_acc.dtype)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # g_lse folds in here: d lse / d s = p, so the lse cotangent adds
        # p * g_lse — the term the ring's softmax-merge backward needs.
        ds = p * (dp - di_ref[0] + gl_ref[0])
        dk_acc[:] = dk_acc[:] + (jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype) * scale).astype(dk_acc.dtype)

    _skip_when(causal, use_segs, qp_ref, kp_ref, qs_ref, ks_ref,
               q_ref.shape[1], compute)

    @pl.when(jq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(qp_ref, kp_ref, km_ref, qs_ref, ks_ref, q_ref, k_ref,
                   v_ref, do_ref, lse_ref, di_ref, gl_ref, dq_ref, dq_acc,
                   *, scale, causal, use_mask, use_segs, nk, acc_dtype):
    from jax.experimental import pallas as pl

    jk = pl.program_id(2)  # kv block index (innermost; Q block is parallel)

    @pl.when(jk == 0)
    def _():
        dq_acc[:] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    def compute():
        p = _recompute_p(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref,
                         ks_ref, lse_ref, scale, causal, use_mask,
                         use_segs)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0] + gl_ref[0])
        dq_acc[:] = dq_acc[:] + (jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype) * scale).astype(dq_acc.dtype)

    _skip_when(causal, use_segs, qp_ref, kp_ref, qs_ref, ks_ref,
               q_ref.shape[1], compute)

    @pl.when(jk == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers over [bh, t, d] arrays.
# ---------------------------------------------------------------------------

def _km_spec(pl, kb, use_mask, kv_axis):
    """key-mask BlockSpec over a [bh, 1, tk] row array (the kv-segment
    layout): Mosaic wants a block's last two dims divisible by (8, 128)
    or equal to the array's, and a (1, kb) block of a [bh, tk] array is
    neither in its second-to-last dim — the unit axis makes it equal.
    When no mask the array is a shared [1, 1, tk] ones row and every bh
    grid step maps to row 0."""
    bh = (lambda i: i) if use_mask else (lambda i: 0)
    return pl.BlockSpec((1, 1, kb), lambda i, j, k:
                        (bh(i), 0, (j, k)[kv_axis - 1]))


def _seg_specs(pl, qb, kb, use_segs, q_axis, kv_axis):
    """segment-id BlockSpecs: qs is a [bh, tq, 1] column array and ks a
    [bh, 1, tk] row array, so in-kernel qs_ref[0]/ks_ref[0] broadcast to
    [qb, kb] like the position arrays. When segments are off both are
    shared [1, ...] zero arrays and every bh grid step maps to row 0
    (the _km_spec trick)."""
    bh = (lambda i: i) if use_segs else (lambda i: 0)
    qspec = pl.BlockSpec((1, qb, 1),
                         lambda i, j, k: (bh(i), (j, k)[q_axis - 1], 0))
    kspec = pl.BlockSpec((1, 1, kb),
                         lambda i, j, k: (bh(i), 0, (j, k)[kv_axis - 1]))
    return qspec, kspec


@jax.named_scope("flash_attention_fwd")
def _fwd_call(q3, k3, v3, km, qp, kp, qs, ks, scale, causal, use_mask,
              use_segs, qb, kb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q3.shape
    tk = k3.shape[1]
    nq, nk = tq // qb, tk // kb
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             use_mask=use_mask, use_segs=use_segs, nk=nk)
    qs_spec, ks_spec = _seg_specs(pl, qb, kb, use_segs, q_axis=1, kv_axis=2)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((qb, 1), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, kb), lambda i, j, k: (0, k)),
            _km_spec(pl, kb, use_mask, kv_axis=2),
            qs_spec,
            ks_spec,
            pl.BlockSpec((1, qb, d), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, qb, d), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((1, qb, 1), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),   # running max m
            pltpu.VMEM((qb, 1), jnp.float32),   # running sum l
            pltpu.VMEM((qb, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qp, kp, km, qs, ks, q3, k3, v3)


def _bwd_calls(q3, k3, v3, km, qp, kp, qs, ks, o, lse, do, dlse,
               scale, causal, use_mask, use_segs, qb, kb, interpret,
               bwd_acc_dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q3.shape
    tk = k3.shape[1]
    nq, nk = tq // qb, tk // kb
    acc_dt = jnp.dtype(bwd_acc_dtype)
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                 keepdims=True)               # [bh, tq, 1]
    gl = dlse.astype(jnp.float32)             # lse cotangent [bh, tq, 1]

    # dk/dv: grid (bh, nk, nq) — KV block parallel, Q sweep innermost.
    qrow = lambda i, j, k: (i, k, 0)          # q-indexed rows by inner dim
    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale,
                                 causal=causal, use_mask=use_mask,
                                 use_segs=use_segs, nq=nq, acc_dtype=acc_dt)
    qs_dkv, ks_dkv = _seg_specs(pl, qb, kb, use_segs, q_axis=2, kv_axis=1)
    dkv_call = pl.pallas_call(
        dkv_kern,
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((qb, 1), lambda i, j, k: (k, 0)),
            pl.BlockSpec((1, kb), lambda i, j, k: (0, j)),
            _km_spec(pl, kb, use_mask, kv_axis=1),
            qs_dkv,
            ks_dkv,
            pl.BlockSpec((1, qb, d), qrow),                       # q
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, j, 0)),  # k
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, j, 0)),  # v
            pl.BlockSpec((1, qb, d), qrow),                       # do
            pl.BlockSpec((1, qb, 1), qrow),                       # lse
            pl.BlockSpec((1, qb, 1), qrow),                       # di
            pl.BlockSpec((1, qb, 1), qrow),                       # g_lse
        ],
        out_specs=[
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kb, d), acc_dt),
            pltpu.VMEM((kb, d), acc_dt),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )
    with jax.named_scope("flash_attention_bwd_dkv"):
        dk, dv = dkv_call(qp, kp, km, qs, ks, q3, k3, v3, do, lse, di, gl)

    # dq: grid (bh, nq, nk) — Q block parallel, KV sweep innermost.
    qblk = lambda i, j, k: (i, j, 0)
    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                use_mask=use_mask, use_segs=use_segs,
                                nk=nk, acc_dtype=acc_dt)
    qs_dq, ks_dq = _seg_specs(pl, qb, kb, use_segs, q_axis=1, kv_axis=2)
    dq_call = pl.pallas_call(
        dq_kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((qb, 1), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, kb), lambda i, j, k: (0, k)),
            _km_spec(pl, kb, use_mask, kv_axis=2),
            qs_dq,
            ks_dq,
            pl.BlockSpec((1, qb, d), qblk),                       # q
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, k, 0)),  # k
            pl.BlockSpec((1, kb, d), lambda i, j, k: (i, k, 0)),  # v
            pl.BlockSpec((1, qb, d), qblk),                       # do
            pl.BlockSpec((1, qb, 1), qblk),                       # lse
            pl.BlockSpec((1, qb, 1), qblk),                       # di
            pl.BlockSpec((1, qb, 1), qblk),                       # g_lse
        ],
        out_specs=pl.BlockSpec((1, qb, d), qblk),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((qb, d), acc_dt)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )
    with jax.named_scope("flash_attention_bwd_dq"):
        dq = dq_call(qp, kp, km, qs, ks, q3, k3, v3, do, lse, di, gl)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp core over [bh, t, d].
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(8, 9, 10, 11, 12, 13, 14, 15))
def _flash(q3, k3, v3, km, qp, kp, qs, ks, scale, causal, use_mask,
           use_segs, qb, kb, interpret, bwd_acc_dtype):
    return _fwd_call(q3, k3, v3, km, qp, kp, qs, ks, scale, causal,
                     use_mask, use_segs, qb, kb, interpret)


def _flash_fwd(q3, k3, v3, km, qp, kp, qs, ks, scale, causal, use_mask,
               use_segs, qb, kb, interpret, bwd_acc_dtype):
    o, lse = _fwd_call(q3, k3, v3, km, qp, kp, qs, ks, scale, causal,
                       use_mask, use_segs, qb, kb, interpret)
    return (o, lse), (q3, k3, v3, km, qp, kp, qs, ks, o, lse)


def _flash_bwd(scale, causal, use_mask, use_segs, qb, kb, interpret,
               bwd_acc_dtype, res, cts):
    q3, k3, v3, km, qp, kp, qs, ks, o, lse = res
    do, dlse = cts
    dq, dk, dv = _bwd_calls(q3, k3, v3, km, qp, kp, qs, ks, o, lse, do,
                            dlse, scale, causal, use_mask, use_segs, qb,
                            kb, interpret, bwd_acc_dtype)
    # Mask, int32 positions and int32 segment ids are non-differentiable:
    # zero / float0.
    return (dq, dk, dv, jnp.zeros_like(km),
            np.zeros(qp.shape, jax.dtypes.float0),
            np.zeros(kp.shape, jax.dtypes.float0),
            np.zeros(qs.shape, jax.dtypes.float0),
            np.zeros(ks.shape, jax.dtypes.float0))


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = False, key_mask=None,
                    segment_ids=None, kv_segment_ids=None,
                    q_pos=None, kv_pos=None, q_block: int = 0,
                    kv_block: int = 0, interpret: bool = False,
                    with_lse: bool = False,
                    bwd_acc_dtype: str = "float32"):
    """Fused flash attention over [batch, time, heads, head_dim].

    Matches dense_attention semantics (scaling, NEG masking, zero output
    for fully-masked query rows) and is differentiable through the
    custom_vjp backward kernels. `q_pos`/`kv_pos` override the default
    arange positions for causal masking — the ring path passes traced
    global offsets here. `with_lse=True` additionally returns the
    per-row log-sum-exp as [batch, time, heads] f32 (NEG sentinel for
    fully-masked rows); its cotangent is supported.

    `segment_ids` ([batch, t_q] int, or 1-D [t_q] shared across the
    batch) packs multiple sequences into one row: attention is masked
    wherever q and kv segment ids differ, and whole cross-segment tiles
    are skipped on the block-skip path. `kv_segment_ids` defaults to
    `segment_ids` (self-attention); pass it explicitly for
    cross-attention geometries. Combine with `key_mask`/`causal` freely
    — masks compose by conjunction. Causal masking inside a packed row
    stays exact under the default global arange positions: the segment
    equality already removes cross-segment pairs, and within a segment
    global and local position orders agree.

    `bwd_acc_dtype` selects the accumulate dtype of the backward
    kernels' scratch and matmuls ("float32" default; "bfloat16" trades
    grad precision for bandwidth — drift numbers in
    docs/perf_attention.md).
    """
    b, tq, hh, d = q.shape
    tk = k.shape[1]
    qb = q_block or pick_kernel_block(tq, DEFAULT_BLOCK_Q)
    kb = kv_block or pick_kernel_block(tk, DEFAULT_BLOCK_KV)
    if tq % qb or tk % kb:
        raise ValueError(
            f"time ({tq}, {tk}) must divide blocks ({qb}, {kb})")

    def fold(a):  # [b, t, h, d] -> [b*h, t, d], lanes padded
        a3 = a.transpose(0, 2, 1, 3).reshape(b * hh, a.shape[1], d)
        return pad_axis_to(a3, 2, _LANE)

    q3, k3, v3 = fold(q), fold(k), fold(v)
    use_mask = key_mask is not None
    if use_mask:
        km = jnp.broadcast_to(key_mask.astype(jnp.float32)[:, None, :],
                              (b, hh, tk)).reshape(b * hh, 1, tk)
    else:
        km = jnp.ones((1, 1, tk), jnp.float32)
    qp = (jnp.arange(tq, dtype=jnp.int32) if q_pos is None
          else q_pos.astype(jnp.int32)).reshape(tq, 1)
    kp = (jnp.arange(tk, dtype=jnp.int32) if kv_pos is None
          else kv_pos.astype(jnp.int32)).reshape(1, tk)

    use_segs = segment_ids is not None
    if kv_segment_ids is not None and not use_segs:
        raise ValueError("kv_segment_ids requires segment_ids")
    if use_segs:
        def seg_rows(seg, t):  # -> [b*h, t] int32, broadcast over heads
            seg = jnp.asarray(seg, jnp.int32)
            if seg.ndim == 1:
                seg = jnp.broadcast_to(seg[None, :], (b, t))
            return jnp.broadcast_to(seg[:, None, :],
                                    (b, hh, t)).reshape(b * hh, t)
        seg_k = segment_ids if kv_segment_ids is None else kv_segment_ids
        qs = seg_rows(segment_ids, tq).reshape(b * hh, tq, 1)
        ks = seg_rows(seg_k, tk).reshape(b * hh, 1, tk)
    else:
        qs = jnp.zeros((1, tq, 1), jnp.int32)
        ks = jnp.zeros((1, 1, tk), jnp.int32)

    # Softmax scale uses the TRUE head_dim, not the lane-padded one.
    o3, lse3 = _flash(q3, k3, v3, km, qp, kp, qs, ks,
                      1.0 / math.sqrt(d), causal, use_mask, use_segs,
                      qb, kb, interpret, str(bwd_acc_dtype))
    o = o3[:, :, :d].reshape(b, hh, tq, d).transpose(0, 2, 1, 3)
    if not with_lse:
        return o
    lse = lse3.reshape(b, hh, tq).transpose(0, 2, 1)
    return o, lse


def flash_attention_supported(t_q: int, t_k: int, head_dim: int, *,
                              q_block: int = 0, kv_block: int = 0) -> bool:
    """Geometry gate: exact block tiling, blocks Mosaic can lay out, and
    a conservative VMEM bound for the worst kernel (dkv: q/k/v/do blocks
    + 2 [kb, d] f32 scratch + the [qb, kb] score block).

    Mosaic wants each block's last two dims divisible by (8, 128) or
    equal to the array's. qb is a second-to-last dim everywhere it
    appears ([qb, d], [qb, 1]); kb is second-to-last in the K/V blocks
    and LAST in the kv position / mask / segment rows ([1, kb]), so it
    must be a lane multiple unless one block spans the whole axis."""
    if t_q < 1 or t_k < 1 or head_dim < 1:
        return False
    qb = q_block or pick_kernel_block(t_q, DEFAULT_BLOCK_Q)
    kb = kv_block or pick_kernel_block(t_k, DEFAULT_BLOCK_KV)
    if t_q % qb or t_k % kb:
        return False
    if (qb % 8 and qb != t_q) or (kb % _LANE and kb != t_k):
        return False
    dp = head_dim + ((-head_dim) % _LANE)
    est = 4 * ((2 * qb + 4 * kb) * dp + 2 * qb * kb)
    return est <= _VMEM_BUDGET


def flash_attention_available() -> bool:
    """The compiled kernels exist only for TPU (Mosaic); everywhere else
    the answer is no, without trying to compile."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Serving kernels: grouped KV heads and a window beside the causal bound.
# Forward only (decode is inference); each takes the `name=` its
# pallas_call carries into the device trace.
# ---------------------------------------------------------------------------

PREFILL_KERNEL_MIN_KEYS = 1024   # under it one XLA fusion beats the grid


def _visible(q_pos, kv_pos, q_seg, kv_seg, window):
    """[tq, tk] bool: same segment, not after the query, inside its
    window."""
    ok = (kv_seg[None, :] == q_seg[:, None]) & \
        (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        ok &= q_pos[:, None] - kv_pos[None, :] < window
    return ok


# a key block's class, known before the block runs (`key_block_classes`)
KEY_SKIPPED, KEY_EDGE, KEY_WHOLE = 0, 1, 2

# The prefill kernel's key block, what one class covers, is the largest
# of these that divides the keys, and one grid step takes as many
# blocks as PREFILL_STEP_KEYS keys hold. Read on the chip (PERF.md, PR
# 36): the correction of the running max, the accumulator's rescale
# and a step's fixed cost are paid once a body, so a slab of 4,096
# whole keys takes 4.4 ms a layer in steps of 256 and 2.0 from 1,024
# on; an edge block costs 2.2 us a 256 keys at 256, 1.4 at 1,024, which
# outweighs the hidden pairs a wider edge block computes.
PREFILL_KEY_BLOCKS = (1024, 512, 256, 128)
PREFILL_STEP_KEYS = 2048
# The scoped VMEM the prefill kernel is compiled with (a v5e's default
# is 16 MiB of its 128), and the share of it the block sizes may plan
# for: the estimate does not see what Mosaic spills.
PREFILL_VMEM_LIMIT = 32 * 1024 * 1024
_PREFILL_VMEM_PLAN = PREFILL_VMEM_LIMIT * 3 // 4


def block_ends(a, block: int):
    """(least, greatest) entry of each `block` entries of `a` [n]."""
    a = a.reshape(-1, block)
    return a.min(axis=1), a.max(axis=1)


def key_classes_of_ends(q_pos, kv_pos, q_seg, kv_seg, window):
    """`key_block_classes` from each tile's and each block's (least,
    greatest) place and segment id, which is all it reads: four pairs
    of arrays [tiles] / [blocks]."""
    (qp_lo, qp_hi), (qs_lo, qs_hi) = ((a[:, None] for a in pair)
                                      for pair in (q_pos, q_seg))
    (kp_lo, kp_hi), (ks_lo, ks_hi) = ((a[None, :] for a in pair)
                                      for pair in (kv_pos, kv_seg))
    run = (kp_lo <= qp_hi) & (ks_lo <= qs_hi) & (ks_hi >= qs_lo)
    whole = (kp_hi <= qp_lo) & (qs_lo == qs_hi) & (ks_lo == ks_hi) \
        & (ks_lo == qs_lo)
    if window is not None:
        run = run & (qp_lo - kp_hi < window)
        whole = whole & (qp_hi - kp_lo < window)
    return run.astype("int32") + (run & whole).astype("int32")


def key_block_classes(q_pos, kv_pos, q_seg, kv_seg, window, q_block: int,
                      kv_block: int):
    """What a tile of `q_block` queries sees of each block of `kv_block`
    keys, from the operands `prefill_attention` takes (int arrays [tq] /
    [tk], NumPy's or jax's): int32 [tq / q_block, tk / kv_block] of

      KEY_SKIPPED  no pair can be visible: the block lies wholly after
                   the tile, wholly behind its window, or the two
                   ranges of segment ids cannot meet
      KEY_WHOLE    every pair is visible: the block's last key is at or
                   before the tile's first query, tile and block hold
                   one segment and the same one, and with a window the
                   tile's last query is less than `window` past the
                   block's first key
      KEY_EDGE     all else: the masks decide, pair by pair"""
    return key_classes_of_ends(
        block_ends(q_pos, q_block), block_ends(kv_pos, kv_block),
        block_ends(q_seg, q_block), block_ends(kv_seg, kv_block), window)


def _prefill_vmem_bytes(qb, step_keys, dp, dvp, itemsize, out_itemsize):
    """The prefill kernel's fast memory at these block sizes: the
    operands' and results' blocks, each twice (the pipeline's two
    buffers), the running softmax, and a step's scores in float32,
    their exponentials and those cast for the value product. A [qb, 1]
    column lies on 128 lanes, as the running max and sum do."""
    column = qb * _LANE * 4
    blocks = (qb * dp + step_keys * (dp + dvp)) * itemsize \
        + qb * dvp * out_itemsize + 3 * column + 8 * step_keys * 4
    stats = 2 * column + qb * dvp * 4
    return 2 * blocks + stats + qb * step_keys * (4 + 4 + itemsize)


def prefill_kernel_blocks(tq: int, tk: int, head_dim: int, v_dim: int, *,
                          impl: str = "auto", q_block: int = 0,
                          kv_block: int = 0, itemsize: int = 2):
    """`prefill_attention`'s rule for the arm a call takes, from its
    shapes: None for the dense arm, else ``(q_block, kv_block, parts)``
    of the kernel: queries a tile, keys a block (what a class of
    `key_block_classes` covers: the largest of PREFILL_KEY_BLOCKS that
    divides the keys and that Mosaic can lay out inside the fast memory
    the kernel may plan for), and the blocks one grid step takes (up to
    PREFILL_STEP_KEYS keys, as far as they divide the keys and fit).
    `"auto"` takes the kernel on a TPU from PREFILL_KERNEL_MIN_KEYS
    keys on; `"flash"` takes it whatever the shapes."""
    if impl not in ("auto", "flash", "dense"):
        raise ValueError(f"unknown prefill_attention impl {impl!r}")
    if impl == "dense" or (impl == "auto" and not (
            flash_attention_available() and tk >= PREFILL_KERNEL_MIN_KEYS)):
        return None
    qb = q_block or pick_kernel_block(tq, 512)
    pad = lambda n: n + (-n) % _LANE
    fits = lambda keys: _prefill_vmem_bytes(
        qb, keys, pad(head_dim), pad(v_dim), itemsize, 4) \
        <= _PREFILL_VMEM_PLAN
    for kb in (kv_block,) if kv_block else [
            b for b in PREFILL_KEY_BLOCKS if tk % b == 0] \
            or [pick_kernel_block(tk, 256)]:
        # a block's last two dims: multiples of (8, 128) or the array's
        tiles = not (tq % qb or tk % kb or (qb % 8 and qb != tq)
                     or (kb % _LANE and kb != tk))
        if impl == "flash" or (tiles and fits(kb)):
            parts = next((n for n in range(PREFILL_STEP_KEYS // kb, 1, -1)
                          if (tk // kb) % n == 0 and fits(kb * n)), 1)
            return qb, kb, parts
    return None


def _prefill_kernel(cls_ref, at_ref, qp_ref, qs_ref, kw_ref, q_ref, k_ref,
                    v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale,
                    window, nk, parts):
    """Grid (head, q tile, step of `parts` key blocks). `cls_ref` holds
    each (tile, key block)'s class, so what a block needs is known
    before it runs: a skipped one nothing, a whole one the online
    softmax alone (no position or segment is read, nothing compared or
    selected), an edge one the masks as well. A step whose blocks are
    all whole takes them as one block: one correction of the running
    max, one rescale of the accumulator. The running max and sum lie on
    `m_ref.shape[1]` lanes, every lane the row's value (128: their
    arithmetic fills its registers; 1 where a key block is no lane
    multiple). `at_ref` (the key operands' index maps read it) is the
    step whose keys a step fetches: its own, or where it runs nothing
    the nearest that does, so no copy is issued for it."""
    from jax.experimental import pallas as pl

    j, step = pl.program_id(1), pl.program_id(2)
    kb = k_ref.shape[1] // parts
    lanes = m_ref.shape[1]
    # a row's value on `lanes` lanes -> on n
    over = lambda a, n: a if lanes == 1 or n == lanes \
        else jnp.tile(a, (1, n // lanes))

    @pl.when(step == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        l_ref[:] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[:] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def add(rows, where=None):
        """Keys `rows` of the step's block into the running softmax;
        `where` = their [positions; segments] if a pair may be hidden."""
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0, rows], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if where is not None:
            kp, ks = where[0:1], where[1:2]
            s = jnp.where(kp <= qp_ref[:], s, NEG)
            if window is not None:
                s = jnp.where(qp_ref[:] - kp < window, s, NEG)
            s = jnp.where(qs_ref[:] == ks, s, NEG)
        n = s.shape[1]
        m_prev = m_ref[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - over(m_next, n))
        if where is not None:
            # a row that has seen nothing yet: m_next == NEG, and
            # exp(0) = 1 would count its hidden keys into l
            p = jnp.where(over(m_next, n) <= NEG / 2, 0.0, p)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_next
        acc_ref[:] = acc_ref[:] * over(alpha, acc_ref.shape[1]) \
            + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, rows],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    first = (j * nk + step) * parts

    def one(i, rows):
        kind = cls_ref[first + i]
        pl.when(kind == KEY_WHOLE)(lambda: add(rows))
        pl.when(kind == KEY_EDGE)(lambda: add(rows, kw_ref[i]))

    if parts == 1:
        one(0, slice(None))
    else:
        whole = cls_ref[first] == KEY_WHOLE
        for i in range(1, parts):
            whole &= cls_ref[first + i] == KEY_WHOLE
        pl.when(whole)(lambda: add(slice(None)))

        @pl.when(jnp.logical_not(whole))
        def _():
            def body(i, carry):
                one(i, pl.ds(pl.multiple_of(i * kb, kb), kb))
                return carry
            jax.lax.fori_loop(0, parts, body, 0)

    @pl.when(step == nk - 1)
    def _():
        l, m = l_ref[:, :1], m_ref[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        # a row that saw no key: zeros, and an lse of NEG so that a
        # merge weighs the part nothing
        o_ref[0] = (acc_ref[:] * jnp.where(l > 0, 1.0 / safe, 0.0)).astype(
            o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m + jnp.log(safe), NEG)


def prefill_attention(q, k, v, *, q_pos, kv_pos, q_seg, kv_seg,
                      window: Optional[int] = None,
                      scale: Optional[float] = None,
                      heads_first: bool = False, return_lse: bool = False,
                      name: str = "prefill_attention", impl: str = "auto",
                      interpret: bool = False, q_block: int = 0,
                      kv_block: int = 0):
    """Attention of one packed row of queries over keys that need not be
    the same row: a chunk of a prompt over the cached keys before it and
    its own.

      q            [tq, heads, head_dim]
      k            [tk, kv_heads, head_dim]; query head n reads KV head
                   n // (heads / kv_heads)
      v            [tk, kv_heads, v_dim]: the value's head size is its
                   own (latent attention: keys of 192, values of 128)
      scale        on the scores (default ``head_dim ** -0.5``)
      heads_first  q, k, v and the result are ``[heads, t, dim]``, the
                   kernel's own order: a caller whose products can give
                   that order spares a transposed copy of each
      return_lse   also the rows' log-sum-exp of their visible scores:
                   ``(result in float32, lse float32 [tq, heads])``
                   (heads first alike), a row that sees no key giving
                   zeros and ``NEG``: one part of a key range, to be
                   joined with the others by :func:`merge_attention`
      q_pos/kv_pos int32 [tq] / [tk]: places on one line
      q_seg/kv_seg int32: a query sees keys of its own segment only

    Query i sees key j iff the segments agree, ``kv_pos[j] <=
    q_pos[i]`` and, with `window`, ``q_pos[i] - kv_pos[j] < window``.
    The kernel knows each key block's class before it runs it
    (`key_block_classes`, one table a call for every head): it skips a
    block wholly above the diagonal, wholly behind the window or of
    other segments, and masks only in a block where a pair may be
    hidden. `impl="auto"` takes the kernel on a TPU from
    PREFILL_KERNEL_MIN_KEYS keys on (`prefill_kernel_blocks`);
    `"dense"` is the einsum arm. Returns [tq, heads, v_dim]."""
    turn = lambda a: a.transpose(1, 0, 2)
    same = lambda a: a
    # to [t, heads, dim] (the dense arm's order) and to the kernel's
    by_t, by_head = (turn, same) if heads_first else (same, turn)
    tq, hh, d = by_t(q).shape
    tk, kvh, dv = by_t(v).shape
    if hh % kvh:
        raise ValueError(f"{hh} query heads over {kvh} KV heads")
    group = hh // kvh
    blocks = prefill_kernel_blocks(tq, tk, d, dv, impl=impl, q_block=q_block,
                                   kv_block=kv_block,
                                   itemsize=jnp.dtype(q.dtype).itemsize)
    q_pos, kv_pos = q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32)
    q_seg, kv_seg = q_seg.astype(jnp.int32), kv_seg.astype(jnp.int32)
    if blocks is None:
        with jax.named_scope(name):
            q, k, v = by_t(q), by_t(k), by_t(v)
            s = jnp.einsum("qkgd,tkd->kgqt", q.reshape(tq, kvh, group, d),
                           k, preferred_element_type=jnp.float32)
            ok = _visible(q_pos, kv_pos, q_seg, kv_seg, window)
            s = s / math.sqrt(d) if scale is None else s * scale
            s = jnp.where(ok[None, None], s, NEG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgqt,tkd->qkgd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32
                           ).reshape(tq, hh, dv)
            if not return_lse:
                return by_t(o.astype(q.dtype))
            seen = ok.any(axis=1)       # the kernel's rule for a blind row
            lse = jnp.where(seen, jax.nn.logsumexp(s, axis=-1).reshape(
                hh, tq), NEG)
            return (by_t(jnp.where(seen[:, None, None], o, 0.0)),
                    lse if heads_first else lse.T)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qb, kb, parts = blocks
    if tq % qb or tk % kb:
        raise ValueError(f"times ({tq}, {tk}) must divide blocks ({qb}, "
                         f"{kb})")
    q3, k3, v3 = (pad_axis_to(by_head(a), 2, _LANE) for a in (q, k, v))
    dp, dvp = q3.shape[2], v3.shape[2]
    nq, nk, wide = tq // qb, tk // (kb * parts), kb * parts
    lanes = _LANE if kb % _LANE == 0 else 1
    with jax.named_scope(name):
        classes = key_block_classes(q_pos, kv_pos, q_seg, kv_seg, window,
                                    qb, kb)
        # the step whose keys a step fetches: its own if one of its
        # blocks runs, else the last before it that ran, else the first
        # that will
        runs = (classes.reshape(nq, nk, parts) != KEY_SKIPPED).any(axis=2)
        step = jnp.arange(nk, dtype=jnp.int32)
        before = jax.lax.cummax(jnp.where(runs, step, -1), axis=1)
        at = jnp.where(before >= 0, before,
                       jnp.argmax(runs, axis=1).astype(jnp.int32)[:, None])
        key_at = lambda i, j, s, cls, at: at[j * nk + s]
        kern = functools.partial(
            _prefill_kernel, window=window, nk=nk, parts=parts,
            scale=1.0 / math.sqrt(d) if scale is None else scale)
        o3, lse = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(hh, nq, nk),
                in_specs=[
                    pl.BlockSpec((qb, 1), lambda i, j, s, *_: (j, 0)),
                    pl.BlockSpec((qb, 1), lambda i, j, s, *_: (j, 0)),
                    pl.BlockSpec((parts, 2, kb), lambda *a: (key_at(*a), 0,
                                                            0)),
                    pl.BlockSpec((1, qb, dp), lambda i, j, s, *_: (i, j, 0)),
                    pl.BlockSpec((1, wide, dp), lambda i, *a:
                                 (i // group, key_at(i, *a), 0)),
                    pl.BlockSpec((1, wide, dvp), lambda i, *a:
                                 (i // group, key_at(i, *a), 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, qb, dvp), lambda i, j, s, *_: (i, j, 0)),
                    pl.BlockSpec((1, qb, 1), lambda i, j, s, *_: (i, j, 0)),
                ],
                scratch_shapes=[
                    pltpu.VMEM((qb, lanes), jnp.float32),   # running max
                    pltpu.VMEM((qb, lanes), jnp.float32),   # running sum
                    pltpu.VMEM((qb, dvp), jnp.float32),
                ]),
            out_shape=[
                jax.ShapeDtypeStruct((hh, tq, dvp),
                                     jnp.float32 if return_lse else q.dtype),
                jax.ShapeDtypeStruct((hh, tq, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=PREFILL_VMEM_LIMIT),
            interpret=interpret,
            name=name,
        )(classes.reshape(-1), at.reshape(-1), q_pos.reshape(tq, 1),
          q_seg.reshape(tq, 1),
          jnp.stack([kv_pos.reshape(-1, kb), kv_seg.reshape(-1, kb)], axis=1),
          q3, k3, v3)
    o = by_head(o3[:, :, :dv])
    return (o, by_head(lse)[..., 0]) if return_lse else o


def merge_attention(o, lse, o_part, lse_part):
    """Join two parts of one softmax over disjoint keys: each an
    attention result in float32 [..., dim] with its rows' log-sum-exp
    [...] (`prefill_attention`'s ``return_lse``). A part whose row saw
    no key (lse ``NEG``, result zeros) weighs nothing. -> (o, lse)."""
    both = jnp.logaddexp(lse, lse_part)
    share = lambda part: jnp.exp(part - both)[..., None]
    return o * share(lse) + o_part * share(lse_part), both


def _online_softmax_add(s, v, m_ref, l_ref, acc_ref):
    """One block into a decode kernel's running softmax: s [heads, bt]
    masked scores, v [bt, dv] with the rows no query sees zeroed (p = 0
    does not silence a NaN that a block's last owner left)."""
    m_prev = m_ref[:]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[:] = m_next
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _paged_decode_kernel(tables_ref, starts_ref, lens_ref, q_ref, kn_ref,
                         vn_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                         *, scale, bt, window, nw):
    """Grid (row, table entry). The row's own new token opens the online
    softmax (so it is never empty); each table entry that holds a key
    the row may see adds its block."""
    from jax.experimental import pallas as pl

    b, j = pl.program_id(0), pl.program_id(1)
    ln, first = lens_ref[b], starts_ref[b] + j * bt
    lo = 0 if window is None else ln - window + 1

    @pl.when(j == 0)
    def _():
        q = q_ref[...].astype(jnp.float32)
        m_ref[:] = jnp.sum(q * kn_ref[...].astype(jnp.float32), axis=1,
                           keepdims=True) * scale
        l_ref[:] = jnp.ones(l_ref.shape, l_ref.dtype)
        acc_ref[:] = jnp.broadcast_to(vn_ref[...].astype(jnp.float32),
                                      acc_ref.shape)

    @pl.when((first < ln) & (first + bt > lo))
    def _():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        at = first + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        s = jnp.where((at < ln) & (at >= lo), s, NEG)
        # a block's tail past `ln` keeps its last owner's values
        down = first + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        v = jnp.where((down < ln) & (down >= lo), v_ref[...], 0)
        _online_softmax_add(s, v, m_ref, l_ref, acc_ref)

    @pl.when(j == nw - 1)
    def _():
        o_ref[...] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def paged_decode_attention(q, k_new, v_new, arena_k, arena_v, layer: int,
                           tables, starts, lens, *,
                           window: Optional[int] = None,
                           name: str = "decode_attention",
                           impl: str = "auto", interpret: bool = False):
    """One new token a row against its paged cache, read through the
    block table inside the kernel: no view is gathered.

      q            [rows, heads, head_dim], this step's queries
      k_new, v_new [rows, kv_heads * head_dim], this step's own key and
                   value (position ``lens[r]``), not yet in the arena
      arena_k/v    [layers, blocks, block_tokens, kv_heads * head_dim]
      layer        which layer of the arena (static)
      tables       int32 [rows, w]: the row's blocks, in order
      starts       int32 [rows]: the position of the first token of a
                   row's first table entry (0 unless blocks behind a
                   window were given back)
      lens         int32 [rows]: cached tokens; positions below it hold
                   real keys

    Row r sees its own token and the cached positions p < lens[r] with,
    under `window`, lens[r] - p < window: `window` keys with its own.
    Query head n reads KV head n // (heads / kv_heads); the queries go
    in block-diagonal over the KV heads' lanes, so one product a block
    serves every head at any head size. A row reads only table entries
    that hold a key it may see: the block index stops moving past them
    and the copy is not issued again. Returns [rows, heads, head_dim]."""
    rows, hh, d = q.shape
    bt, kd = arena_k.shape[2], arena_k.shape[3]
    kvh = kd // d
    if kvh * d != kd or hh % kvh:
        raise ValueError(f"{hh} heads of {d} over an arena row of {kd}")
    group, w = hh // kvh, tables.shape[1]
    if impl not in ("auto", "paged", "dense"):
        raise ValueError(f"unknown paged_decode_attention impl {impl!r}")
    tables = tables.astype(jnp.int32)
    starts, lens = starts.astype(jnp.int32), lens.astype(jnp.int32)
    scale = 1.0 / math.sqrt(d)
    if not (impl == "paged"
            or (impl == "auto" and flash_attention_available())):
        with jax.named_scope(name):
            at = starts[:, None] + jnp.arange(w * bt, dtype=jnp.int32)
            ok = at < lens[:, None]
            if window is not None:
                ok &= lens[:, None] - at < window
            ok = jnp.concatenate([ok, jnp.ones((rows, 1), bool)], axis=1)

            def keys(arena, new):   # [rows, w*bt + 1, kvh, d]
                got = arena[layer][tables].reshape(rows, w * bt, kd)
                return jnp.concatenate([got, new[:, None]], axis=1) \
                    .reshape(rows, w * bt + 1, kvh, d)

            s = jnp.einsum("rkgd,rtkd->rkgt", q.reshape(rows, kvh, group, d),
                           keys(arena_k, k_new),
                           preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(ok[:, None, None], s, NEG), axis=-1)
            vv = keys(arena_v, v_new)
            o = jnp.einsum("rkgt,rtkd->rkgd", p.astype(vv.dtype),
                           jnp.where(ok[:, :, None, None], vv, 0),
                           preferred_element_type=jnp.float32)
            return o.reshape(rows, hh, d).astype(q.dtype)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # head n's query on the lanes of KV head n // group, zero elsewhere
    own = (jnp.arange(hh)[:, None] // group
           == jnp.arange(kvh)[None, :]).astype(q.dtype)      # [hh, kvh]
    q_bd = (q[:, :, None, :] * own[None, :, :, None]).reshape(rows, hh, kd)

    def block(b, j, tables_ref, starts_ref, lens_ref):
        held = lens_ref[b] - starts_ref[b]
        hi = jnp.maximum((held + bt - 1) // bt, 1) - 1
        lo = 0 if window is None else \
            jnp.clip((held - window + 1) // bt, 0, hi)
        return (layer, tables_ref[b, jnp.clip(j, lo, hi)], 0, 0)

    row3 = lambda b, j, *_: (b, 0, 0)
    kern = functools.partial(_paged_decode_kernel, scale=scale, bt=bt,
                             window=window, nw=w)
    with jax.named_scope(name):
        o = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(rows, w),
                in_specs=[
                    pl.BlockSpec((None, hh, kd), row3),
                    pl.BlockSpec((None, 1, kd), row3),
                    pl.BlockSpec((None, 1, kd), row3),
                    pl.BlockSpec((None, None, bt, kd), block),
                    pl.BlockSpec((None, None, bt, kd), block),
                ],
                out_specs=pl.BlockSpec((None, hh, kd), row3),
                scratch_shapes=[
                    pltpu.VMEM((hh, 1), jnp.float32),
                    pltpu.VMEM((hh, 1), jnp.float32),
                    pltpu.VMEM((hh, kd), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((rows, hh, kd), q.dtype),
            interpret=interpret,
            name=name,
        )(tables, starts, lens, q_bd, k_new[:, None, :], v_new[:, None, :],
          arena_k, arena_v)
    # back from the lanes of each head's own KV head
    return jnp.einsum("rhkd,hk->rhd", o.reshape(rows, hh, kvh, d), own)


def _latent_decode_kernel(tables_ref, lens_ref, q_ref, new_ref, c_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, scale, bt, dv, nw):
    """Grid (row, table entry), as `_paged_decode_kernel`, over a cache
    whose entry is key and value at once: every head's query meets the
    one block, and the value is the block's first `dv` lanes."""
    from jax.experimental import pallas as pl

    b, j = pl.program_id(0), pl.program_id(1)
    ln, first = lens_ref[b], j * bt

    @pl.when(j == 0)
    def _():
        new = new_ref[...].astype(jnp.float32)
        m_ref[:] = jnp.sum(q_ref[...].astype(jnp.float32) * new, axis=1,
                           keepdims=True) * scale
        l_ref[:] = jnp.ones(l_ref.shape, l_ref.dtype)
        acc_ref[:] = jnp.broadcast_to(new[:, :dv], acc_ref.shape)

    @pl.when(first < ln)
    def _():
        # a block's tail past `ln` keeps its last owner's values
        down = first + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        c = jnp.where(down < ln, c_ref[...], 0)
        s = jax.lax.dot_general(
            q_ref[...], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        at = first + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        _online_softmax_add(jnp.where(at < ln, s, NEG), c[:, :dv], m_ref,
                            l_ref, acc_ref)

    @pl.when(j == nw - 1)
    def _():
        o_ref[...] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def latent_decode_attention(q, new, arena, layer: int, tables, lens, *,
                            v_width: int, scale: float,
                            name: str = "decode_attention_latent",
                            impl: str = "auto", interpret: bool = False):
    """Latent attention in the ABSORBED form, one new token a row
    against its paged cache of latents, read through the block table
    inside the kernel. A cached entry is one vector a token that every
    head shares: it is the key whole, and its first `v_width` values are
    the value, so a block is read once and serves both products.

      q        [rows, heads, width]: each head's query in the entry's
               own space (the caller has multiplied the up-projection of
               the keys into it), zero on lanes the entry pads
      new      [rows, width], this step's own entry (position
               ``lens[r]``), not yet in the arena
      arena    [layers, blocks, block_tokens, width]
      layer    which layer of the arena (static)
      tables   int32 [rows, w]: the row's blocks, from position 0
      lens     int32 [rows]: cached tokens

    Row r sees its own token and the cached positions below lens[r]; a
    row reads only table entries that hold one. Returns the weighted
    entries [rows, heads, v_width] (the caller's up-projection of the
    values turns them into heads' outputs)."""
    rows, hh, width = q.shape
    bt, w = arena.shape[2], tables.shape[1]
    if arena.shape[3] != width or not 0 < v_width <= width:
        raise ValueError(f"queries of {width} and values of {v_width} over "
                         f"an arena entry of {arena.shape[3]}")
    if impl not in ("auto", "paged", "dense"):
        raise ValueError(f"unknown latent_decode_attention impl {impl!r}")
    tables, lens = tables.astype(jnp.int32), lens.astype(jnp.int32)
    if not (impl == "paged"
            or (impl == "auto" and flash_attention_available())):
        with jax.named_scope(name):
            ok = jnp.arange(w * bt, dtype=jnp.int32) < lens[:, None]
            ok = jnp.concatenate([ok, jnp.ones((rows, 1), bool)], axis=1)
            c = jnp.concatenate([arena[layer][tables].reshape(
                rows, w * bt, width), new[:, None]], axis=1)
            c = jnp.where(ok[:, :, None], c, 0)
            s = jnp.einsum("rhc,rtc->rht", q, c,
                           preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(ok[:, None], s, NEG), axis=-1)
            o = jnp.einsum("rht,rtc->rhc", p.astype(c.dtype),
                           c[:, :, :v_width],
                           preferred_element_type=jnp.float32)
            return o.astype(q.dtype)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def block(b, j, tables_ref, lens_ref):
        hi = jnp.maximum((lens_ref[b] + bt - 1) // bt, 1) - 1
        return (layer, tables_ref[b, jnp.minimum(j, hi)], 0, 0)

    row3 = lambda b, j, *_: (b, 0, 0)
    kern = functools.partial(_latent_decode_kernel, scale=scale, bt=bt,
                             dv=v_width, nw=w)
    with jax.named_scope(name):
        return pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(rows, w),
                in_specs=[
                    pl.BlockSpec((None, hh, width), row3),
                    pl.BlockSpec((None, 1, width), row3),
                    pl.BlockSpec((None, None, bt, width), block),
                ],
                out_specs=pl.BlockSpec((None, hh, v_width), row3),
                scratch_shapes=[
                    pltpu.VMEM((hh, 1), jnp.float32),
                    pltpu.VMEM((hh, 1), jnp.float32),
                    pltpu.VMEM((hh, v_width), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((rows, hh, v_width), q.dtype),
            interpret=interpret,
            name=name,
        )(tables, lens, q, new[:, None, :], arena)
