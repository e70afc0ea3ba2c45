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

# Cross-file trace surface (analysis/boundaries.py): decode_attention is
# dispatched inside jitted decode steps (serving/decode.py _step_pure),
# so the JL0xx/JL2xx purity rules must treat it as a traced root here.
__traced__ = ("decode_attention",)

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
            causal, use_mask, use_segs):
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
    if use_mask:
        s = jnp.where(km_ref[0] > 0, s, NEG)
    if use_segs:
        s = jnp.where(qs_ref[0] == ks_ref[0], s, NEG)
    return s


def _skip_when(causal, use_segs, qp_ref, kp_ref, qs_ref, ks_ref, q_block,
               body):
    """Run `body` — under a block-skip predicate when causal and/or
    segment-masked. Causal: the whole KV block is strictly above the
    diagonal iff min(kv_pos) > max(q_pos); positions are traced data, so
    this is a runtime `pl.when`, not a trace-time grid trim (the ring
    path's offsets are traced). Segments: a tile contributes nothing
    when the q tile's segment-id RANGE cannot intersect the kv tile's —
    conservative for arbitrary ids, exact for the packed case (ids
    monotone within a row), and it skips every fully-cross-segment tile
    of a packed batch."""
    from jax.experimental import pallas as pl

    pred = None
    if causal:
        pred = kp_ref[0, 0] <= qp_ref[q_block - 1, 0]
    if use_segs:
        qs, ks = qs_ref[0], ks_ref[0]
        seg_pred = (jnp.min(ks) <= jnp.max(qs)) & \
            (jnp.max(ks) >= jnp.min(qs))
        pred = seg_pred if pred is None else pred & seg_pred
    if pred is not None:
        @pl.when(pred)
        def _():
            body()
    else:
        body()


def _fwd_kernel(qp_ref, kp_ref, km_ref, qs_ref, ks_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale, causal,
                use_mask, use_segs, nk):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)  # kv block index (innermost)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        l_ref[:] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[:] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def compute():
        s = _scores(q_ref, k_ref, qp_ref, kp_ref, km_ref, qs_ref, ks_ref,
                    scale, causal, use_mask, use_segs)
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
               q_ref.shape[1], compute)

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


@jax.named_scope("decode_attention")
def decode_attention(q, k, v, cache_len, *, impl: str = "auto",
                     interpret: bool = False):
    """Single-query-row attention against a growing KV cache.

    The decode-loop variant of `flash_attention`: each batch row holds
    ONE new query token attending to its first `cache_len[i]` cached
    KV positions. Inputs:

      q          [batch, 1, heads, head_dim]  — this step's query
      k, v       [batch, t_kv, heads, head_dim] — bucketed cache view
                 (t_kv is a pow2 bucket; tail rows beyond cache_len are
                 garbage and masked out here)
      cache_len  [batch] int32 — valid prefix length per row, >= 1
                 (the row INCLUDING the current token, already
                 scattered into k/v at position cache_len-1)

    Returns [batch, 1, heads, head_dim].

    `impl="flash"` routes through the flash kernel with q_block=1
    (pick_kernel_block(1, ·) == 1, so the tq=1 row tiles legally);
    `impl="dense"` is the einsum reference; `impl="auto"` picks flash
    on a TPU when the geometry gate passes. No
    backward: decode is inference-only, and the wrapper is jit-friendly
    (cache_len is a traced operand, so one executable serves every
    fill level of a given bucket).
    """
    b, tq, hh, d = q.shape
    if tq != 1:
        raise ValueError(f"decode_attention takes one query row, got {tq}")
    tk = k.shape[1]
    cache_len = jnp.asarray(cache_len, jnp.int32)
    valid = jnp.arange(tk, dtype=jnp.int32)[None, :] < cache_len[:, None]
    if impl not in ("auto", "flash", "dense"):
        raise ValueError(f"unknown decode_attention impl {impl!r}")
    use_flash = impl == "flash" or (
        impl == "auto" and flash_attention_supported(1, tk, d)
        and flash_attention_available())
    if use_flash:
        return flash_attention(q, k, v, key_mask=valid,
                               interpret=interpret)
    # Dense reference arm: f32 accumulate, NEG for masked positions.
    # A fully-masked row cannot occur (cache_len >= 1 by contract).
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    scores = jnp.where(valid[:, None, None, :], scores, NEG)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return o.astype(q.dtype)
