"""Attention ops: dense multi-head attention + ring attention for
sequence/context parallelism.

The reference predates attention entirely (SURVEY.md §5.7: its only
long-sequence devices are truncated BPTT + masking, both implemented
here) — this module is deliberate BEYOND-parity scope: long-context is
first-class on TPU, and the canonical mechanism is ring attention
(Liu et al. 2023): shard the sequence axis across the mesh, keep Q
local, rotate K/V blocks around the ring with `ppermute` over ICI, and
accumulate softmax online (flash-attention's running max/denominator),
so attention over a sequence of length N*t costs each device O(t^2 * N)
time and O(t) memory with communication fully overlappable.

`ring_self_attention` is numerically identical (up to f32 reassociation)
to dense softmax attention — tested against `dense_attention` on the
8-device CPU mesh, causal and bidirectional.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NEG = -1e30  # finite -inf stand-in: keeps exp() NaN-free in masked rows

# --------------------------------------------------------------------------
# Sequence-parallel context: while active, SelfAttentionLayer routes its
# attention through ring_self_attention over the given mesh axis instead of
# dense_attention — the switch that turns the ring kernel from a standalone
# op into a trainable network path (SequenceParallelWrapper sets it; the
# context must be active while the train step TRACES, which the wrapper
# guarantees by holding it across every jitted call).
# --------------------------------------------------------------------------

_SEQ_PARALLEL: list = []


@contextlib.contextmanager
def sequence_parallel(mesh, axis: str = "seq",
                      batch_axis: Optional[str] = None,
                      head_axis: Optional[str] = None):
    """Route attention layers through the ppermute ring while active.
    `batch_axis` optionally names a mesh axis the BATCH dim is sharded
    over (the DP half of a DP x SP mesh); `head_axis` optionally names
    one the HEAD dim is sharded over (tensor parallelism — attention is
    per-head independent, so head sharding composes with the ring for
    free)."""
    _SEQ_PARALLEL.append((mesh, axis, batch_axis, head_axis))
    try:
        yield
    finally:
        _SEQ_PARALLEL.pop()


def active_sequence_parallel():
    """(mesh, seq_axis, batch_axis, head_axis) of the innermost active
    sequence_parallel context, or None."""
    return _SEQ_PARALLEL[-1] if _SEQ_PARALLEL else None


# --------------------------------------------------------------------------
# Single-device dispatch: pallas (fused flash kernel) / blockwise / dense.
# The rule is MEASURED, not aspirational — docs/perf_attention.md holds the
# A/B behind it.
# --------------------------------------------------------------------------

ATTENTION_IMPLS = ("pallas", "blockwise", "dense")


def pick_block_size(t: int, block_size: int = 0) -> int:
    """Block size for single-device blockwise attention; 0 = dense.
    block_size: 0 = auto (blockwise once t >= 2048; probe order 512,
    1024, 256, 128 — 512 measured fastest on v5e), -1 = always dense,
    >0 = that block size whenever it divides t (including t == block,
    a single-block run)."""
    if block_size == -1:
        return 0
    if block_size > 0:
        return block_size if t % block_size == 0 else 0
    if t < 2048:
        return 0
    for blk in (512, 1024, 256, 128):
        if t % blk == 0:
            return blk
    return 0


def _pallas_ready(t_q: int, t_k: int, head_dim: int,
                  interpret: bool) -> bool:
    from . import flash_attention as fa
    if not fa.flash_attention_supported(t_q, t_k, head_dim):
        return False
    return True if interpret else fa.flash_attention_available()


def _count_attention_impl(impl: str) -> None:
    from ..optimize.metrics import registry
    registry().counter(
        "attention_kernel_selected_total",
        "Attention implementations chosen at dispatch (trace) time",
    ).labels(impl=impl).inc()


def select_attention_impl(t_q: int, head_dim: int, *,
                          requested: Optional[str] = None,
                          block_size: int = 0,
                          interpret: bool = False,
                          t_k: Optional[int] = None) -> str:
    """Pick 'pallas' | 'blockwise' | 'dense' for a single-device
    attention call, increment `attention_kernel_selected_total{impl=}`,
    and return the choice. Runs at TRACE time (shapes are static), so
    the counter counts selections, not per-step executions.

    Rule (measured A/B, docs/perf_attention.md): below t=2048 dense wins
    (blockwise/pallas overheads don't amortize); from 2048 up the fused
    Pallas kernel wins wherever it exists (a TPU backend, or
    interpret=True for CPU tests) and the geometry gate admits the
    shape, else blockwise, else dense. An explicit user block_size (> 0)
    keeps the blockwise path — the user asked for that shape;
    block_size == -1 forces dense (the pre-existing contract).
    `requested` overrides ('auto'/None = the rule). A requested 'pallas'
    that cannot be honoured raises ValueError: the caller named a
    kernel, and running another in its place would hide that it never
    ran."""
    t_k = t_q if t_k is None else t_k
    req = None if requested in (None, "auto") else requested
    if req is not None and req not in ATTENTION_IMPLS:
        raise ValueError(f"attention impl {requested!r} not in "
                         f"{ATTENTION_IMPLS + ('auto',)}")
    if req == "dense":
        choice = "dense"
    else:
        blk = pick_block_size(t_q, block_size)
        if req == "pallas" and not _pallas_ready(t_q, t_k, head_dim,
                                                 interpret):
            raise ValueError(
                f"attention impl 'pallas' requested but the fused kernel "
                f"cannot run t_q={t_q} t_k={t_k} head_dim={head_dim} on "
                f"backend {jax.default_backend()!r} "
                "(flash_attention_supported / flash_attention_available)")
        if req == "pallas":
            choice = "pallas"
        elif req == "blockwise":
            choice = "blockwise" if blk else "dense"
        elif (block_size == 0 and t_q >= 2048 and t_q == t_k
                and _pallas_ready(t_q, t_k, head_dim, interpret)):
            choice = "pallas"
        else:
            choice = "blockwise" if blk else "dense"
    _count_attention_impl(choice)
    return choice


def single_device_attention(q, k, v, *, causal: bool = False,
                            key_mask: Optional[jax.Array] = None,
                            segment_ids: Optional[jax.Array] = None,
                            impl: Optional[str] = None,
                            block_size: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Dispatching front door for unsharded attention: routes to the
    fused Pallas flash kernel, blockwise, or dense per
    select_attention_impl. Same signature/semantics as dense_attention
    plus the routing knobs; SelfAttentionLayer's single-chip path calls
    this. `segment_ids` ([batch, time] int) enables packed-batch
    attention — every impl applies the identical segment-equality mask,
    so the dispatch choice never changes the math."""
    choice = select_attention_impl(q.shape[1], q.shape[-1],
                                   requested=impl, block_size=block_size,
                                   interpret=interpret, t_k=k.shape[1])
    if choice == "pallas":
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, key_mask=key_mask,
                               segment_ids=segment_ids,
                               interpret=interpret)
    if choice == "blockwise":
        blk = pick_block_size(q.shape[1], block_size)
        return blockwise_attention(q, k, v, causal=causal,
                                   key_mask=key_mask,
                                   segment_ids=segment_ids, q_block=blk,
                                   kv_block=blk)
    return dense_attention(q, k, v, causal=causal, key_mask=key_mask,
                           segment_ids=segment_ids)


def dense_attention(q, k, v, *, causal: bool = False,
                    key_mask: Optional[jax.Array] = None,
                    segment_ids: Optional[jax.Array] = None,
                    kv_segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Plain softmax attention. q/k/v: [batch, time, heads, head_dim];
    key_mask: [batch, time_k] 1.0 = real key; segment_ids:
    [batch, time_q] int packed-batch ids (attention masked where q and
    kv ids differ; kv_segment_ids defaults to segment_ids). f32 softmax
    accumulation."""
    d = q.shape[-1]
    # accumulate in at LEAST f32, but never demote f64 (gradient checks
    # and x64 runs must keep full precision)
    acc = jnp.promote_types(q.dtype, jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(acc),
                        k.astype(acc)) / np.sqrt(d)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        scores = jnp.where(mask[None, None], scores, NEG)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :] > 0, scores, NEG)
    if segment_ids is not None:
        q_seg = jnp.asarray(segment_ids, jnp.int32)
        k_seg = (q_seg if kv_segment_ids is None
                 else jnp.asarray(kv_segment_ids, jnp.int32))
        scores = jnp.where(
            q_seg[:, None, :, None] == k_seg[:, None, None, :],
            scores, NEG)
    elif kv_segment_ids is not None:
        raise ValueError("kv_segment_ids requires segment_ids")
    p = jax.nn.softmax(scores, axis=-1)
    # a query with NO valid keys (all masked) outputs ZERO, not the
    # uniform average softmax would produce over the NEG sentinels —
    # matching ring attention's accumulate-nothing behavior
    any_valid = scores.max(-1, keepdims=True) > NEG / 2
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def blockwise_attention(q, k, v, *, causal: bool = False,
                        key_mask: Optional[jax.Array] = None,
                        segment_ids: Optional[jax.Array] = None,
                        q_block: int = 1024,
                        kv_block: int = 1024) -> jax.Array:
    """Memory-efficient (flash-style) attention on ONE device: identical
    math to dense_attention but never materializes the [T, T] score
    matrix — an online-softmax accumulation over K/V blocks (the Rabe &
    Staats / flash-attention recipe, same running max/denominator as the
    ring kernel, which is this op's multi-device analog). Peak live
    memory is O(T * block) instead of O(T^2).

    Causal runs skip the strictly-upper-triangular blocks entirely (the
    outer q-block loop is a static python loop, so each q block scans
    only the <= diagonal kv blocks — about half the FLOPs of the masked
    dense form). The kv-block body is jax.checkpoint'ed: the backward
    pass recomputes block scores instead of saving them, which is what
    keeps TRAINING memory sub-quadratic too.

    q/k/v: [batch, time, heads, head_dim]; key_mask: [batch, time_k];
    segment_ids: [batch, time] int packed-batch ids (same semantics as
    dense_attention). Requires time % q_block == 0 and
    time % kv_block == 0 (callers fall back to dense_attention
    otherwise)."""
    b, t, h, d = q.shape
    if t % q_block or t % kv_block:
        raise ValueError(f"time {t} must divide q_block={q_block} and "
                         f"kv_block={kv_block}")
    nq, nk = t // q_block, t // kv_block
    acc = jnp.promote_types(q.dtype, jnp.float32)
    qf = (q.astype(acc) / np.sqrt(d)).reshape(b, nq, q_block, h, d)
    kb = k.reshape(b, nk, kv_block, h, d)
    vb = v.reshape(b, nk, kv_block, h, d)
    kmb = None if key_mask is None else key_mask.reshape(b, nk, kv_block)
    if segment_ids is None:
        sqb = skb = None
    else:
        seg = jnp.asarray(segment_ids, jnp.int32)
        if seg.ndim == 1:
            seg = jnp.broadcast_to(seg[None, :], (b, t))
        sqb = seg.reshape(b, nq, q_block)
        skb = seg.reshape(b, nk, kv_block)

    def kv_step(qi, q_pos0, qseg_i):
        """Scan body over kv blocks for one q block (checkpointed)."""

        @jax.checkpoint
        def body(carry, blk):
            m, l, o = carry
            k_blk, v_blk, km_blk, ks_blk, kv_pos0 = blk
            scores = jnp.einsum("bqhd,bkhd->bhqk", qi, k_blk.astype(acc))
            if causal:
                q_pos = q_pos0 + jnp.arange(q_block)
                kv_pos = kv_pos0 + jnp.arange(kv_block)
                valid = kv_pos[None, :] <= q_pos[:, None]
                scores = jnp.where(valid[None, None], scores, NEG)
            if km_blk is not None:
                scores = jnp.where(km_blk[:, None, None, :] > 0, scores,
                                   NEG)
            if ks_blk is not None:
                same = qseg_i[:, :, None] == ks_blk[:, None, :]
                scores = jnp.where(same[:, None], scores, NEG)
            s_max = scores.max(-1)
            new_m = jnp.maximum(m, s_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(scores - new_m[..., None])
            p = jnp.where(new_m[..., None] <= NEG / 2,
                          jnp.zeros_like(p), p)
            l = l * corr + p.sum(-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_blk.astype(acc))
            return (new_m, l, o), None

        return body

    outs = []
    for i in range(nq):  # static loop: causal sees only blocks <= diag
        qi = qf[:, i]
        q_pos0 = i * q_block
        hi = nk if not causal else \
            min(nk, (q_pos0 + q_block + kv_block - 1) // kv_block)
        init = (jnp.full((b, h, q_block), NEG, acc),
                jnp.zeros((b, h, q_block), acc),
                jnp.zeros((b, h, q_block, d), acc))
        # The scan xs carry only the arrays that exist; `wrap` splices
        # Nones back into the fixed body slot order (scan xs must be
        # arrays, not Nones).
        parts = [jnp.swapaxes(kb[:, :hi], 0, 1),
                 jnp.swapaxes(vb[:, :hi], 0, 1)]
        if kmb is not None:
            parts.append(jnp.swapaxes(kmb[:, :hi], 0, 1))
        if skb is not None:
            parts.append(jnp.swapaxes(skb[:, :hi], 0, 1))
        parts.append(jnp.arange(hi) * kv_block)
        body = kv_step(qi, q_pos0, None if sqb is None else sqb[:, i])
        has_km, has_seg = kmb is not None, skb is not None

        def wrap(c, x, body=body, has_km=has_km, has_seg=has_seg):
            it = iter(x)
            k_x, v_x = next(it), next(it)
            km_x = next(it) if has_km else None
            ks_x = next(it) if has_seg else None
            return body(c, (k_x, v_x, km_x, ks_x, next(it)))

        (m, l, o), _ = jax.lax.scan(wrap, init, tuple(parts))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.transpose(out, (0, 2, 1, 3)))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _ring_body(axis: str, n_dev: int, t_loc: int, causal: bool,
               block_size: int = 0):
    """Per-device ring loop (runs inside shard_map). With block_size > 0
    (dividing t_loc), each hop's K/V block is consumed in blockwise
    sub-blocks through a checkpointed scan — the single-device
    blockwise_attention recipe composed INSIDE the ring, so per-device
    live memory is O(t_loc x block) instead of the [t_loc, t_loc] score
    matrix, and long-per-device sequences stay trainable."""

    def fn(q, k, v, key_mask):
        # q/k/v local blocks [b, t_loc, h, d]; key_mask [b, t_loc] or None
        d = q.shape[-1]
        my = jax.lax.axis_index(axis)
        acc = jnp.promote_types(q.dtype, jnp.float32)
        qf = q.astype(acc) / np.sqrt(d)
        b, _, h, _ = q.shape
        m = jnp.full((b, h, t_loc), NEG, acc)
        l = jnp.zeros((b, h, t_loc), acc)
        o = jnp.zeros((b, h, t_loc, q.shape[-1]), acc)
        q_pos = my * t_loc + jnp.arange(t_loc)

        def online_update(m, l, o, k_sub, v_sub, km_sub, kv_pos):
            """One K/V sub-block folded into the (m, l, o) running
            softmax state — the shared flash/ring accumulation."""
            scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                                k_sub.astype(acc))
            if causal:
                valid = kv_pos[None, :] <= q_pos[:, None]
                scores = jnp.where(valid[None, None], scores, NEG)
            if km_sub is not None:
                scores = jnp.where(km_sub[:, None, None, :] > 0, scores,
                                   NEG)
            s_max = scores.max(-1)
            new_m = jnp.maximum(m, s_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(scores - new_m[..., None])
            # exp(NEG - new_m) underflows to exactly 0 for any realistic
            # new_m, so fully-masked columns contribute nothing; rows
            # with new_m == NEG (nothing valid yet) keep l = 0 via the
            # explicit wipe below
            p = jnp.where(new_m[..., None] <= NEG / 2,
                          jnp.zeros_like(p), p)
            l = l * corr + p.sum(-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_sub.astype(acc))
            return new_m, l, o

        def step(s, carry):
            m, l, o, k_blk, v_blk, km_blk = carry
            src = (my - s) % n_dev  # which device's block we now hold
            kv_pos0 = src * t_loc
            if block_size and block_size < t_loc:
                nb = t_loc // block_size
                kb = k_blk.reshape(b, nb, block_size, h, d)
                vb = v_blk.reshape(b, nb, block_size, h, d)
                kmb = None if km_blk is None else \
                    km_blk.reshape(b, nb, block_size)

                @jax.checkpoint
                def sub(carry, xs):
                    mm, ll, oo = carry
                    if kmb is None:
                        k_s, v_s, j = xs
                        km_s = None
                    else:
                        k_s, v_s, km_s, j = xs
                    kv_pos = kv_pos0 + j * block_size + \
                        jnp.arange(block_size)
                    return online_update(mm, ll, oo, k_s, v_s, km_s,
                                         kv_pos), None

                xs = (jnp.swapaxes(kb, 0, 1), jnp.swapaxes(vb, 0, 1)) \
                    + (() if kmb is None else (jnp.swapaxes(kmb, 0, 1),)) \
                    + (jnp.arange(nb),)
                (m, l, o), _ = jax.lax.scan(sub, (m, l, o), xs)
            else:
                m, l, o = online_update(
                    m, l, o, k_blk, v_blk, km_blk,
                    kv_pos0 + jnp.arange(t_loc))
            if s < n_dev - 1:  # the last block is never needed again
                perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
                k_blk = jax.lax.ppermute(k_blk, axis, perm)
                v_blk = jax.lax.ppermute(v_blk, axis, perm)
                if km_blk is not None:
                    km_blk = jax.lax.ppermute(km_blk, axis, perm)
            return m, l, o, k_blk, v_blk, km_blk

        carry = (m, l, o, k, v, key_mask)
        # n_dev is static: unrolled python loop keeps ppermute schedules
        # visible to XLA's latency-hiding scheduler
        for s in range(n_dev):
            carry = step(s, carry)
        m, l, o, _, _, _ = carry
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

    return fn


def _ring_body_flash(axis: str, n_dev: int, t_loc: int, causal: bool,
                     q_block: int, kv_block: int, interpret: bool):
    """Fused-kernel ring inner step (runs inside shard_map): each hop
    runs the Pallas flash kernel over the local Q against the visiting
    K/V block — with KV positions offset by the TRACED source index, so
    causal masking and the kernel's block-skip predicate see global
    coordinates — then merges the hop's normalized (o, lse) pair into
    the running accumulator:

        new = max(lse_acc, lse_hop); w_i = exp(lse_i - new)
        o_acc = (o_acc*w_acc + o_hop*w_hop) / (w_acc + w_hop)
        lse_acc = new + log(w_acc + w_hop)

    which is exact softmax reassociation (each o is normalized w.r.t.
    its own lse). Fully-masked hops come back as (0, NEG) and merge as
    weight-0; rows masked across ALL hops output zero, matching
    dense_attention. Differentiable: the merge consumes lse, whose
    cotangent the kernel's custom_vjp supports (ds += p * g_lse)."""

    def fn(q, k, v, key_mask):
        from .flash_attention import flash_attention
        b, _, h, d = q.shape
        my = jax.lax.axis_index(axis)
        q_pos = my * t_loc + jnp.arange(t_loc, dtype=jnp.int32)
        o_acc = jnp.zeros((b, t_loc, h, d), jnp.float32)
        lse_acc = jnp.full((b, t_loc, h), NEG, jnp.float32)
        k_blk, v_blk, km_blk = k, v, key_mask
        for s in range(n_dev):  # static unroll (see _ring_body)
            src = (my - s) % n_dev
            kv_pos = src * t_loc + jnp.arange(t_loc, dtype=jnp.int32)
            o_hop, lse_hop = flash_attention(
                q, k_blk, v_blk, causal=causal, key_mask=km_blk,
                q_pos=q_pos, kv_pos=kv_pos, q_block=q_block,
                kv_block=kv_block, interpret=interpret, with_lse=True)
            new = jnp.maximum(lse_acc, lse_hop)
            w_acc = jnp.exp(lse_acc - new)
            w_hop = jnp.exp(lse_hop - new)
            denom = w_acc + w_hop
            o_acc = (o_acc * w_acc[..., None]
                     + o_hop.astype(jnp.float32) * w_hop[..., None]) \
                / denom[..., None]
            lse_acc = jnp.where(new <= NEG / 2, NEG,
                                new + jnp.log(denom))
            if s < n_dev - 1:
                perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
                k_blk = jax.lax.ppermute(k_blk, axis, perm)
                v_blk = jax.lax.ppermute(v_blk, axis, perm)
                if km_blk is not None:
                    km_blk = jax.lax.ppermute(km_blk, axis, perm)
        return o_acc.astype(q.dtype)

    return fn


def ring_self_attention(q, k, v, mesh, *, axis: str = "seq",
                        causal: bool = False,
                        key_mask: Optional[jax.Array] = None,
                        batch_axis: Optional[str] = None,
                        head_axis: Optional[str] = None,
                        block_size: int = 0,
                        use_flash: Optional[bool] = None,
                        flash_interpret: bool = False,
                        flash_q_block: int = 0,
                        flash_kv_block: int = 0) -> jax.Array:
    """Sequence-parallel attention: q/k/v [batch, time, heads, head_dim]
    with TIME sharded over `axis` of `mesh` (and, optionally, BATCH
    sharded over `batch_axis` — the DP x SP layout — and HEADS over
    `head_axis` — the TP third dimension; heads are independent, so the
    ring body is unchanged and each device simply holds its head slice).
    Returns the attention output with the same sharding. Fully
    differentiable: the VJP retraces the ring in reverse (ppermute
    transposes to the inverse permutation), so this is a trainable path,
    not just a forward op. See module docstring.

    `use_flash` selects the fused Pallas kernel as the per-hop inner
    step (_ring_body_flash): None = auto — on when the geometry gate
    admits the per-device shape and the kernel exists (a TPU backend, or
    flash_interpret=True for CPU tests), off otherwise, so CPU parity
    tests keep exercising the legacy scan body unchanged."""
    n_dev = int(mesh.shape[axis])
    t = q.shape[1]
    if t % n_dev:
        raise ValueError(f"time axis {t} must divide the {n_dev}-device "
                         f"'{axis}' mesh axis")
    if head_axis is not None and q.shape[2] % int(mesh.shape[head_axis]):
        raise ValueError(
            f"heads {q.shape[2]} must divide the "
            f"{int(mesh.shape[head_axis])}-device '{head_axis}' mesh axis")
    if block_size and (t // n_dev) % block_size:
        raise ValueError(
            f"per-device time {t // n_dev} must divide "
            f"block_size={block_size}")
    t_loc = t // n_dev
    if use_flash is None:
        from . import flash_attention as fa
        use_flash = (
            fa.flash_attention_supported(t_loc, t_loc, q.shape[-1],
                                         q_block=flash_q_block,
                                         kv_block=flash_kv_block)
            and (flash_interpret or fa.flash_attention_available()))
    if use_flash:
        from . import flash_attention as fa
        qb = flash_q_block or fa.pick_kernel_block(t_loc,
                                                   fa.DEFAULT_BLOCK_Q)
        kb = flash_kv_block or fa.pick_kernel_block(t_loc,
                                                    fa.DEFAULT_BLOCK_KV)
        _count_attention_impl("pallas")
        body = _ring_body_flash(axis, n_dev, t_loc, causal, qb, kb,
                                flash_interpret)
    else:
        _count_attention_impl("blockwise" if block_size else "dense")
        body = _ring_body(axis, n_dev, t_loc, causal, block_size)
    spec_qkv = P(batch_axis, axis, head_axis, None)
    # check_vma off: the ring body returns per-shard values stitched by
    # out_specs
    if key_mask is None:
        fn = jax.shard_map(lambda a, b, c: body(a, b, c, None), mesh=mesh,
                           in_specs=(spec_qkv,) * 3, out_specs=spec_qkv,
                           check_vma=False)
        return fn(q, k, v)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec_qkv, spec_qkv, spec_qkv,
                                 P(batch_axis, axis)),
                       out_specs=spec_qkv, check_vma=False)
    return fn(q, k, v, key_mask)
