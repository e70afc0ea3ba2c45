"""Self-attention layer for recurrent-shaped ([batch, time, features])
data.

BEYOND-parity scope (the reference predates attention; SURVEY.md §5.7):
long-context is first-class on TPU, so the framework ships a
multi-head self-attention layer on the standard Layer SPI — configs
serialize, gradients autodiff, masks flow like every recurrent layer —
plus the sequence-parallel ring kernel in ops/attention.py for
sequences too long for one device.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ...ops.attention import (active_sequence_parallel, pick_block_size,
                              ring_self_attention, single_device_attention)
from ...quantize import matmul_any
from ...utils import serde
from .core import Layer, dropout

W_Q, W_K, W_V, W_O = "Wq", "Wk", "Wv", "Wo"
B_Q, B_K, B_V, B_O = "bq", "bk", "bv", "bo"


@serde.register
@dataclass
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over [batch, time, features]; output
    [batch, time, n_out]. `causal=True` masks future positions (the
    autoregressive/char-RNN setting); the feature mask (like every
    recurrent layer's) hides padded timesteps as attention KEYS."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 4
    causal: bool = False
    # Single-device long-context routing: 0 = auto (blockwise
    # flash-style attention once t >= 2048; block probe order 512,
    # 1024, 256, 128 — 512 measured fastest on v5e, docs/
    # perf_attention.md), -1 = always dense, >0 = that block size
    # whenever it divides t. Blockwise is bit-comparable to dense up to
    # f32 reassociation (ops/attention.py, tests/test_attention.py).
    block_size: int = 0
    # Implementation override for the single-chip path: "auto" routes
    # through ops.attention.select_attention_impl (fused Pallas flash
    # kernel on TPU once t >= 2048, else blockwise/dense per the
    # measured rule in docs/perf_attention.md); "pallas" / "blockwise" /
    # "dense" force a path ("pallas" raises when the kernel cannot run
    # the shape on this backend). The ring path picks its own
    # fused inner step (ring_self_attention use_flash auto).
    attention_impl: str = "auto"
    # Packed-batch mode (docs/perf_data_pipeline.md §PackToBucket): the
    # feature mask carries SEGMENT IDS instead of a 0/1 key mask — 0 is
    # still padding, 1..k number the sequences packed into each row.
    # Attention masks key padding (mask > 0, unchanged semantics) AND
    # forbids cross-segment pairs (segment-equality term in every impl).
    # Off by default: a plain 0/1 mask behaves identically either way
    # (all real tokens share segment 1), but the knob keeps the
    # segment-equality compare out of unpacked traces.
    packed_segments: bool = False

    def input_kind(self):
        return "rnn"

    def set_input_type(self, input_type):
        from ..conf.inputs import RecurrentType
        if not isinstance(input_type, RecurrentType):
            raise ValueError(
                f"SelfAttentionLayer needs RNN input, got {input_type}")
        if self.n_in == 0:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out={self.n_out} must divide into "
                             f"{self.n_heads} heads")
        return RecurrentType(size=self.n_out,
                             timeseries_length=input_type.timeseries_length)

    def has_params(self):
        return True

    def supports_streaming(self):
        return False  # attention needs the full sequence (rnn_time_step
        # over single steps would softmax each step against itself)

    def param_reg(self, pname):
        if pname in (W_Q, W_K, W_V, W_O):
            return (self.l1 or 0.0, self.l2 or 0.0)
        if pname in (B_Q, B_K, B_V, B_O):
            return (self.l1_bias or 0.0, self.l2_bias or 0.0)
        return (0.0, 0.0)

    def init_params(self, key, dtype=jnp.float32):
        import jax
        kq, kk, kv, ko = jax.random.split(key, 4)
        E, M = self.n_in, self.n_out
        p = {}
        for name, k_, (i, o) in ((W_Q, kq, (E, M)), (W_K, kk, (E, M)),
                                 (W_V, kv, (E, M)), (W_O, ko, (M, M))):
            p[name] = self._winit(k_, (i, o), i, o, dtype)
        for name, n in ((B_Q, M), (B_K, M), (B_V, M), (B_O, M)):
            p[name] = jnp.zeros((n,), dtype)
        return p

    def _pick_block(self, t: int) -> int:
        """Block size for single-device blockwise attention; 0 = dense.
        Policy lives in ops.attention.pick_block_size (shared with the
        dispatch rule); see the block_size field doc."""
        return pick_block_size(t, self.block_size)

    def forward(self, params, state, x, *, train=False, rng=None,
                mask=None):
        x = dropout(x, self.dropout_rate, train, rng)
        b, t, _ = x.shape
        h = self.n_heads
        d = self.n_out // h
        # matmul_any: bf16-quantized projection weights compute in bf16
        # with an fp32 epilogue; fp32 weights take the original ops.
        q = matmul_any(x, params[W_Q], params[B_Q]).reshape(b, t, h, d)
        k = matmul_any(x, params[W_K], params[B_K]).reshape(b, t, h, d)
        v = matmul_any(x, params[W_V], params[B_V]).reshape(b, t, h, d)
        seg = None
        if self.packed_segments and mask is not None:
            seg = mask.astype(jnp.int32)
        sp = active_sequence_parallel()
        use_ring = False
        if sp is not None:
            if seg is not None:
                raise ValueError(
                    "packed_segments is a single-device mode; it does "
                    "not compose with sequence_parallel (the ring has "
                    "no segment operand)")
            seq_shards = int(sp[0].shape[sp[1]])
            use_ring = t % seq_shards == 0
            if not use_ring and not getattr(
                    SelfAttentionLayer, "_warned_time_fallback", False):
                # indivisible time (e.g. a short final tBPTT window):
                # dense fallback — mathematically identical but without
                # the ring's O(T^2/N) memory property; warn once so
                # inactive sequence parallelism is visible (mirrors the
                # head-indivisible warn)
                import logging
                logging.getLogger(__name__).warning(
                    "sequence length %d does not divide the %d-way '%s' "
                    "mesh axis; attention runs unsharded (dense or "
                    "blockwise — sequence parallelism inactive for this "
                    "window)", t, seq_shards, sp[1])
                SelfAttentionLayer._warned_time_fallback = True
        if use_ring:
            # Sequence-parallel training (SequenceParallelWrapper active):
            # time is sharded over the mesh's seq axis, so attention runs
            # the ppermute ring instead of materializing [t, t] scores —
            # gradients flow back through the reversed ring. A head axis
            # (tensor parallelism) composes per-head.
            mesh, seq_axis, batch_axis, head_axis = sp
            if head_axis is not None and \
                    h % int(mesh.shape[head_axis]) != 0:
                # indivisible heads: replicate them (params may still be
                # sharded, so q/k/v all-gather before the ring) — warn
                # once so the inactive head-parallelism is visible
                if not getattr(SelfAttentionLayer,
                               "_warned_head_fallback", False):
                    import logging
                    logging.getLogger(__name__).warning(
                        "n_heads=%d does not divide the %d-way '%s' "
                        "mesh axis; attention heads replicate (tensor "
                        "parallelism inactive for the ring)",
                        h, int(mesh.shape[head_axis]), head_axis)
                    SelfAttentionLayer._warned_head_fallback = True
                head_axis = None
            # compose blockwise INSIDE the ring when the PER-DEVICE
            # slice is itself long (same policy as the single-device
            # path): live memory O(t_loc x block), not [t_loc, t_loc]
            out = ring_self_attention(q, k, v, mesh, axis=seq_axis,
                                      causal=self.causal, key_mask=mask,
                                      batch_axis=batch_axis,
                                      head_axis=head_axis,
                                      block_size=self._pick_block(
                                          t // seq_shards))
        else:
            # measured pallas/blockwise/dense dispatch + selection
            # counter (ops.attention.select_attention_impl)
            out = single_device_attention(
                q, k, v, causal=self.causal, key_mask=mask,
                segment_ids=seg,
                impl=self.attention_impl, block_size=self.block_size)
        out = out.reshape(b, t, self.n_out)
        out = matmul_any(out, params[W_O], params[B_O])
        out = self._act()(out)
        if mask is not None:
            # zero masked timesteps POST-activation (the recurrent-layer
            # convention: padded steps output exactly 0). In packed mode
            # the mask holds segment IDS (1..k), so binarize — scaling by
            # the id would corrupt every segment past the first.
            zm = (mask > 0) if seg is not None else mask
            out = out * zm[..., None].astype(out.dtype)
        return out, state
