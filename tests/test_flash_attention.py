"""Fused Pallas flash-attention kernel (ops/flash_attention.py) and the
pallas/blockwise/dense dispatch around it (ISSUE 7).

Everything runs the REAL kernels in interpret mode on CPU (the lrn test
precedent): fwd and bwd parity against dense_attention, the lse output
and its cotangent (the ring merge's requirement), the dispatch rule +
selection counter + one-shot fallback warning, and the ring composition
with the fused inner step. 8k/16k shapes ride the `slow` marker
(ROADMAP maintenance note: tier-1 budget is tight on this rig).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import pallas_kernels as pk

# interpret-mode kernels accumulate identically to the f32 dense
# reference; grads tolerate one extra reassociation
FWD_TOL = dict(rtol=1e-5, atol=1e-5)
GRAD_TOL = dict(rtol=2e-4, atol=1e-5)


def _qkv(seed=0, B=2, T=64, H=4, D=16, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
    return mk(), mk(), mk()


def _mask(seed=3, B=2, T=64):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random((B, T)) > 0.3, jnp.float32)


def _flash(q, k, v, **kw):
    kw.setdefault("q_block", 16)
    kw.setdefault("kv_block", 16)
    return fa.flash_attention(q, k, v, interpret=True, **kw)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = _qkv()
        got = _flash(q, k, v, causal=causal)
        want = att.dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **FWD_TOL)

    def test_key_mask_matches_dense(self):
        q, k, v = _qkv()
        km = _mask()
        got = _flash(q, k, v, causal=True, key_mask=km)
        want = att.dense_attention(q, k, v, causal=True, key_mask=km)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **FWD_TOL)

    def test_fully_masked_rows_output_zero(self):
        # dense_attention convention: a query with NO valid keys outputs
        # exactly zero (not a uniform average over sentinels)
        q, k, v = _qkv()
        km = _mask().at[0].set(0.0)
        got = _flash(q, k, v, key_mask=km)
        want = att.dense_attention(q, k, v, key_mask=km)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **FWD_TOL)
        assert np.all(np.asarray(got)[0] == 0.0)

    def test_lse_matches_logsumexp(self):
        q, k, v = _qkv(B=1, T=32, H=2, D=8)
        _, lse = _flash(q, k, v, with_lse=True)
        s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / np.sqrt(q.shape[-1])
        want = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                                   **FWD_TOL)

    def test_position_offsets_shift_causal_mask(self):
        # the ring path feeds global positions; a uniform offset must
        # leave self-attention causality unchanged
        q, k, v = _qkv(B=1, T=32, H=2, D=8)
        off = jnp.arange(32, dtype=jnp.int32) + 96
        got = _flash(q, k, v, causal=True, q_pos=off, kv_pos=off)
        want = att.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **FWD_TOL)

    def test_indivisible_block_raises(self):
        q, k, v = _qkv(B=1, T=48, H=1, D=8)
        with pytest.raises(ValueError, match="must divide"):
            fa.flash_attention(q, k, v, q_block=32, kv_block=32,
                               interpret=True)

    def test_bf16_runs(self):
        q, k, v = _qkv(B=1, T=32, H=2, D=8, dtype=jnp.bfloat16)
        got = _flash(q, k, v, causal=True)
        want = att.dense_attention(q, k, v, causal=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv()
        g = jnp.asarray(np.random.default_rng(9).standard_normal(q.shape),
                        jnp.float32)

        def f_flash(q, k, v):
            return jnp.sum(_flash(q, k, v, causal=causal) * g)

        def f_dense(q, k, v):
            return jnp.sum(att.dense_attention(q, k, v, causal=causal)
                           * g)

        got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       **GRAD_TOL)

    def test_key_mask_grads_match_dense(self):
        q, k, v = _qkv(B=1, T=32, H=2, D=8)
        km = _mask(B=1, T=32)
        g = jnp.asarray(np.random.default_rng(9).standard_normal(q.shape),
                        jnp.float32)
        got = jax.grad(lambda q, k, v: jnp.sum(_flash(
            q, k, v, causal=True, key_mask=km) * g),
            argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda q, k, v: jnp.sum(att.dense_attention(
            q, k, v, causal=True, key_mask=km) * g),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       **GRAD_TOL)

    def test_lse_cotangent(self):
        # the ring merge differentiates THROUGH lse: ds += p * g_lse in
        # the backward kernels must reproduce autodiff of logsumexp
        q, k, v = _qkv(B=1, T=32, H=2, D=8)

        def f_flash(q, k, v):
            o, lse = _flash(q, k, v, with_lse=True)
            return jnp.sum(o) + jnp.sum(jnp.sin(lse))

        def f_ref(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / np.sqrt(q.shape[-1])
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            return jnp.sum(att.dense_attention(q, k, v)) + \
                jnp.sum(jnp.sin(lse))

        got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       **GRAD_TOL)


@pytest.mark.slow
class TestFlashLongSequences:
    """8k/16k interpret-mode parity (slow: interpret executes the grid
    in python). Blocks sized so the grid stays ~256 steps."""

    @pytest.mark.parametrize("seq,blk", [(8192, 512), (16384, 1024)])
    def test_long_forward_matches_blockwise(self, seq, blk):
        rng = np.random.default_rng(11)
        mk = lambda: jnp.asarray(
            rng.standard_normal((1, seq, 1, 8)), jnp.float32)
        q, k, v = mk(), mk(), mk()
        got = fa.flash_attention(q, k, v, causal=True, q_block=blk,
                                 kv_block=blk, interpret=True)
        want = att.blockwise_attention(q, k, v, causal=True, q_block=blk,
                                       kv_block=blk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestDispatch:
    def _counter(self, impl):
        from deeplearning4j_tpu.optimize.metrics import registry
        return registry().counter(
            "attention_kernel_selected_total").value(impl=impl)

    def test_rule_short_sequences_dense(self):
        assert att.select_attention_impl(64, 16) == "dense"
        assert att.select_attention_impl(1024, 64) == "dense"

    def test_rule_long_sequences_cpu(self):
        # no TPU here: the answer is "no" without compiling anything,
        # and the rule lands blockwise
        assert att.select_attention_impl(4096, 128) == "blockwise"

    def test_rule_long_sequences_interpret_pallas(self):
        # interpret=True vouches for the kernel (CPU tests), so the
        # >=2048 auto rule picks pallas
        assert att.select_attention_impl(4096, 128,
                                         interpret=True) == "pallas"

    def test_rule_explicit_block_size_keeps_blockwise(self):
        assert att.select_attention_impl(
            4096, 128, block_size=256, interpret=True) == "blockwise"

    def test_rule_block_size_minus_one_forces_dense(self):
        assert att.select_attention_impl(
            4096, 128, block_size=-1) == "dense"

    def test_requested_dense_honored(self):
        assert att.select_attention_impl(
            4096, 128, requested="dense", interpret=True) == "dense"

    def test_invalid_impl_raises(self):
        with pytest.raises(ValueError, match="attention impl"):
            att.select_attention_impl(64, 16, requested="cudnn")

    def test_counter_increments(self):
        before = self._counter("dense")
        att.select_attention_impl(64, 16)
        assert self._counter("dense") == before + 1

    def test_pallas_request_that_cannot_run_raises(self):
        # off-TPU and not interpreted the fused kernel does not exist:
        # a caller who NAMED it gets an error, never another impl
        before = self._counter("dense")
        with pytest.raises(ValueError, match="'pallas' requested"):
            att.select_attention_impl(64, 16, requested="pallas")
        assert self._counter("dense") == before

    def test_single_device_attention_pallas_parity(self):
        q, k, v = _qkv(B=1, T=32, H=2, D=8)
        got = att.single_device_attention(q, k, v, causal=True,
                                          impl="pallas", interpret=True)
        want = att.dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **FWD_TOL)

    def test_layer_attention_impl_serde_roundtrip(self):
        from deeplearning4j_tpu.nn.layers.attention import \
            SelfAttentionLayer
        from deeplearning4j_tpu.utils import serde
        layer = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                   attention_impl="dense")
        back = serde.from_json(serde.to_json(layer))
        assert back.attention_impl == "dense"


class TestRingFusedStep:
    def _mesh(self):
        from deeplearning4j_tpu.parallel.mesh import SEQ_AXIS, create_mesh
        return create_mesh([8], (SEQ_AXIS,), jax.devices())

    def test_ring_flash_forward_matches_dense(self):
        q, k, v = _qkv(B=1, T=32, H=2, D=8)
        km = _mask(B=1, T=32)
        got = att.ring_self_attention(q, k, v, self._mesh(), causal=True,
                                      key_mask=km, use_flash=True,
                                      flash_interpret=True)
        want = att.dense_attention(q, k, v, causal=True, key_mask=km)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_ring_flash_grads_match_dense(self):
        q, k, v = _qkv(B=1, T=32, H=2, D=8)
        g = jnp.asarray(np.random.default_rng(9).standard_normal(q.shape),
                        jnp.float32)
        mesh = self._mesh()
        got = jax.grad(lambda q, k, v: jnp.sum(att.ring_self_attention(
            q, k, v, mesh, causal=True, use_flash=True,
            flash_interpret=True) * g), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda q, k, v: jnp.sum(att.dense_attention(
            q, k, v, causal=True) * g), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=1e-5)


class TestSharedPlumbing:
    def test_pad_axis_to(self):
        a = jnp.ones((3, 5))
        out = pk.pad_axis_to(a, 1, 4)
        assert out.shape == (3, 8)
        assert float(out[0, 5]) == 0.0
        assert pk.pad_axis_to(a, 0, 3) is a  # already aligned: no copy

