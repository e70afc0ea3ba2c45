"""Pooling implementation parity (ops/pooling.py, ISSUE 10): the
argmax-equality-mask max-pool backward vs XLA's select-and-scatter, the
depthwise-conv average pool vs reduce_window, the count-exclude-pad AVG
divisor under finite differences, and the measured-dispatch selector.

Shapes are deliberately tiny — the suite already brushes the tier-1
wall budget on the 1-core rig (ROADMAP maintenance note)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from deeplearning4j_tpu.nn.layers.convolution import (PoolingType,
                                                      SubsamplingLayer)
from deeplearning4j_tpu.ops import pooling
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.utils import serde

# (shape, window, strides, pads) — SAME/VALID, strides 1-3, asymmetric
# pads, truncation where the last window over-reaches the padded input.
GEOMETRIES = [
    ((2, 7, 9, 3), (3, 3), (2, 2), ((1, 1), (1, 1))),
    ((2, 7, 9, 3), (3, 3), (1, 1), ((1, 1), (1, 1))),
    ((2, 8, 8, 2), (2, 2), (2, 2), ((0, 0), (0, 0))),
    ((1, 9, 9, 4), (3, 3), (2, 2), ((1, 0), (0, 1))),
    ((2, 5, 5, 1), (3, 3), (3, 3), ((0, 0), (0, 0))),
    ((2, 10, 6, 2), (2, 3), (2, 1), ((1, 1), (1, 1))),
]


def _x(shape, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


class TestMaxPoolMask:
    """mask must be a drop-in for sns: bitwise forward (same
    reduce_window), backward equal wherever window maxima are unique
    (random continuous inputs: everywhere)."""

    @pytest.mark.parametrize("shape,window,strides,pads", GEOMETRIES)
    def test_fwd_bitwise_and_bwd_parity(self, shape, window, strides, pads):
        x = _x(shape)
        y_sns = pooling.max_pool(x, window, strides, pads, impl="sns")
        y_mask = pooling.max_pool(x, window, strides, pads, impl="mask")
        assert np.array_equal(np.asarray(y_sns), np.asarray(y_mask))

        def loss(impl):
            return lambda a: jnp.sum(jnp.cos(pooling.max_pool(
                a, window, strides, pads, impl=impl)))

        g_sns = jax.grad(loss("sns"))(x)
        g_mask = jax.grad(loss("mask"))(x)
        np.testing.assert_allclose(np.asarray(g_mask), np.asarray(g_sns),
                                   rtol=2e-6, atol=2e-6)

    def test_nonoverlapping_exact(self):
        x = _x((2, 8, 8, 2), seed=3)
        g_sns = jax.grad(lambda a: jnp.sum(pooling.max_pool(
            a, (2, 2), (2, 2), ((0, 0), (0, 0)), impl="sns") ** 2))(x)
        g_mask = jax.grad(lambda a: jnp.sum(pooling.max_pool(
            a, (2, 2), (2, 2), ((0, 0), (0, 0)), impl="mask") ** 2))(x)
        assert np.array_equal(np.asarray(g_sns), np.asarray(g_mask))

    def test_tie_splitting_preserves_cotangent_sum(self):
        """Deliberate semantics difference: on a constant window S&S
        routes the whole cotangent to one element, mask splits it
        equally among the tied maxima. Both conserve the sum."""
        x = jnp.ones((1, 4, 4, 1), jnp.float32)
        g_mask = jax.grad(lambda a: jnp.sum(pooling.max_pool(
            a, (2, 2), (2, 2), ((0, 0), (0, 0)), impl="mask")))(x)
        np.testing.assert_allclose(np.asarray(g_mask),
                                   np.full((1, 4, 4, 1), 0.25), rtol=0)
        g_sns = jax.grad(lambda a: jnp.sum(pooling.max_pool(
            a, (2, 2), (2, 2), ((0, 0), (0, 0)), impl="sns")))(x)
        assert float(g_mask.sum()) == pytest.approx(float(g_sns.sum()))

    def test_bf16_fwd_bitwise_bwd_close(self):
        x = _x((2, 7, 9, 3), seed=5, dtype=jnp.bfloat16)
        y_sns = pooling.max_pool(x, (3, 3), (2, 2), ((1, 1), (1, 1)),
                                 impl="sns")
        y_mask = pooling.max_pool(x, (3, 3), (2, 2), ((1, 1), (1, 1)),
                                  impl="mask")
        assert y_mask.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(y_sns, np.float32),
                              np.asarray(y_mask, np.float32))
        g = jax.grad(lambda a: jnp.sum(pooling.max_pool(
            a, (3, 3), (2, 2), ((1, 1), (1, 1)),
            impl="mask").astype(jnp.float32)))(x)
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, np.float32)).all()


class TestAvgPool:
    @pytest.mark.parametrize("shape,window,strides,pads", GEOMETRIES)
    def test_conv_matches_window(self, shape, window, strides, pads):
        x = _x(shape, seed=1)
        y_w = pooling.avg_pool(x, window, strides, pads, impl="window")
        y_c = pooling.avg_pool(x, window, strides, pads, impl="conv")
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_w),
                                   rtol=2e-6, atol=2e-6)
        g_w = jax.grad(lambda a: jnp.sum(jnp.sin(pooling.avg_pool(
            a, window, strides, pads, impl="window"))))(x)
        g_c = jax.grad(lambda a: jnp.sum(jnp.sin(pooling.avg_pool(
            a, window, strides, pads, impl="conv"))))(x)
        np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_w),
                                   rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("impl", pooling.AVG_IMPLS)
    def test_count_exclude_pad_finite_difference(self, impl):
        """ISSUE 10 satellite: the AVG backward must be the true VJP of
        the count-exclude-pad forward under SAME-style padding with
        stride > 1 — the geometry where edge windows see fewer in-bounds
        elements and a wrong divisor shows up as a grad mismatch."""
        x = _x((2, 7, 7, 2), seed=2)
        f = lambda a: pooling.avg_pool(a, (3, 3), (2, 2), ((1, 1), (1, 1)),
                                       impl=impl)
        check_grads(f, (x,), order=1, modes=("rev",), rtol=1e-4)

    def test_edge_divisor_counts_inbounds_only(self):
        # 1x1 corner window under pad 1 covers 1 in-bounds cell of a 2x2
        # window's 4 — average must divide by the 1..4 count, not kh*kw.
        x = jnp.asarray(np.arange(9, dtype=np.float32).reshape(1, 3, 3, 1))
        y = pooling.avg_pool(x, (2, 2), (2, 2), ((1, 0), (1, 0)),
                             impl="conv")
        assert float(y[0, 0, 0, 0]) == 0.0  # corner: single cell 0/1
        assert float(y[0, 1, 1, 0]) == pytest.approx((4 + 5 + 7 + 8) / 4)


class TestDispatch:
    def test_auto_defaults_and_override(self):
        # measured per-backend rule: mask on CPU, sns on TPU
        want = "mask" if jax.default_backend() == "cpu" else "sns"
        assert pooling.select_pooling_impl("max", (3, 3), (2, 2)) == want
        assert pooling.select_pooling_impl(
            "max", (3, 3), (2, 2), requested="auto") == want
        assert pooling.select_pooling_impl(
            "max", (3, 3), (2, 2), requested="mask") == "mask"
        assert pooling.select_pooling_impl("avg", (3, 3), (2, 2)) == "window"
        assert pooling.select_pooling_impl(
            "avg", (3, 3), (2, 2), requested="conv") == "conv"

    def test_bad_requests_raise(self):
        with pytest.raises(ValueError):
            pooling.select_pooling_impl("max", (3, 3), (2, 2),
                                        requested="conv")
        with pytest.raises(ValueError):
            pooling.select_pooling_impl("pnorm", (3, 3), (2, 2))

    def test_counter_increments(self):
        fam = registry().counter(
            "pooling_impl_selected_total",
            "Pooling implementations chosen at dispatch (trace) time")
        before = fam.value(impl="max_mask")
        pooling.select_pooling_impl("max", (3, 3), (2, 2),
                                    requested="mask")
        assert fam.value(impl="max_mask") == before + 1


class TestSubsamplingLayerKnob:
    def _fwd(self, layer, x):
        out, _ = layer.forward({}, {}, x)
        return out

    def test_layer_impls_agree_and_serde_roundtrip(self):
        x = _x((2, 9, 9, 3), seed=4)
        outs = [self._fwd(SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1),
            pooling_type=PoolingType.MAX, pooling_impl=impl), x)
            for impl in ("auto", "sns", "mask")]
        for other in outs[1:]:
            assert np.array_equal(np.asarray(outs[0]), np.asarray(other))
        layer = SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2),
                                 pooling_type=PoolingType.AVG,
                                 pooling_impl="conv")
        rt = serde.from_json(serde.to_json(layer))
        assert rt.pooling_impl == "conv"
        np.testing.assert_allclose(np.asarray(self._fwd(rt, x)),
                                   np.asarray(self._fwd(layer, x)))

    def test_pnorm_untouched_and_differentiable(self):
        x = _x((1, 6, 6, 2), seed=6)
        layer = SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2),
                                 pooling_type=PoolingType.PNORM, pnorm=2,
                                 pooling_impl="mask")  # ignored for pnorm
        g = jax.grad(lambda a: jnp.sum(layer.forward({}, {}, a)[0]))(x)
        assert np.isfinite(np.asarray(g)).all()
