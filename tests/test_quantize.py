"""Quantized inference (ISSUE 16): post-training int8/bf16 param-tree
quantization, the int8 matmul kernel arms, and the canary-gated
quantized swap plane (docs/serving.md §quantized, docs/design.md
"Quantized serving").

Covers: the per-channel round-trip error bound (|W - deq(q(W))| <=
scale/2, with and without zero-points), the typed AlreadyQuantizedError
on re-quantization, bf16-mode casting rules, arm parity for the int8
matmul (native vs XLA bit-exact, Pallas interpret-mode bit-exact)
across ragged shapes including the tile-padding edge sizes, the
dense_qforward-vs-fp32 accuracy bound, the measured-dispatch env
override, and the ModelPool swap plane: promotion with precision
labels, canary rejection past `canary_max_drift` with rollback (old
params keep serving), the same-file re-quantization noop rule, and the
fused-group member refusal.

Device work per test is tiny (4->16->3 heads on CPU); the serving
tests reuse the test_serving_gateway fixtures.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import native_quant
from deeplearning4j_tpu.ops import pallas_kernels
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.optimize.resilience import CheckpointManager
from deeplearning4j_tpu.quantize import (AlreadyQuantizedError, QuantSpec,
                                         dense_qforward, dequantize_tree,
                                         quantize_tree, sidecar_scales,
                                         tree_precision)
from deeplearning4j_tpu.serving import ServingGateway, SwapError

from test_multimodel import trio
from test_serving_gateway import make_net, rand_x


def dense_tree(n_in=8, n_out=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"W": jnp.asarray(rng.standard_normal(
                (n_in, n_out)).astype(np.float32)),
            "b": jnp.asarray(rng.standard_normal(
                (n_out,)).astype(np.float32))}


# ---------------------------------------------------------------------------
# quantize_tree / dequantize_tree properties
# ---------------------------------------------------------------------------
class TestQuantizeTree:
    @pytest.mark.parametrize("zero_point", [False, True])
    def test_roundtrip_error_bounded_per_channel(self, zero_point):
        """The pinned property: per output channel, the dequantized
        weight is within scale/2 of the original (round-to-nearest on a
        uniform grid)."""
        tree = {"layer_0": dense_tree(n_in=32, n_out=11)}
        q = quantize_tree(tree, QuantSpec(mode="int8",
                                          zero_point=zero_point))
        back = dequantize_tree(q)
        w, w2 = np.asarray(tree["layer_0"]["W"]), \
            np.asarray(back["layer_0"]["W"])
        scale = np.asarray(q["layer_0"]["W_scale"])
        err = np.max(np.abs(w - w2), axis=0)  # per output channel
        assert (err <= scale / 2 + 1e-7).all(), (err, scale)
        # bias rides through untouched
        np.testing.assert_array_equal(np.asarray(back["layer_0"]["b"]),
                                      np.asarray(tree["layer_0"]["b"]))

    def test_requantization_raises_typed_error(self):
        tree = {"layer_0": dense_tree()}
        q = quantize_tree(tree, "int8")
        with pytest.raises(AlreadyQuantizedError):
            quantize_tree(q, "int8")
        with pytest.raises(AlreadyQuantizedError):
            quantize_tree(q, "bf16")
        b16 = quantize_tree(tree, "bf16")
        with pytest.raises(AlreadyQuantizedError):
            quantize_tree(b16, "bf16")
        # the typed error is a TypeError so generic handlers catch it
        assert issubclass(AlreadyQuantizedError, TypeError)

    def test_bf16_mode_casts_ndim2_only(self):
        rng = np.random.default_rng(1)
        tree = {"conv": {"W": jnp.asarray(rng.standard_normal(
                    (3, 3, 2, 4)).astype(np.float32)),
                         "b": jnp.zeros((4,), jnp.float32)},
                "dense": dense_tree()}
        q = quantize_tree(tree, "bf16")
        assert q["conv"]["W"].dtype == jnp.bfloat16
        assert q["dense"]["W"].dtype == jnp.bfloat16
        assert q["conv"]["b"].dtype == jnp.float32
        assert q["dense"]["b"].dtype == jnp.float32
        assert tree_precision(q) == "bf16"
        back = dequantize_tree(q)
        # bf16 keeps the top 8 mantissa bits: relative error < 2^-8
        np.testing.assert_allclose(np.asarray(back["dense"]["W"]),
                                   np.asarray(tree["dense"]["W"]),
                                   rtol=1 / 256, atol=1e-7)

    def test_int8_mode_routes_non_dense_to_bf16(self):
        """Attention/conv-shaped material (keys that are not the dense
        W/b pair, or ndim != 2) takes the bf16 arm inside int8 mode."""
        rng = np.random.default_rng(2)
        tree = {"attn": {"Wq": jnp.asarray(rng.standard_normal(
                    (8, 8)).astype(np.float32)),
                         "bq": jnp.zeros((8,), jnp.float32)},
                "conv": {"W": jnp.asarray(rng.standard_normal(
                    (3, 3, 2, 4)).astype(np.float32)),
                         "b": jnp.zeros((4,), jnp.float32)},
                "dense": dense_tree()}
        q = quantize_tree(tree, "int8")
        assert q["attn"]["Wq"].dtype == jnp.bfloat16
        assert q["conv"]["W"].dtype == jnp.bfloat16
        assert q["dense"]["W_q"].dtype == jnp.int8
        # transposed layout: [n_out, n_in] unit-stride channel rows
        assert q["dense"]["W_q"].shape == (16, 8)
        assert tree_precision(q) == "int8"

    def test_sidecar_and_precision_labels(self):
        tree = {"layer_0": dense_tree()}
        assert tree_precision(tree) == "fp32"
        q = quantize_tree(tree, QuantSpec(mode="int8", zero_point=True))
        side = sidecar_scales(q)
        assert set(side["layer_0"]) == {"W_scale", "W_zp"}
        assert side["layer_0"]["W_scale"].shape == (16,)
        assert side["layer_0"]["W_zp"].dtype == jnp.int32


# ---------------------------------------------------------------------------
# int8 matmul arms (contract: s8[B,K] x s8[N,K] -> s32[B,N])
# ---------------------------------------------------------------------------
# Ragged + tile-edge shapes: around the Pallas (32, 128) minimum tile
# and the native kernel's 64-lane K tail / 8-row batch blocking.
SHAPES = [(1, 1, 1), (3, 5, 7), (8, 64, 16), (7, 127, 13),
          (8, 128, 256), (9, 130, 33), (32, 256, 10), (5, 1024, 8)]


def _ref_i32(x, w):
    return np.asarray(x, np.int32) @ np.asarray(w, np.int32).T


class TestInt8MatmulArms:
    @pytest.mark.parametrize("b,k,n", SHAPES)
    def test_native_and_xla_bit_exact(self, b, k, n):
        rng = np.random.default_rng(b * 1000 + k + n)
        x = rng.integers(-127, 128, (b, k), dtype=np.int8)
        w = rng.integers(-127, 128, (n, k), dtype=np.int8)
        ref = _ref_i32(x, w)
        xq, wq = jnp.asarray(x), jnp.asarray(w)
        np.testing.assert_array_equal(
            np.asarray(pallas_kernels.int8_matmul_xla(xq, wq)), ref)
        # the host-side entry (ctypes or numpy fallback) agrees too
        np.testing.assert_array_equal(native_quant.int8_gemm(x, w), ref)
        if not native_quant.available():
            pytest.skip("native/libdl4jtpu_quant.so cannot be built here")
        # the XLA custom call (jax.ffi) reaches the same kernel
        np.testing.assert_array_equal(
            np.asarray(jax.jit(pallas_kernels.int8_matmul_native)(xq, wq)),
            ref)

    @pytest.mark.parametrize("b,k,n", [(1, 1, 1), (3, 5, 7), (8, 128, 256)])
    def test_pallas_interpret_bit_exact(self, b, k, n):
        rng = np.random.default_rng(7)
        x = rng.integers(-127, 128, (b, k), dtype=np.int8)
        w = rng.integers(-127, 128, (n, k), dtype=np.int8)
        out = pallas_kernels.int8_matmul_pallas(
            jnp.asarray(x), jnp.asarray(w), interpret=True)
        np.testing.assert_array_equal(np.asarray(out), _ref_i32(x, w))

    @pytest.mark.parametrize("b,n_in,n_out", [(1, 8, 3), (5, 33, 17),
                                              (8, 128, 64)])
    def test_dense_qforward_close_to_fp32(self, b, n_in, n_out):
        """End-to-end int8 dense vs the fp32 preout: bounded by the
        combined weight+activation grid steps, checked against a loose
        envelope (each product errs by <= ~(|x| w_scale + |w| x_scale)/2
        per element before accumulation)."""
        rng = np.random.default_rng(3)
        tree = dense_tree(n_in, n_out, seed=4)
        x = jnp.asarray(rng.standard_normal((b, n_in)).astype(np.float32))
        want = np.asarray(x @ tree["W"] + tree["b"])
        for spec in (QuantSpec("int8"), QuantSpec("int8", zero_point=True)):
            q = quantize_tree(tree, spec)
            got = np.asarray(dense_qforward(q, x))
            # Scale-aware statistical envelope: each of the n_in
            # products errs by O(|x| w_scale + |w| x_scale)/2 with
            # random sign, so the sum concentrates around
            # sqrt(n_in) * x_max * w_scale (|w| <= 127 w_scale and
            # x_scale = x_max/127 make both terms that size). 2x that
            # is > 6 sigma for uniform rounding noise — loose enough
            # never to flake, tight enough that a broken epilogue
            # (missing zp correction, transposed scales) blows through.
            tol = 2.0 * np.sqrt(n_in) * np.max(np.abs(np.asarray(x))) \
                * np.max(np.asarray(q["W_scale"]))
            np.testing.assert_allclose(got, want, atol=max(tol, 1e-3))

    def test_env_override_and_measured_dispatch(self, monkeypatch):
        backend = jax.default_backend()
        saved = dict(pallas_kernels._quant_impl)
        try:
            pallas_kernels._quant_impl.clear()
            monkeypatch.setenv(pallas_kernels.QUANT_MATMUL_ENV, "xla")
            assert pallas_kernels.select_quant_impl() == "xla"
            pallas_kernels._quant_impl.clear()
            monkeypatch.delenv(pallas_kernels.QUANT_MATMUL_ENV)
            winner = pallas_kernels.select_quant_impl()
            assert winner in ("xla", "native", "pallas")
            if backend == "cpu" and not native_quant.available():
                assert winner == "xla"
        finally:
            pallas_kernels._quant_impl.clear()
            pallas_kernels._quant_impl.update(saved)


# ---------------------------------------------------------------------------
# The quantized swap plane (ModelPool.swap(quantize=...))
# ---------------------------------------------------------------------------
def _swaps(model, outcome, precision):
    return registry().counter("serving_swaps_total").value(
        model=model, outcome=outcome, precision=precision)


class TestQuantizedSwap:
    def test_promote_label_and_roundtrip(self, tmp_path):
        """Loose drift budget: the int8 tree promotes, the precision
        label lands on the result / entry / gauge, outputs stay within
        the budget of fp32, and a fp32 re-swap of the SAME file is a
        real swap back (precision change is never a noop)."""
        net = make_net(seed=42, train_seed=3)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(net)
        gw = ServingGateway()
        golden = rand_x(4, seed=50)
        gw.add_model("m", net, checkpoints=mgr, batch_limit=8,
                     golden_batch=golden, canary_max_drift=0.05)
        try:
            ok_before = _swaps("m", "ok", "int8")
            ref = np.asarray(gw.predict("m", golden))
            res = gw.swap("m", quantize="int8")
            assert res["swapped"] is True
            assert res["precision"] == "int8"
            assert gw.pool.get("m").precision == "int8"
            assert _swaps("m", "ok", "int8") == ok_before + 1
            gauge = registry().gauge("serving_precision")
            assert gauge.value(model="m", precision="int8") == 1
            assert gauge.value(model="m", precision="fp32") == 0
            got = np.asarray(gw.predict("m", golden))
            assert np.max(np.abs(got - ref)) <= 0.05
            # same file, int8 again: noop (the idempotence rule keys on
            # file AND precision)
            again = gw.swap("m", quantize="int8")
            assert again["swapped"] is False
            # same file back to fp32: a real swap, bitwise restoration
            back = gw.swap("m")
            assert back["swapped"] is True
            assert back["precision"] == "fp32"
            np.testing.assert_array_equal(
                np.asarray(gw.predict("m", golden)), ref)
        finally:
            gw.pool.shutdown()

    def test_canary_rejects_drift_and_rolls_back(self, tmp_path):
        """The satellite acceptance test: a quantized swap whose golden
        -batch drift exceeds canary_max_drift is rejected with the
        canary_rejected outcome (precision-labeled) and the old fp32
        params keep serving bitwise."""
        net = make_net(seed=42, train_seed=3)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(net)
        gw = ServingGateway()
        golden = rand_x(4, seed=51)
        gw.add_model("m", net, checkpoints=mgr, batch_limit=8,
                     golden_batch=golden, canary_max_drift=1e-9)
        try:
            before = _swaps("m", "canary_rejected", "int8")
            ref = np.asarray(gw.predict("m", golden))
            with pytest.raises(SwapError, match="canary gate rejected"):
                gw.swap("m", quantize="int8")
            assert _swaps("m", "canary_rejected", "int8") == before + 1
            # rolled back: fp32 precision, zero promoted swaps, bitwise
            # the old outputs
            entry = gw.pool.get("m")
            assert entry.precision == "fp32"
            assert entry.swaps == 0
            np.testing.assert_array_equal(
                np.asarray(gw.predict("m", golden)), ref)
            assert registry().gauge("serving_precision").value(
                model="m", precision="fp32") == 1
        finally:
            gw.pool.shutdown()

    def test_unknown_mode_is_typed_error(self, tmp_path):
        net = make_net()
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(net)
        gw = ServingGateway()
        gw.add_model("m", net, checkpoints=mgr)
        try:
            with pytest.raises(SwapError, match="unknown quantize mode"):
                gw.swap("m", quantize="int4")
        finally:
            gw.pool.shutdown()

    def test_fused_member_refuses_quantize(self, tmp_path):
        """A fused group's single channel-concatenated weight cannot
        hold per-member precision: quantized member swap is a typed
        refusal, and the member keeps serving fp32."""
        donor = trio()[1][1]
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(donor)
        gw = ServingGateway()
        gw.add_fused_group("grp", trio(), batch_limit=4)
        x = rand_x(2, seed=9)
        try:
            ref = np.asarray(gw.predict("b", x))
            with pytest.raises(SwapError, match="per-model"):
                gw.swap("b", manager=mgr, quantize="int8")
            np.testing.assert_array_equal(np.asarray(gw.predict("b", x)),
                                          ref)
            assert gw.pool.get("b").precision == "fp32"
        finally:
            gw.pool.shutdown()


class TestQuantizedInference:
    def test_quantized_net_output_close_and_training_untouched(self):
        """MultiLayerNetwork.output on a quantized tree stays within the
        int8 grid of the fp32 output; the fp32 net is untouched by the
        pure quantize_tree call (bitwise identical afterwards)."""
        net = make_net(seed=42, train_seed=6)
        x = rand_x(5, seed=60)
        ref = np.asarray(net.output(x))
        fp32_leaves = [np.asarray(a) for a in
                       jax.tree_util.tree_leaves(net.params_tree)]
        qtree = quantize_tree(net.params_tree, "int8")
        old = net.params_tree
        try:
            net.params_tree = qtree
            got = np.asarray(net.output(x))
        finally:
            net.params_tree = old
        assert np.max(np.abs(got - ref)) < 0.05, \
            np.max(np.abs(got - ref))
        for a, b in zip(fp32_leaves,
                        jax.tree_util.tree_leaves(net.params_tree)):
            np.testing.assert_array_equal(a, np.asarray(b))
