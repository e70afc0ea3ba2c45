"""Convolutional layer family.

Reference parity: nn/conf/layers/{ConvolutionLayer,Convolution1DLayer,
SubsamplingLayer,Subsampling1DLayer,ZeroPaddingLayer} + impls under
nn/layers/convolution/ (im2col+gemm path at ConvolutionLayer.java:312-370,
output-size math in util/ConvolutionUtils.java, ConvolutionMode
Strict/Truncate/Same in nn/conf/ConvolutionMode.java), the cuDNN fast path
(deeplearning4j-cuda CudnnConvolutionHelper.java:100-205).

TPU-native redesign: NHWC layout, HWIO weights, one lax.conv_general_dilated
call — XLA lowers it straight onto the MXU with autotuned tiling, which is
both the im2col+gemm path and the cuDNN algo-selection knob in one (the
reference needs a Helper SPI per layer because its default path is unfused;
here the compiler owns that). Pooling is lax.reduce_window. No hand-written
backward passes: autodiff emits the transposed-conv gradients.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...ops import pooling as pool_ops
from ...utils import serde
from ..conf.inputs import ConvolutionalType, FeedForwardType, InputType
from .core import BIAS, WEIGHT, Layer, dropout

Array = jax.Array


@serde.register
class ConvolutionMode(enum.Enum):
    """Reference nn/conf/ConvolutionMode.java. STRICT errors when sizes don't
    divide exactly; TRUNCATE floors; SAME pads to ceil(in/stride)."""

    STRICT = "strict"
    TRUNCATE = "truncate"
    SAME = "same"


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        if len(v) == 1:
            return (int(v[0]), int(v[0]))
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def conv_output_size(in_size: int, kernel: int, stride: int, pad: int,
                     mode: ConvolutionMode, dilation: int = 1) -> int:
    """Output spatial extent (reference ConvolutionUtils.getOutputSize)."""
    eff_k = kernel + (kernel - 1) * (dilation - 1)
    if mode == ConvolutionMode.SAME:
        return -(-in_size // stride)  # ceil
    out = (in_size + 2 * pad - eff_k) // stride + 1
    if mode == ConvolutionMode.STRICT and (in_size + 2 * pad - eff_k) % stride != 0:
        raise ValueError(
            f"ConvolutionMode.STRICT: (in={in_size} + 2*pad={pad} - k={eff_k}) "
            f"not divisible by stride={stride}; use TRUNCATE or SAME")
    return out


def _same_pads(in_size: int, kernel: int, stride: int, dilation: int = 1):
    """Explicit SAME padding (TF convention, matches reference Same mode)."""
    eff_k = kernel + (kernel - 1) * (dilation - 1)
    out = -(-in_size // stride)
    total = max(0, (out - 1) * stride + eff_k - in_size)
    return (total // 2, total - total // 2)


@serde.register
@dataclass
class ConvolutionLayer(Layer):
    """2D convolution (reference nn/conf/layers/ConvolutionLayer).

    Weights are HWIO [kh, kw, c_in, c_out]; data NHWC."""

    n_in: int = 0   # input channels
    n_out: int = 0  # output channels / filters
    kernel_size: Sequence[int] = (5, 5)
    stride: Sequence[int] = (1, 1)
    padding: Sequence[int] = (0, 0)
    dilation: Sequence[int] = (1, 1)
    convolution_mode: Optional[ConvolutionMode] = None  # None -> inherit/Truncate
    # cuDNN-algo-mode analog: XLA autotunes; field kept for config parity.
    cudnn_algo_mode: str = "PREFER_FASTEST"
    # TPU algo choice (the working half of the cuDNN AlgoMode analog,
    # reference ConvolutionLayer.java:66-77): "auto" picks space-to-depth
    # for few-channel strided stems (exact reparametrization, see
    # _conv_space_to_depth), "direct" forces plain conv.
    conv_algo: str = "auto"

    def input_kind(self):
        return "cnn"

    def _mode(self) -> ConvolutionMode:
        return self.convolution_mode or ConvolutionMode.TRUNCATE

    def set_input_type(self, input_type):
        if not isinstance(input_type, ConvolutionalType):
            raise ValueError(f"ConvolutionLayer needs CNN input, got {input_type}")
        if self.n_in == 0:
            self.n_in = input_type.channels
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dh, dw = _pair(self.dilation)
        oh = conv_output_size(input_type.height, kh, sh, ph, self._mode(), dh)
        ow = conv_output_size(input_type.width, kw, sw, pw, self._mode(), dw)
        return ConvolutionalType(height=oh, width=ow, channels=self.n_out)

    def has_params(self):
        return True

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        w = self._winit(key, (kh, kw, self.n_in, self.n_out), fan_in, fan_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return {WEIGHT: w, BIAS: b}

    def _conv(self, x, w):
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        if self._mode() == ConvolutionMode.SAME:
            pads = (_same_pads(x.shape[1], w.shape[0], sh, dh),
                    _same_pads(x.shape[2], w.shape[1], sw, dw))
        else:
            ph, pw = _pair(self.padding)
            pads = ((ph, ph), (pw, pw))
        if self._use_space_to_depth(x, w, (sh, sw), (dh, dw), pads):
            return self._conv_space_to_depth(x, w, sh, pads)
        # bf16 convs accumulate in f32 on the MXU by default under XLA; no
        # preferred_element_type (it breaks the transpose rule's dtype match).
        return lax.conv_general_dilated(
            x, w, window_strides=(sh, sw), padding=pads,
            rhs_dilation=(dh, dw),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def _use_space_to_depth(self, x, w, strides, dilation, pads) -> bool:
        """Heuristic: a strided conv over very few input channels (an
        ImageNet stem: 3 RGB channels vs the MXU's 128 lanes) wastes >97%
        of the systolic array; its dW gradient was the single hottest
        fusion in the profiled ResNet50 step. Space-to-depth regroups
        stride x stride pixel blocks into channels, which is exactly
        equivalent (see _conv_space_to_depth) and ~s^2 x denser."""
        if self.conv_algo not in ("auto", "direct", "space_to_depth"):
            raise ValueError(
                f"conv_algo={self.conv_algo!r}: expected 'auto', 'direct' "
                "or 'space_to_depth'")
        if self.conv_algo == "direct":
            return False
        sh, sw = strides
        if self.conv_algo != "space_to_depth":  # auto
            if w.shape[2] > 4 or sh < 2:
                return False
        if sh != sw or dilation != (1, 1):
            return False
        hp = x.shape[1] + pads[0][0] + pads[0][1]
        wp = x.shape[2] + pads[1][0] + pads[1][1]
        if hp % sh or wp % sh:
            return False
        # exact-equivalence condition: padding the kernel to a multiple of
        # the stride must not change the output extent
        k_pad = -(-w.shape[0] // sh) * sh
        kw_pad = -(-w.shape[1] // sh) * sh
        return ((hp - k_pad) // sh == (hp - w.shape[0]) // sh
                and (wp - kw_pad) // sh == (wp - w.shape[1]) // sh)

    def _conv_space_to_depth(self, x, w, s, pads):
        """Exact reparametrization of a stride-s conv as a stride-1 conv on
        space-to-depth-transformed input (the MLPerf TPU ResNet stem trick).
        Pixel (i*s+a, j*s+b, c) maps to channel (a*s+b)*C+c of s2d cell
        (i, j); the kernel, zero-padded up to a stride multiple, regroups
        identically, so out[i,j] = sum x[i*s+p, j*s+q, c] w[p,q,c] term for
        term. Gradients flow through pad/reshape back onto the original
        7x7-style params, so training math is untouched."""
        B, _, _, C = x.shape
        kh, kw = w.shape[0], w.shape[1]
        O = w.shape[3]
        xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
        hp, wp = xp.shape[1], xp.shape[2]
        # s2d cell (i,j) channel (a*s+b)*C+c = pixel (i*s+a, j*s+b, c).
        # (A/B-profiled vs a concat-of-strided-slices formulation: this
        # reshape+transpose chain is ~1.5x faster on v5e.)
        xs = xp.reshape(B, hp // s, s, wp // s, s, C)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, hp // s, wp // s, s * s * C)
        kp, kq = -(-kh // s) * s, -(-kw // s) * s
        wpad = jnp.pad(w, ((0, kp - kh), (0, kq - kw), (0, 0), (0, 0)))
        ws = wpad.reshape(kp // s, s, kq // s, s, C, O)
        ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(
            kp // s, kq // s, s * s * C, O)
        return lax.conv_general_dilated(
            xs, ws, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout(x, self.dropout_rate, train, rng)
        w = params[WEIGHT]
        # bf16-quantized kernels (quantize.quantize_tree) compute the
        # conv in bf16; the f32 bias add promotes the epilogue back up.
        xc = x.astype(w.dtype) if w.dtype == jnp.bfloat16 else x
        out = self._conv(xc, w) + params[BIAS]
        return self._act()(out.astype(x.dtype)), state


@serde.register
@dataclass
class Convolution1DLayer(ConvolutionLayer):
    """1D convolution over [batch, time, features] (reference
    nn/conf/layers/Convolution1DLayer — rnn-style data)."""

    kernel_size: Sequence[int] = (3,)
    stride: Sequence[int] = (1,)
    padding: Sequence[int] = (0,)
    dilation: Sequence[int] = (1,)

    def input_kind(self):
        return "rnn"

    def set_input_type(self, input_type):
        from ..conf.inputs import RecurrentType
        if not isinstance(input_type, RecurrentType):
            raise ValueError(f"Convolution1DLayer needs RNN input, got {input_type}")
        if self.n_in == 0:
            self.n_in = input_type.size
        k, s = _pair(self.kernel_size)[0], _pair(self.stride)[0]
        p = _pair(self.padding)[0]
        d = _pair(self.dilation)[0]
        t = input_type.timeseries_length
        out_t = None if t is None else conv_output_size(
            t, k, s, p, self._mode(), d)
        return RecurrentType(size=self.n_out, timeseries_length=out_t)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout(x, self.dropout_rate, train, rng)
        x4 = x[:, :, None, :]  # [b, t, 1, f] as NHWC
        w = params[WEIGHT]
        x4 = x4.astype(w.dtype) if w.dtype == jnp.bfloat16 else x4
        out = self._conv4d_1d(x4, w) + params[BIAS]
        return self._act()(out[:, :, 0, :]), state

    def init_params(self, key, dtype=jnp.float32):
        k = _pair(self.kernel_size)[0]
        fan_in = self.n_in * k
        fan_out = self.n_out * k
        w = self._winit(key, (k, 1, self.n_in, self.n_out), fan_in, fan_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return {WEIGHT: w, BIAS: b}

    def _conv4d_1d(self, x, w):
        s = _pair(self.stride)[0]
        d = _pair(self.dilation)[0]
        if self._mode() == ConvolutionMode.SAME:
            pads = (_same_pads(x.shape[1], w.shape[0], s, d), (0, 0))
        else:
            p = _pair(self.padding)[0]
            pads = ((p, p), (0, 0))
        return lax.conv_general_dilated(
            x, w, window_strides=(s, 1), padding=pads, rhs_dilation=(d, 1),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


@serde.register
class PoolingType(enum.Enum):
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


@serde.register
@dataclass
class SubsamplingLayer(Layer):
    """Spatial pooling (reference nn/conf/layers/SubsamplingLayer +
    nn/layers/convolution/subsampling/SubsamplingLayer,
    CudnnSubsamplingHelper)."""

    kernel_size: Sequence[int] = (2, 2)
    stride: Sequence[int] = (2, 2)
    padding: Sequence[int] = (0, 0)
    pooling_type: PoolingType = PoolingType.MAX
    convolution_mode: Optional[ConvolutionMode] = None  # None -> inherit/Truncate
    pnorm: int = 2
    eps: float = 1e-8
    # Backward-pass implementation knob (ops/pooling.py): "auto" follows
    # the measured dispatch rule; MAX accepts "sns"/"mask", AVG
    # "window"/"conv". Selection is counted in
    # pooling_impl_selected_total{impl=} at trace time.
    pooling_impl: str = "auto"

    def input_kind(self):
        return "cnn"

    def _mode(self) -> ConvolutionMode:
        return self.convolution_mode or ConvolutionMode.TRUNCATE

    def set_input_type(self, input_type):
        if not isinstance(input_type, ConvolutionalType):
            raise ValueError(f"SubsamplingLayer needs CNN input, got {input_type}")
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        oh = conv_output_size(input_type.height, kh, sh, ph, self._mode())
        ow = conv_output_size(input_type.width, kw, sw, pw, self._mode())
        return ConvolutionalType(height=oh, width=ow, channels=input_type.channels)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout(x, self.dropout_rate, train, rng)
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self._mode() == ConvolutionMode.SAME:
            pads = ((0, 0), _same_pads(x.shape[1], kh, sh),
                    _same_pads(x.shape[2], kw, sw), (0, 0))
        else:
            ph, pw = _pair(self.padding)
            pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        window = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        spatial_pads = (pads[1], pads[2])
        pt = self.pooling_type
        if pt == PoolingType.MAX:
            # Backward-emitter dispatch (ops/pooling.py): "sns" keeps
            # XLA's reduce_window + select-and-scatter VJP — the fastest
            # formulation for VGG16-sized pools (reshape-max and
            # strided-slice max measured SLOWER, 178 -> 197 / 243
            # ms/step, docs/perf_vgg16.md); "mask" swaps in the
            # argmax-equality-mask backward (no S&S). "auto" follows the
            # measured rule in docs/perf_googlenet.md round 6.
            impl = pool_ops.select_pooling_impl(
                "max", (kh, kw), (sh, sw), requested=self.pooling_impl)
            out = pool_ops.max_pool(x, (kh, kw), (sh, sw), spatial_pads,
                                    impl=impl)
        elif pt == PoolingType.SUM:
            out = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        elif pt == PoolingType.AVG:
            # Divisor counts only in-bounds elements (matches reference
            # average-pool edge behavior under padding); "conv" trades
            # the reduce_window pair for a depthwise conv whose backward
            # is a transposed conv.
            impl = pool_ops.select_pooling_impl(
                "avg", (kh, kw), (sh, sw), requested=self.pooling_impl)
            out = pool_ops.avg_pool(x, (kh, kw), (sh, sw), spatial_pads,
                                    impl=impl)
        elif pt == PoolingType.PNORM:
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides,
                                  pads)
            out = (s + self.eps) ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type {pt}")
        return out, state


@serde.register
@dataclass
class Subsampling1DLayer(SubsamplingLayer):
    """1D pooling over [batch, time, features] (reference
    Subsampling1DLayer)."""

    kernel_size: Sequence[int] = (2,)
    stride: Sequence[int] = (2,)
    padding: Sequence[int] = (0,)

    def input_kind(self):
        return "rnn"

    def set_input_type(self, input_type):
        from ..conf.inputs import RecurrentType
        if not isinstance(input_type, RecurrentType):
            raise ValueError(f"Subsampling1DLayer needs RNN input, got {input_type}")
        k, s = _pair(self.kernel_size)[0], _pair(self.stride)[0]
        p = _pair(self.padding)[0]
        t = input_type.timeseries_length
        out_t = None if t is None else conv_output_size(
            t, k, s, p, self._mode())
        return RecurrentType(size=input_type.size, timeseries_length=out_t)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x4 = x[:, :, None, :]
        kw_saved = self.kernel_size, self.stride, self.padding
        k = _pair(self.kernel_size)[0]
        s = _pair(self.stride)[0]
        p = _pair(self.padding)[0]
        layer2d = SubsamplingLayer(
            kernel_size=(k, 1), stride=(s, 1), padding=(p, 0),
            pooling_type=self.pooling_type, convolution_mode=self._mode(),
            pnorm=self.pnorm, eps=self.eps, dropout_rate=self.dropout_rate,
            pooling_impl=self.pooling_impl)
        out, _ = layer2d.forward(params, state, x4, train=train, rng=rng, mask=mask)
        return out[:, :, 0, :], state


@serde.register
@dataclass
class ZeroPaddingLayer(Layer):
    """Spatial zero padding (reference nn/conf/layers/ZeroPaddingLayer)."""

    padding: Sequence[int] = (1, 1)  # (top=bottom, left=right) or 4-tuple

    def input_kind(self):
        return "cnn"

    def _pads(self):
        p = list(self.padding)
        if len(p) == 2:
            return (p[0], p[0], p[1], p[1])
        if len(p) == 4:
            return tuple(p)
        raise ValueError("padding must be 2 or 4 ints")

    def set_input_type(self, input_type):
        if not isinstance(input_type, ConvolutionalType):
            raise ValueError(f"ZeroPaddingLayer needs CNN input, got {input_type}")
        t, b, l, r = self._pads()
        return ConvolutionalType(height=input_type.height + t + b,
                                 width=input_type.width + l + r,
                                 channels=input_type.channels)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self._pads()
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state


@serde.register
@dataclass
class BatchNormalization(Layer):
    """Batch normalization (reference nn/conf/layers/BatchNormalization +
    nn/layers/normalization/BatchNormalization.java,
    CudnnBatchNormalizationHelper). Feature axis = channels (NHWC) or the
    last axis for dense inputs. Running stats live in the layer state tree
    (the reference stores them as params globalMean/globalVar); decay matches
    the reference's `decay` (running = decay*running + (1-decay)*batch)."""

    n_out: int = 0  # feature count, inferred
    decay: float = 0.9
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False

    def input_kind(self):
        return "any"

    def set_input_type(self, input_type):
        if isinstance(input_type, ConvolutionalType):
            self.n_out = input_type.channels
        elif isinstance(input_type, FeedForwardType):
            self.n_out = input_type.size
        else:
            from ..conf.inputs import RecurrentType
            if isinstance(input_type, RecurrentType):
                self.n_out = input_type.size
            else:
                raise ValueError(f"BatchNormalization: unsupported {input_type}")
        return input_type

    def has_params(self):
        return not self.lock_gamma_beta

    def init_params(self, key, dtype=jnp.float32):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.full((self.n_out,), self.gamma_init, dtype),
                "beta": jnp.full((self.n_out,), self.beta_init, dtype)}

    def init_state(self, dtype=jnp.float32):
        return {"mean": jnp.zeros((self.n_out,), jnp.float32),
                "var": jnp.ones((self.n_out,), jnp.float32)}

    def param_reg(self, pname):
        return (0.0, 0.0)  # reference: no l1/l2 on gamma/beta

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout(x, self.dropout_rate, train, rng)
        axes = tuple(range(x.ndim - 1))  # all but feature axis
        if train:
            # Single-pass stats: E[x^2]-E[x]^2 (the cuDNN formulation).
            # jnp.var's mean((x-mean)^2) needs mean first, forcing XLA into
            # two sequential reduction passes over the activations; as
            # independent reductions of the same input they sibling-fuse
            # into ONE pass (profiled 22% of the ResNet50 step, halved).
            # Pivoting on the RUNNING mean bounds the f32 cancellation the
            # raw form hits when |mean| >> std, at zero cost: d var/d
            # pivot = 0 so any pivot is mathematically exact, and unlike a
            # pivot computed from x it cannot create a cycle that splits
            # the producer-conv+stats fusion (an x-derived pivot measured
            # -16% on the ResNet50 step). Cold start (running mean still
            # zero) matches cuDNN's unpivoted single-pass behavior; the
            # running mean converges to the batch mean within ~1/(1-decay)
            # iterations and the cancellation vanishes.
            xf = x.astype(jnp.float32)
            pivot = state["mean"]
            xc = xf - pivot
            mean_c = jnp.mean(xc, axes)
            var = jnp.maximum(jnp.mean(lax.square(xc), axes)
                              - lax.square(mean_c), 0.0)
            mean = mean_c + pivot
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        out = (x - mean) * inv
        if not self.lock_gamma_beta:
            out = out * params["gamma"] + params["beta"]
        return self._act()(out.astype(x.dtype)), new_state


@serde.register
@dataclass
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (reference nn/conf/layers/LocalResponseNormalization
    + nn/layers/normalization/LocalResponseNormalization.java,
    CudnnLocalResponseNormalizationHelper):
    out = x / (k + alpha * sum_{window} x^2)^beta."""

    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75
    n: int = 5  # window size over channels

    def input_kind(self):
        return "cnn"

    # Pallas kernel toggle (the optional-helper contract, reference
    # ConvolutionLayer.java:66-77). OFF by default: the round-5
    # in-workload A/B (AlexNet with and without the kernel, after
    # fixing the probe bug that had silently disabled the kernel in
    # every traced run) measured XLA's fused lax chain FASTER than the
    # VMEM kernel — the pallas_call is a fusion barrier and its
    # 128-lane channel padding doubles bytes for 64-channel layers
    # (docs/perf_googlenet.md). The kernel stays available for
    # channel-heavy geometries where the window pass dominates.
    use_pallas: bool = False

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        from ...ops import pallas_kernels as pk
        import jax as _jax
        # Eligibility is static (shape gate + backend). A kernel Mosaic
        # refuses fails the step's compile; nothing falls back to lax.
        if self.use_pallas and pk.lrn_supported(x) and \
                _jax.default_backend() == "tpu":
            return pk.lrn(x, self.k, self.alpha, self.beta, self.n), state
        return pk.lrn_reference(x, self.k, self.alpha, self.beta,
                                self.n), state


@serde.register
@dataclass
class GlobalPoolingLayer(Layer):
    """Global pooling over spatial (CNN→FF) or time (RNN→FF) dims with mask
    support (reference nn/conf/layers/GlobalPoolingLayer +
    util/MaskedReductionUtil)."""

    pooling_type: PoolingType = PoolingType.MAX
    pnorm: int = 2
    collapse_dimensions: bool = True

    def input_kind(self):
        return "any"

    def set_input_type(self, input_type):
        from ..conf.inputs import RecurrentType
        if isinstance(input_type, ConvolutionalType):
            return FeedForwardType(size=input_type.channels)
        if isinstance(input_type, RecurrentType):
            return FeedForwardType(size=input_type.size)
        raise ValueError(f"GlobalPoolingLayer: unsupported {input_type}")

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if x.ndim == 4:      # NHWC → pool over H, W
            axes = (1, 2)
            m = None
        elif x.ndim == 3:    # [batch, time, features] → pool over time
            axes = (1,)
            m = None if mask is None else mask[..., None]  # [b, t, 1]
        else:
            raise ValueError(f"GlobalPoolingLayer: rank {x.ndim} unsupported")
        pt = self.pooling_type
        if m is not None:
            if pt == PoolingType.MAX:
                x = jnp.where(m > 0, x, -jnp.inf)
            else:
                x = x * m
        if pt == PoolingType.MAX:
            out = jnp.max(x, axes)
        elif pt == PoolingType.SUM:
            out = jnp.sum(x, axes)
        elif pt == PoolingType.AVG:
            if m is not None:
                denom = jnp.clip(jnp.sum(m, axes), 1e-8, None)
                out = jnp.sum(x, axes) / denom
            else:
                out = jnp.mean(x, axes)
        elif pt == PoolingType.PNORM:
            p = float(self.pnorm)
            out = jnp.sum(jnp.abs(x) ** p, axes) ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type {pt}")
        return self._act()(out), state
