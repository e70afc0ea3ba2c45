"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline workload: zoo ResNet50 ImageNet-shape training (BASELINE.json
north star: >=35% MFU), bf16, batch 1024, one chip — images/sec/chip.
The reference publishes no numbers, so vs_baseline is reported against
the best previously-recorded run of this same bench
(BENCH_baseline.json, created on demand). `python bench.py lenet` runs
the LeNet-MNIST secondary workload.

By default the measurement runs in N=3 FRESH SUBPROCESSES (compile +
placement + timing each) and the printed line carries
median-of-processes plus {min, max} spread, a host-load sentinel (fixed
busy-loop calibration), and a loud "regression": true flag whenever
vs_baseline < 0.97. `--once` runs a single in-process measurement (what
each subprocess executes). BENCH_REPEATS overrides N.

One process per chip: this parent never imports jax. The device probe
and the children are sequential subprocesses, each gone before the next
starts. Every row names the backend the measuring child reported.

Fail-safe plane (optimize/scoreboard.py): children publish heartbeats on
a side channel and the parent watchdog tells alive-but-slow (extend)
from wedged (kill + typed failure); a device-liveness probe runs before
the first child. Every invocation appends a schema-validated row to
BENCH_ledger.jsonl (created on demand); `python bench.py check` is the
regression sentinel (non-zero exit on regression vs best-so-far with a
noise band) and `python bench.py report` renders the trajectory. No
full-config measurement means a non-zero exit: a dead device, or a first
child that wedges or times out, writes its typed ledger row and fails —
there is no reduced-config stand-in.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# ResNet50 fwd FLOPs at 224x224, multiply-add = 2 FLOPs (4.09 GMACs x 2);
# training step ~= 3x forward. Round 4 fixed a 2x undercount here: the
# old constants used the GMAC figures while claiming the 2x count
# (docs/perf_vgg16.md "accounting artifact").
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 8.18e9
# MFU denominator: the published bf16 peak FLOP/s of one chip, keyed by
# the device_kind jax reports. Source: Google Cloud documentation, "TPU
# v5e" (197 TFLOP/s bf16). A device that is not in the table is an error,
# not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}

# Raw per-repeat seconds from the most recent _measure call; run_once
# forwards them into the artifact extras and the ledger row.
_LAST_RAW_TIMES: list = []


def _beat(**kw):
    """Publish one heartbeat on the bench side channel (no-op unless the
    parent armed DL4JTPU_BENCH_HB_FILE)."""
    from deeplearning4j_tpu.optimize import scoreboard
    scoreboard.child_heartbeat(**kw)


def _measure(run, fence, repeats):
    """Shared warm-then-timed-repeats engine for the workload benches:
    one unmeasured warm pass (compile + placement), then `repeats` timed
    passes, each announced on the heartbeat channel so the parent
    watchdog sees (repeat, phase) progress instead of silence during a
    minutes-long compile. Returns the median repeat's seconds."""
    _beat(phase="warm")
    run()
    fence()
    times = []
    for r in range(repeats):
        _beat(repeat=r + 1, phase="measure")
        t0 = time.perf_counter()
        run()
        fence()
        times.append(time.perf_counter() - t0)
    _beat(phase="done")
    _LAST_RAW_TIMES[:] = times
    return sorted(times)[len(times) // 2]


def build_lenet(height=28, width=28, channels=1, num_classes=10, seed=42):
    """LeNet per reference zoo/model/LeNet.java: conv5x5x20 → maxpool2 →
    conv5x5x50 → maxpool2 → dense500(relu) → softmax output."""
    from deeplearning4j_tpu import (InputType, NeuralNetConfiguration,
                                    OutputLayer, DenseLayer, Adam, WeightInit)
    from deeplearning4j_tpu.nn.layers.convolution import (
        ConvolutionLayer, SubsamplingLayer, ConvolutionMode, PoolingType)

    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .activation("identity")
            .weight_init(WeightInit.XAVIER)
            .updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1),
                                    padding=(0, 0), n_out=20,
                                    convolution_mode=ConvolutionMode.SAME))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2),
                                    pooling_type=PoolingType.MAX,
                                    convolution_mode=ConvolutionMode.SAME))
            .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1),
                                    padding=(0, 0), n_out=50,
                                    convolution_mode=ConvolutionMode.SAME))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2),
                                    pooling_type=PoolingType.MAX,
                                    convolution_mode=ConvolutionMode.SAME))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=num_classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.convolutional(height, width, channels))
            .build())
    return conf


def bench_lenet(batch=2048, steps=50, repeats=3):
    import jax
    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.data.dataset import DataSet

    net = MultiLayerNetwork(build_lenet()).init()
    # AOT precompile (docs/perf_compile_cache.md): the train step and the
    # fused repeat dispatch compile BEFORE the first fit call — off the
    # warm-up line below and, when the persistent cache is enabled
    # (--once does), into it, so repeat processes deserialize instead of
    # recompiling.
    net.precompile(batch, repeat_steps=steps)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 28, 28, 1), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch)]
    # Device-resident batch: the metric is the compiled train-step rate
    # (host→device streaming is AsyncDataSetIterator's job, benched apart).
    ds = DataSet(jax.device_put(x), jax.device_put(y))

    # Fence = fetch the loss scalar. Fused multi-step loop
    # (scan-vs-loop bit-identical, tested).
    dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                  lambda: float(net.score_value), repeats)
    return (batch * steps) / dt, dt / steps


def bench_resnet50(batch=1024, steps=10, repeats=3):
    """Headline: batch 1024 sweeps the MXU best on one v5e chip (256:
    ~5.7k, 512: ~6.1k, 1024: ~6.3k, 2048: ~5.9k img/s measured
    2026-07-30); params/opt/state donate so buffers reuse in place."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.data.dataset import MultiDataSet

    g = ResNet50(num_labels=1000).init(dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    # Pre-cast to the training dtype so the timed loop measures the train
    # step, not a per-step 77MB f32->bf16 cast.
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3)), jnp.bfloat16))
    y = jax.device_put(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    mds = MultiDataSet([x], [y])
    # Fused multi-step loop (lax.scan over `steps` optimizer steps in one
    # dispatch): one host dispatch per `steps` device steps. Math is
    # scan-vs-loop bit-identical
    # (tests/test_graph.py::test_fused_multi_step_*).
    dt = _measure(lambda: g.fit_batch_repeated(mds, steps),
                  lambda: float(g.score_value), repeats)
    return (batch * steps) / dt


def bench_vgg16(batch=256, steps=10, repeats=3):
    """zoo VGG16 ImageNet-shape training img/s/chip (the companion row
    to ResNet50; reference zoo/model/VGG16.java). bf16, fused multi-step
    loop."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import VGG16
    from deeplearning4j_tpu.data.dataset import DataSet

    net = VGG16(num_labels=1000).init(dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3)), jnp.bfloat16))
    y = jax.device_put(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    ds = DataSet(x, y)
    dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                  lambda: float(net.score_value), repeats)
    return (batch * steps) / dt


# VGG16 (conv-only zoo variant) fwd FLOPs at 224x224, multiply-add = 2
# FLOPs (30.75 GFLOP fwd, per-layer arithmetic in docs/perf_vgg16.md);
# train ~3x forward.
VGG16_TRAIN_FLOPS_PER_IMAGE = 3 * 30.75e9

# Train-step FLOPs measured by XLA cost analysis of the ACTUAL jitted
# step (jit(net._train_step_raw).lower(...).compile().cost_analysis(),
# multiply-add = 2 convention verified against a known matmul; linear in
# batch to <3%). The zoo AlexNet is the reference's quirky variant
# (AlexNet.java:104-121: conv2 stride 2 + pool3 stride 7, both marked
# TODO in the reference source) — 1.35 GFLOP/img train, ~3x lighter
# than canonical AlexNet, hence byte/latency-bound (docs/
# perf_googlenet.md). Cross-check: the same method reproduces the
# analytic VGG16 constant within 3.3% (conv1_1 dgrad DCE'd).
ALEXNET_TRAIN_FLOPS_PER_IMAGE = 1.35e9
GOOGLENET_TRAIN_FLOPS_PER_IMAGE = 9.15e9
ATTENTION_TRAIN_FLOPS_PER_TOKEN = 5.72e6   # batch x 512, width 256
LSTM_TRAIN_FLOPS_PER_TOKEN = 2.02e5        # TextGenerationLSTM geometry


def bench_alexnet(batch=2048, steps=10, repeats=3, use_pallas=False):
    """zoo AlexNet training img/s/chip — the LRN workload (reference
    zoo/model/AlexNet.java; LRN helper parity
    CudnnLocalResponseNormalizationHelper.java). Default = the lax LRN
    (the measured-fastest path); `python bench.py alexnet_pallaslrn`
    re-runs with the Pallas kernel forced ON so its in-workload cost is
    a standing measured A/B. Round-5 finding: after fixing the probe
    bug that had silently kept every traced run on lax, the honest A/B
    at THIS row's config (batch 2048, bf16, 2026-07-31) shows lax ~3x
    FASTER (28.2k vs 9.3k img/s) — the standalone-op 1.9x
    never survived fusion+layout reality (docs/perf_googlenet.md)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import AlexNet
    from deeplearning4j_tpu.data.dataset import DataSet

    # bf16 like the resnet50/vgg16/googlenet rows: the workload is
    # byte-bound (docs/perf_googlenet.md) and halving bytes measured
    # 21.9k -> 28.8k img/s at b2048 (2026-07-31)
    net = AlexNet(num_labels=1000).init(dtype=jnp.bfloat16)
    if use_pallas:
        for layer in net.layers:
            if hasattr(layer, "use_pallas"):
                layer.use_pallas = True
        net._build_jitted()  # retrace with the Pallas LRN path
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3)), jnp.bfloat16))
    y = jax.device_put(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    ds = DataSet(x, y)
    dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                  lambda: float(net.score_value), repeats)
    return (batch * steps) / dt


def bench_googlenet(batch=512, steps=10, repeats=3):
    """zoo GoogLeNet (inception v1) training img/s/chip — the
    ComputationGraph inception-merge + LRN workload (reference
    zoo/model/GoogLeNet.java:83-180). bf16, fused multi-step loop.
    Batch sweep 2026-07-31: 128: 3.8k, 256: 4.2k, 512: 4.3k, 1024:
    4.3k img/s — 512 is the knee (AlexNet: 256: 14.1k, 512: 17.4k,
    1024: 18.8k, 2048: 21.9k, 4096 failed to compile;
    docs/perf_googlenet.md)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import GoogLeNet
    from deeplearning4j_tpu.data.dataset import MultiDataSet

    g = GoogLeNet(num_labels=1000).init(dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3)), jnp.bfloat16))
    y = jax.device_put(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    mds = MultiDataSet([x], [y])
    dt = _measure(lambda: g.fit_batch_repeated(mds, steps),
                  lambda: float(g.score_value), repeats)
    return (batch * steps) / dt


def bench_googlenet_pool_ab(batch=512, steps=10, repeats=3):
    """Standing A/B for the round-6 GoogLeNet attacks (ISSUE 10): full
    train-step img/s of the 2x2 grid {unfused, fused inception 1x1
    branches} x {sns, mask max-pool backward}. Fusion rides
    GoogLeNet(fuse_siblings=True) (nn/graph/fusion.py — exact concat
    rewrite, bitwise forward); the pool axis rides pooling_impl=
    (ops/pooling.py — S&S vs argmax-equality-mask backward, round-5
    profile put 9.5 ms/step at 2.1x byte bound in S&S). The dispatch
    defaults in select_pooling_impl / the zoo knobs ship whatever wins
    here; docs/perf_googlenet.md round 6 records the sweep. Each arm is
    a fresh net + fresh jit so the four compiles never share traces."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import GoogLeNet
    from deeplearning4j_tpu.data.dataset import MultiDataSet

    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3)), jnp.bfloat16))
    y = jax.device_put(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    mds = MultiDataSet([x], [y])

    arms = [(f"{'fused' if fuse else 'unfused'}_{impl}", fuse, impl)
            for fuse in (False, True) for impl in ("sns", "mask")]
    extras = {"batch": batch}
    best = None
    for name, fuse, impl in arms:
        g = GoogLeNet(num_labels=1000, fuse_siblings=fuse,
                      pooling_impl=impl).init(dtype=jnp.bfloat16)
        _beat(phase=f"arm_{name}")
        dt = _measure(lambda g=g: g.fit_batch_repeated(mds, steps),
                      lambda g=g: float(g.score_value), repeats)
        ips = (batch * steps) / dt
        # 3 decimals: CPU-host runs of this row sit at O(0.1) img/s and
        # the winner must still be resolvable from the extras.
        extras[f"img_s_{name}"] = round(ips, 3)
        extras[f"step_ms_{name}"] = round(dt / steps * 1e3, 1)
        extras[f"est_mfu_{name}"] = _mfu(ips,
                                         GOOGLENET_TRAIN_FLOPS_PER_IMAGE)
        if best is None or ips > best[1]:
            best = (name, ips)
        del g  # free the arm's buffers before the next compile
    extras["winner"] = best[0]
    return best[1], extras


def bench_attention(batch=64, seq_len=512, width=256, heads=8, steps=10,
                    repeats=3):
    """Self-attention char-model training tokens/sec (BEYOND-parity
    workload — the reference predates attention, SURVEY.md §5.7): two
    causal multi-head SelfAttention layers + RnnOutput, bf16, fused
    multi-step loop. The long-context companion row to `lstm`."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer,
                                    Sgd)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

    vocab = 96
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Sgd(0.1)).list()
            .layer(SelfAttentionLayer(n_out=width, n_heads=heads,
                                      causal=True, activation="relu"))
            .layer(SelfAttentionLayer(n_out=width, n_heads=heads,
                                      causal=True, activation="relu"))
            .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab))
            .build())
    net = MultiLayerNetwork(conf).init(dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, vocab, (batch, seq_len))
    x = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[idx], jnp.bfloat16))
    y = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[np.roll(idx, -1, 1)]))
    ds = DataSet(x, y)
    dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                  lambda: float(net.score_value), repeats)
    return (batch * seq_len * steps) / dt


def attention_train_flops_per_token(seq_len: int, width=256,
                                    vocab=96, causal_executed=True):
    """Derived (validated against XLA cost analysis at T=512 to 0.1%):
    projections are T-independent, the score/value matmuls scale with T.
    Head count cancels out (h heads of dim d contribute h * 2*d*T =
    2*width*T per matmul regardless of the split), so it is not a
    parameter. `causal_executed` counts the FLOPs the BLOCKWISE path
    executes for a causal model (lower-triangular blocks only, ~T/2 avg
    keys); dense executes the full [T,T] (masked), i.e. 2x the
    quadratic term."""
    proj = (3 * 2 * vocab * width + 2 * width * width) \
        + (3 * 2 * width * width + 2 * width * width) \
        + 2 * width * vocab
    attn_per_layer = 2 * 2 * width * (seq_len // 2 if causal_executed
                                      else seq_len)
    return 3 * (proj + 2 * attn_per_layer)


def attention_op_flops_per_token(seq_len: int, width=512, bwd=True,
                                 causal_executed=True):
    """Attention-op-only FLOPs per token (the projections are excluded —
    bench_attention_ab times the bare op). Forward: 2 block matmuls
    (QK^T, PV) over ~T/2 executed keys when causal. Backward: 5 block
    matmuls (recompute s, then dv, dp, dk, dq), i.e. 2.5x forward — the
    flash recompute schedule, which all three impls share in spirit
    (dense re-materializes instead but runs the same contraction
    count)."""
    keys = seq_len // 2 if causal_executed else seq_len
    fwd = 2 * 2 * width * keys
    return fwd + (5 * 2 * width * keys if bwd else 0)


def bench_attention_ab(seq_len=4096, width=512, heads=4, steps=3,
                       repeats=3):
    """Standing op-level A/B (ISSUE 7): fwd+bwd wall time of causal
    dense vs blockwise vs fused-Pallas attention at the longctx geometry
    (head_dim 128, tokens/step 32k). The dispatch rule in
    ops.attention.select_attention_impl ships whatever wins here;
    docs/perf_attention.md records the v5e sweep. Off-TPU the pallas
    column is absent (probe fails → clean fallback, never a crash)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import flash_attention as fa

    batch = max(1, 32768 // seq_len)
    d = width // heads
    rng = np.random.default_rng(0)

    def mk():
        return jax.device_put(jnp.asarray(
            rng.standard_normal((batch, seq_len, heads, d)), jnp.bfloat16))

    q, k, v, g = mk(), mk(), mk(), mk()
    impls = {"dense": lambda q, k, v: att.dense_attention(q, k, v,
                                                          causal=True)}
    blk = att.pick_block_size(seq_len, 0)
    if blk:
        impls["blockwise"] = lambda q, k, v: att.blockwise_attention(
            q, k, v, causal=True, q_block=blk, kv_block=blk)
    if fa.flash_attention_supported(seq_len, seq_len, d) and \
            fa.flash_attention_available():
        impls["pallas"] = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True)

    fpt = attention_op_flops_per_token(seq_len, width)
    extras = {"batch": batch, "seq_len": seq_len}
    best = None
    for name, fn in impls.items():
        def loss(q, k, v, fn=fn):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * g.astype(jnp.float32))

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        _beat(phase=f"warm_{name}")
        jax.block_until_ready(step(q, k, v))  # compile + warm
        times = []
        for r in range(repeats):
            _beat(repeat=r + 1, phase=f"measure_{name}")
            t0 = time.perf_counter()
            out = None
            for _ in range(steps):
                out = step(q, k, v)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        dt = sorted(times)[len(times) // 2] / steps
        tps = batch * seq_len / dt
        extras[f"fwdbwd_ms_{name}"] = round(dt * 1e3, 2)
        extras[f"est_mfu_{name}"] = _mfu(tps, fpt)
        if best is None or tps > best[1]:
            best = (name, tps)
    extras["winner"] = best[0]
    if "pallas" in impls:
        # Satellite A/B (ISSUE 13): bf16 backward accumulators vs the
        # f32 default — max-abs gradient drift across dq/dk/dv at this
        # geometry (the bwd_acc_dtype knob's standing honesty row;
        # docs/perf_attention.md records the measured number).
        def acc_grads(dt_name):
            def loss(q, k, v):
                return jnp.sum(fa.flash_attention(
                    q, k, v, causal=True,
                    bwd_acc_dtype=dt_name).astype(jnp.float32)
                    * g.astype(jnp.float32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        _beat(phase="acc_ab")
        g32 = jax.block_until_ready(acc_grads("float32"))
        g16 = jax.block_until_ready(acc_grads("bfloat16"))
        drift = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(g32, g16))
        extras["bwd_acc_bf16_max_grad_drift"] = round(drift, 6)
    return best[1], extras


def bench_attention_longctx(seq_len=8192, width=512, heads=4, steps=5,
                            repeats=3, impl="auto"):
    """LONG-context single-chip training tokens/sec: 2-layer causal
    self-attention char model at seq 4k-16k where the [T, T] matrix
    dominates — routed through blockwise flash-style attention
    (ops/attention.py blockwise_attention; auto at t >= 2048), which
    keeps live memory O(T x block) and skips the upper-triangular
    blocks. Geometry is TPU-shaped: width 512 over 4 heads = head_dim
    128, filling the 128-lane MXU contraction (the `attention` row's
    d=32 starves it — docs/perf_attention.md). Batch scales down with T
    (tokens/step constant at 32k). est_mfu uses the EXECUTED
    (lower-triangular) FLOP count."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer,
                                    Sgd)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

    batch = max(1, 32768 // seq_len)
    vocab = 96
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Sgd(0.1)).list()
            .layer(SelfAttentionLayer(n_out=width, n_heads=heads,
                                      causal=True, activation="relu",
                                      attention_impl=impl))
            .layer(SelfAttentionLayer(n_out=width, n_heads=heads,
                                      causal=True, activation="relu",
                                      attention_impl=impl))
            .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab))
            .build())
    net = MultiLayerNetwork(conf).init(dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, vocab, (batch, seq_len))
    x = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[idx], jnp.bfloat16))
    y = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[np.roll(idx, -1, 1)]))
    ds = DataSet(x, y)
    dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                  lambda: float(net.score_value), repeats)
    tps = (batch * seq_len * steps) / dt
    fpt = attention_train_flops_per_token(seq_len, width)
    # the impl the dispatch actually picked for this geometry (same rule
    # the layer trace ran — select is deterministic in (t, d, impl))
    from deeplearning4j_tpu.ops.attention import select_attention_impl
    picked = select_attention_impl(seq_len, width // heads,
                                   requested=impl)
    return tps, {"batch": batch, "seq_len": seq_len,
                 "attention_impl": picked,
                 "est_mfu": _mfu(tps, fpt)}


def bench_attention_packed(bucket=4096, n_seqs=32, width=512, heads=4,
                           steps=3, repeats=3):
    """Packed vs padded varlen training tokens/sec (ISSUE 13): ragged
    lognormal-length sequences (median ~30% of the bucket, capped at
    bucket) trained two ways at the SAME canonical [rows, bucket] shape —
    one-sequence-per-row zero-padding with a key mask, vs first-fit
    packing with in-kernel segment masks (data/padding.pack_sequences +
    SelfAttentionLayer packed_segments). Both arms step on the SAME real
    tokens under the rank-2 zero-weight loss contract, so tokens/sec =
    real_tokens/wall and the ratio is pure density win: packing needs
    ~utilization x n_seqs rows instead of n_seqs. The headline value is
    the PACKED arm; extras carry the padded arm, the speedup, and the
    utilization so the ratio is interpretable."""
    import math

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer,
                                    Sgd)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.padding import (first_fit_pack,
                                                 pack_sequences)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

    vocab = 96
    rng = np.random.default_rng(0)
    # Ragged real-corpus-ish lengths: lognormal with median 30% of the
    # bucket, sigma 0.8, clipped to [8, bucket] — mean utilization lands
    # ~35-45%, the regime packing exists for.
    lengths = np.clip(rng.lognormal(math.log(bucket * 0.3), 0.8,
                                    n_seqs).astype(np.int64),
                      8, bucket).astype(np.int32)
    idx = rng.integers(0, vocab, (n_seqs, bucket))
    eye = np.eye(vocab, dtype=np.float32)
    feats = eye[idx]
    labels = eye[np.roll(idx, -1, 1)]
    t_idx = np.arange(bucket)[None, :]
    key_mask = (t_idx < lengths[:, None]).astype(np.float32)
    feats = feats * key_mask[..., None]
    labels = labels * key_mask[..., None]
    real_tokens = int(lengths.sum())

    def mk_net(packed):
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Sgd(0.1)).list()
                .layer(SelfAttentionLayer(n_out=width, n_heads=heads,
                                          causal=True, activation="relu",
                                          packed_segments=packed))
                .layer(SelfAttentionLayer(n_out=width, n_heads=heads,
                                          causal=True, activation="relu",
                                          packed_segments=packed))
                .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(vocab))
                .build())
        return MultiLayerNetwork(conf).init(dtype=jnp.bfloat16)

    def arm(name, net, ds):
        _beat(phase=f"arm_{name}")
        dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                      lambda: float(net.score_value), repeats)
        return real_tokens * steps / dt

    # Padded arm: one sequence per row, zero-weight pad tail.
    padded_ds = DataSet(
        jax.device_put(jnp.asarray(feats, jnp.bfloat16)),
        jax.device_put(jnp.asarray(labels)),
        jax.device_put(jnp.asarray(key_mask)),
        jax.device_put(jnp.asarray(key_mask)))
    padded_tps = arm("padded", mk_net(False), padded_ds)

    # Packed arm: first-fit into segment-masked rows, same real tokens.
    bins = first_fit_pack(lengths, bucket)
    pf, pl, seg, lm, _pos = pack_sequences(feats, labels, lengths, bucket,
                                           bins=bins)
    packed_ds = DataSet(
        jax.device_put(jnp.asarray(pf, jnp.bfloat16)),
        jax.device_put(jnp.asarray(pl)),
        jax.device_put(jnp.asarray(seg)),
        jax.device_put(jnp.asarray(lm)))
    packed_tps = arm("packed", mk_net(True), packed_ds)

    util = real_tokens / float(n_seqs * bucket)
    return packed_tps, {
        "bucket": bucket,
        "n_seqs": n_seqs,
        "rows_packed": len(bins),
        "mean_utilization": round(util, 3),
        "pack_fill": round(real_tokens / float(len(bins) * bucket), 3),
        "padded_tokens_per_sec": round(padded_tps, 1),
        "packed_vs_padded": round(packed_tps / padded_tps, 2),
    }


def bench_lstm(batch=128, seq_len=64, steps=30, repeats=3):
    """GravesLSTM char-RNN tokens/sec (zoo TextGenerationLSTM workload;
    reference zoo/model/TextGenerationLSTM.java)."""
    import jax
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.data.dataset import DataSet

    model = TextGenerationLSTM(num_labels=77, input_shape=(seq_len, 77))
    net = model.init()
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 77, (batch, seq_len))
    x = np.eye(77, dtype=np.float32)[idx]
    y = np.eye(77, dtype=np.float32)[np.roll(idx, -1, axis=1)]
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    # Fused multi-step: each repeat = the full tBPTT window schedule in
    # one dispatch (bit-identical to the per-window loop,
    # tests/test_multilayer.py), so the bench measures the windows'
    # device time rather than per-window dispatch latency.
    dt = _measure(lambda: net.fit_batch_repeated(ds, steps),
                  lambda: float(net.score_value), repeats)
    return (batch * seq_len * steps) / dt


def bench_w2v(vocab=50_000, sentences=10_000, sent_len=40, epochs=1):
    """Word2Vec skip-gram negative-sampling words/sec, END-TO-END with
    the device-corpus engine (nlp/distributed.py): corpus upload +
    device-side pair generation/negative sampling/updates, lax.scan over
    chunks. Replaced the host-pair-generation path (57-137k words/sec,
    host-bound — the round-2 VERDICT item) at 4x+ its rate; the
    AggregateSkipGram role (SkipGram.java:176-283) now genuinely lives
    on the device. `python bench.py w2v large` runs the
    production-scale geometry (1M vocab, 10M-token corpus — the r3
    VERDICT "toy-sized bench" item)."""
    from deeplearning4j_tpu.nlp.distributed import (ShardedWord2Vec,
                                                    corpus_arrays)
    from deeplearning4j_tpu.nlp.vocab import VocabCache

    rng = np.random.default_rng(0)
    # zipf-ish frequencies like natural text; ONE vectorized draw (the
    # per-sentence rng.choice(p=...) loop redoes the 1M-entry cumsum per
    # sentence — minutes of setup at production scale)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.05
    probs /= probs.sum()
    mat = rng.choice(vocab, size=(sentences, sent_len), p=probs)
    corpus = mat.astype(np.int32)
    cache = VocabCache()
    flat, counts = np.unique(corpus, return_counts=True)
    for w, c in zip(flat, counts):
        cache.add_token(str(w), count=int(c))
    cache.finish(min_word_frequency=1)
    remap = np.zeros(vocab, np.int32)
    for w in flat:
        remap[w] = cache.index_of(str(w))
    toks, sids = corpus_arrays(list(remap[corpus]))
    # chunk 16384 x 8 steps/dispatch swept best 2026-07-30 (4096/16:
    # 561k, 8192/16: 560k, 16384/8: 584k words/sec)
    trainer = ShardedWord2Vec(cache, layer_size=128, window=5, negative=5,
                              chunk=16384, steps_per_call=8, seed=1)
    _beat(phase="warm")
    trainer.fit_corpus(toks, sids, epochs=1)  # warm compile
    _ = np.asarray(trainer.tables["syn0"][:1])  # fence the warm-up
    total_words = len(toks) * epochs
    _beat(repeat=1, phase="measure")
    t0 = time.perf_counter()
    trainer.fit_corpus(toks, sids, epochs=epochs)
    _ = np.asarray(trainer.tables["syn0"][:1])  # device fence
    dt = time.perf_counter() - t0
    _LAST_RAW_TIMES[:] = [dt]
    return total_words / dt


def bench_etl(n_images=768, src=256, dst=224, workers=8, epochs=3):
    """HOST-side image pipeline images/sec at the headline geometry:
    PPM decode → native bilinear resize 256→224 → batch assembly →
    native u8→f32 scale (no device). This is the feed side of the async
    pipeline."""
    import shutil
    import tempfile
    from deeplearning4j_tpu.data.fetchers import synthesize_lfw_dir
    from deeplearning4j_tpu.data.images import (
        ImageRecordReader, ImageRecordReaderDataSetIterator)

    d = tempfile.mkdtemp(prefix="dl4jtpu_etl_bench_")
    try:
        synthesize_lfw_dir(d, num_people=8, per_person=n_images // 8,
                           size=src)
        reader = ImageRecordReader(dst, dst, 3, root=d)
        it = ImageRecordReaderDataSetIterator(reader, batch_size=64,
                                              workers=workers)
        _beat(phase="warm")
        for _ in it:  # warm: page cache + thread pool
            pass
        total = 0
        _beat(repeat=1, phase="measure")
        t0 = time.perf_counter()
        for _ in range(epochs):
            it.reset()
            for ds in it:
                total += ds.features.shape[0]
        dt = time.perf_counter() - t0
        return total / dt
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_lenet_hostfed(batch=2048, n_train=8192, epochs=2):
    """TRUE host-fed end-to-end: MNIST idx binaries on disk → fetcher →
    ImagePreProcessingScaler → AsyncDataSetIterator prefetch →
    host→device transfer → the same jitted LeNet train step as the
    device-resident `lenet` workload. The gap vs `lenet` prices the
    host→device feed (bench_etl shows the host pipeline side)."""
    import shutil
    import tempfile
    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.data.fetchers import MnistDataSetIterator
    from deeplearning4j_tpu.data.normalizers import ImagePreProcessingScaler

    d = tempfile.mkdtemp(prefix="dl4jtpu_hostfed_")
    try:
        from deeplearning4j_tpu.data.fetchers import synthesize_mnist_idx
        # synthesize explicitly: the iterator's synthesize=True writes
        # only the 1024-image default, silently shrinking the epoch
        synthesize_mnist_idx(d, n_train=n_train, n_test=64)
        net = MultiLayerNetwork(build_lenet()).init()
        it = MnistDataSetIterator(batch, num_examples=n_train,
                                  flatten=False, path=d)
        it.pre_processor = ImagePreProcessingScaler()
        served = it.total_examples()  # count what actually flows
        _beat(phase="warm")
        net.fit(it, epochs=1)  # warm: compile + page cache
        float(net.score_value)
        _beat(repeat=1, phase="measure")
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        float(net.score_value)
        dt = time.perf_counter() - t0
        # Per-batch ETL breakdown from the device prefetcher (host-side
        # pipeline wait vs host→device staging wait) — the split that
        # tells transfer-bound apart from pipeline-bound.
        extra = {"etl_host_ms": round(net.last_etl_host_ms, 2),
                 "etl_h2d_ms": round(net.last_etl_h2d_ms, 2)}
        return served * epochs / dt, extra
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _bench_serving_packed(clients=4, requests_per_client=25, bucket=128):
    """Companion measurement for the serving row: a tiny packed_segments
    attention model behind packed admission (parallel/inference.py),
    ragged [1, 4..32] requests coalescing into one segment-masked
    [1, bucket] row. Returns the extras block (rps + the packing
    counters/efficiency the observability satellite pre-registers)."""
    import queue as _queue
    import threading
    from deeplearning4j_tpu import (Adam, InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    feat = 8
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .list()
            .layer(SelfAttentionLayer(n_out=8, n_heads=2, causal=True,
                                      packed_segments=True))
            .layer(RnnOutputLayer(n_out=4, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(feat)).build())
    net = MultiLayerNetwork(conf).init()
    pi = ParallelInference(net, batch_limit=8, batch_timeout_ms=2.0,
                           queue_limit=1024, packed_admission=True,
                           pack_bucket=bucket)
    pi.warmup(max_bucket=1, time_steps=bucket)
    rng = np.random.default_rng(1)
    payloads = [rng.standard_normal((1, 4 + (i % 29), feat))
                .astype(np.float32) for i in range(16)]
    errors: "_queue.Queue" = _queue.Queue()

    def client(ci):
        try:
            for j in range(requests_per_client):
                pi.output(payloads[(ci + j) % len(payloads)])
        except Exception as e:
            errors.put(e)

    pi.output(payloads[0])  # seed the EWMA off the clock
    t0 = time.perf_counter()
    ts = [threading.Thread(target=client, args=(i,))
          for i in range(clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    if not errors.empty():
        raise errors.get()
    from deeplearning4j_tpu.optimize.metrics import registry as _reg
    eff = _reg().gauge("packing_efficiency").value(source="serve")
    out = {
        "requests_per_sec": round(clients * requests_per_client / dt, 1),
        "pack_bucket": bucket,
        "packed_requests": pi.total_packed_requests,
        "pack_fallbacks": pi.total_pack_fallbacks,
        "forwards": pi.total_forwards,
        "requests_per_forward": round(
            pi.total_packed_requests / max(1, pi.total_forwards), 2),
        "packing_efficiency": round(eff, 3),
    }
    pi.shutdown()
    return out


def bench_serving(clients=8, requests_per_client=200, batch_limit=8):
    """Serving gateway requests/sec (docs/serving.md): concurrent
    clients with mixed 1-5 row payloads through the continuous-batching
    gateway (in-process predict — the HTTP framing is stdlib, not the
    subsystem under measure), after warmup() so the steady state rides
    the AOT executables. Extras carry the latency percentiles, the shed
    count (0 expected — no deadlines here), and the coalescing rate
    (rows per forward) that continuous batching exists to maximize."""
    import queue as _queue
    import threading
    from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, OutputLayer,
                                    WeightInit)
    from deeplearning4j_tpu.serving import ServingGateway

    conf = (NeuralNetConfiguration.builder().seed(42)
            .updater(Adam(1e-3)).weight_init(WeightInit.XAVIER).list()
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(64))
            .build())
    net = MultiLayerNetwork(conf).init()
    gw = ServingGateway()
    gw.add_model("default", net, batch_limit=batch_limit,
                 queue_limit=1024)
    gw.warmup()
    rng = np.random.default_rng(0)
    payloads = [rng.standard_normal((1 + (i % 5), 64)).astype(np.float32)
                for i in range(16)]
    errors: "_queue.Queue" = _queue.Queue()

    def client(ci):
        try:
            for j in range(requests_per_client):
                gw.predict("default", payloads[(ci + j) % len(payloads)])
        except Exception as e:
            errors.put(e)

    # one unmeasured pass seeds the EWMA + any lazy route state
    gw.predict("default", payloads[0])
    _beat(repeat=1, phase="measure")
    t0 = time.perf_counter()
    ts = [threading.Thread(target=client, args=(i,))
          for i in range(clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    if not errors.empty():
        raise errors.get()
    total = clients * requests_per_client
    st = gw.stats()
    entry = gw.pool.get("default")
    forwards = max(1, entry.engine.total_forwards)
    served_rows = sum(entry.engine.executed_batch_sizes)
    gw.pool.shutdown()
    lat = st["latency"].get("default", {})
    # Serving-resilience counters (docs/serving.md) ride the extras so
    # every BENCH_*.json records chaos activity — including its absence
    # (all zeros on a healthy run).
    from deeplearning4j_tpu.optimize.metrics import registry as _reg
    reg = _reg()
    return total / dt, {
        "clients": clients,
        "p50_ms": lat.get("p50_ms", 0.0),
        "p99_ms": lat.get("p99_ms", 0.0),
        "shed": entry.engine.total_shed,
        "rows_per_forward": round(served_rows / forwards, 2),
        "batch_failures": int(reg.counter(
            "serving_batch_failures_total").total()),
        "breaker_transitions": int(reg.counter(
            "serving_breaker_transitions_total").total()),
        "breaker_state": int(reg.gauge(
            "serving_breaker_state").value(model="default")),
        "swaps_canary_rejected": int(reg.counter(
            "serving_swaps_total").value(model="default",
                                         outcome="canary_rejected",
                                         precision="fp32")),
        # Packed-admission companion row (docs/serving.md §packed):
        # short ragged requests through a segment-masked packed row.
        "serving_packed": _bench_serving_packed(),
    }


def bench_serving_multimodel(heads=3, clients=6, requests_per_client=120,
                             batch_limit=16, batch_timeout_ms=0.0):
    """Multi-model serving aggregate requests/sec (docs/serving.md
    §multi-model): N same-geometry heads served two ways on one device
    budget — first as independent tiered entries (critical/standard/
    batch, one continuous-batching engine each, WFQ-arbitrated), then as
    ONE FusedModelGroup (a single channel-concatenated forward; every
    member's traffic rides the shared batch). Each client is PINNED to
    one head and sends single-row payloads with zero batch linger — the
    thin-per-model regime fusion exists for: an independent engine sees
    only its own head's trickle (rows/forward near 1) while the fused
    engine coalesces all members' rows into one forward, so the speedup
    measures cross-model coalescing, not intra-model batching. The
    headline value is the fused aggregate rps; extras carry the
    independent baseline, the speedup, the per-tier latency percentiles
    from the tiered run, the typed tier-shed count, and the starvation
    totals (nonzero only for the batch tier, and only while it actually
    held queued work that higher tiers outranked — the pager signal the
    counter exists for; it can never grow on an idle entry)."""
    import queue as _queue
    import threading
    from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                    NeuralNetConfiguration, OutputLayer,
                                    WeightInit)
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.metrics import registry as _reg
    from deeplearning4j_tpu.serving import (FusedModelGroup,
                                            ServingGateway, TierShedError)

    def head(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-3)).weight_init(WeightInit.XAVIER)
                .graph_builder()
                .add_inputs("in")
                .add_layer("dense",
                           DenseLayer(n_out=128, activation="relu"), "in")
                .add_layer("out",
                           OutputLayer(n_out=10, activation="softmax",
                                       loss="mcxent"), "dense")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(32))
                .build())
        return ComputationGraph(conf).init()

    names = [f"head{i}" for i in range(heads)]
    tiers = ("critical", "standard", "batch")
    rng = np.random.default_rng(0)
    payloads = [rng.standard_normal((1, 32)).astype(np.float32)
                for i in range(16)]

    def drive(gw):
        errors: "_queue.Queue" = _queue.Queue()
        done = [0] * clients
        sheds = [0] * clients

        def client(ci):
            try:
                nm = names[ci % heads]  # pinned: per-model traffic is thin
                for j in range(requests_per_client):
                    try:
                        gw.predict(nm, payloads[(ci + j) % len(payloads)])
                        done[ci] += 1
                    except TierShedError:
                        sheds[ci] += 1  # typed graceful degradation
            except Exception as e:
                errors.put(e)

        for nm in names:  # seed EWMAs + lazy route state, unmeasured
            gw.predict(nm, payloads[0])
        _beat(repeat=1, phase="measure")
        t0 = time.perf_counter()
        ts = [threading.Thread(target=client, args=(i,))
              for i in range(clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        if not errors.empty():
            raise errors.get()
        return sum(done) / dt, sum(sheds)

    # --- independent tiered baseline: one engine per head -------------
    gw = ServingGateway()
    for i, nm in enumerate(names):
        gw.add_model(nm, head(7 + i), batch_limit=batch_limit,
                     queue_limit=1024, batch_timeout_ms=batch_timeout_ms,
                     tier=tiers[i % len(tiers)])
    gw.warmup()
    independent_rps, independent_sheds = drive(gw)
    tier_lat = gw.stats().get("tiers", {})
    gw.pool.shutdown()

    # --- fused: the same heads as ONE concatenated forward ------------
    gw = ServingGateway()
    grp = gw.add_fused_group(
        "fused", [(nm, head(7 + i)) for i, nm in enumerate(names)],
        batch_limit=batch_limit, queue_limit=1024,
        batch_timeout_ms=batch_timeout_ms, tier="critical", weight=2.0)
    gw.warmup()
    fused_rps, fused_sheds = drive(gw)
    engine = gw.pool.get(names[0]).engine
    forwards = max(1, engine.total_forwards)
    served_rows = sum(engine.executed_batch_sizes)
    gw.pool.shutdown()

    reg = _reg()
    return fused_rps, {
        "heads": heads,
        "clients": clients,
        "fused_rps": round(fused_rps, 1),
        "independent_rps": round(independent_rps, 1),
        "fused_speedup": round(fused_rps / max(independent_rps, 1e-9), 2),
        "fused_group": isinstance(grp, FusedModelGroup),
        "rows_per_forward_fused": round(served_rows / forwards, 2),
        "tier_latency_ms": {
            t: {"p50": v.get("p50_ms", 0.0), "p99": v.get("p99_ms", 0.0)}
            for t, v in tier_lat.items()},
        "tier_sheds": int(independent_sheds + fused_sheds),
        "starvation_total": int(reg.counter(
            "serving_starvation_total").total()),
        "sched_dispatches": int(reg.counter(
            "serving_sched_dispatch_total").total()),
    }


def bench_serving_autotune(run_s=6.0, shift_s=2.0, clients=3,
                           bulk_clients=2, linger_ms=8.0,
                           standard_slo_ms=6.0, interval_s=0.25,
                           window_s=2.0):
    """Self-tuning serving A/B (docs/observability.md §"The serving
    control loop"): the SAME deliberately mis-tuned gateway — a
    standard-tier `app` model stuck with a fat collector linger under a
    tight tier SLO — driven through the SAME chaos-shifted workload
    twice: once left alone (static arm), once with the AutoTuner armed
    at bench cadence (tuned arm). Mid-run a batch-tier `bulk` flood
    starts (the workload shift); the flight recorder is on in BOTH arms
    so phase attribution (queue_wait dominating the standard tier)
    routes the tuner's hill-climb at the linger knob through the same
    reconfigure seam POST /config drives. Headline is the post-shift
    standard-tier p99 speedup (static/tuned, client-observed); extras
    carry both p99s, the verdict, the tuner's move/freeze counters and
    its decision trail — the same rows appended to
    autotune_ledger.jsonl, so the BENCH row is auditable against the
    control loop's own ledger."""
    import queue as _queue
    import threading
    from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, OutputLayer,
                                    WeightInit)
    from deeplearning4j_tpu.optimize.metrics import registry as _reg
    from deeplearning4j_tpu.serving import (ServingGateway, SLOMonitor,
                                            TierShedError)
    from deeplearning4j_tpu.serving import flight_recorder

    def head(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Adam(1e-3)).weight_init(WeightInit.XAVIER).list()
                .layer(DenseLayer(n_out=64, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(32))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    payloads = [rng.standard_normal((1, 32)).astype(np.float32)
                for _ in range(16)]

    def build():
        gw = ServingGateway(latency_window_s=window_s)
        gw.add_model("app", head(7), batch_limit=8, queue_limit=1024,
                     batch_timeout_ms=linger_ms, tier="standard")
        gw.add_model("bulk", head(11), batch_limit=16, queue_limit=1024,
                     batch_timeout_ms=linger_ms, tier="batch")
        gw.pool.reconfigure_scheduler(
            tier_slo_ms={"standard": standard_slo_ms, "batch": 500.0})
        gw.warmup()
        return gw

    def drive(gw):
        """The chaos-shifted load: pinned app clients throughout, the
        bulk flood joining at shift_s. Returns (sorted post-shift app
        latencies in ms, total app requests served)."""
        errors: "_queue.Queue" = _queue.Queue()
        samples = [[] for _ in range(clients)]
        gw.predict("app", payloads[0])  # seed EWMAs, unmeasured
        gw.predict("bulk", payloads[0])
        _beat(repeat=1, phase="measure")
        start = time.perf_counter()
        shift_at = start + shift_s
        end = start + run_s

        def app_client(ci):
            try:
                i = 0
                while time.perf_counter() < end:
                    t0 = time.perf_counter()
                    try:
                        gw.predict("app", payloads[(ci + i) % len(payloads)])
                        samples[ci].append(
                            (t0, (time.perf_counter() - t0) * 1e3))
                    except TierShedError:
                        pass
                    i += 1
            except Exception as e:
                errors.put(e)

        def bulk_client(ci):
            try:
                i = 0
                while time.perf_counter() < shift_at:
                    time.sleep(0.02)
                while time.perf_counter() < end:
                    try:
                        gw.predict("bulk", payloads[i % len(payloads)])
                    except TierShedError:
                        time.sleep(0.001)  # typed backoff, keep flooding
                    i += 1
            except Exception as e:
                errors.put(e)

        ts = [threading.Thread(target=app_client, args=(i,))
              for i in range(clients)]
        ts += [threading.Thread(target=bulk_client, args=(i,))
               for i in range(bulk_clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if not errors.empty():
            raise errors.get()
        post = sorted(ms for cell in samples
                      for (t0, ms) in cell if t0 >= shift_at)
        return post, sum(len(cell) for cell in samples)

    def p99(vals):
        if not vals:
            return 0.0
        return vals[min(len(vals) - 1, int(round(0.99 * (len(vals) - 1))))]

    flight_recorder.enable()
    try:
        # --- static arm: the mis-tuned config left standing ------------
        gw = build()
        static_post, static_served = drive(gw)
        gw.pool.shutdown()

        # --- tuned arm: same config + the control loop at fast cadence -
        gw = build()
        tuner = gw.attach_tuner(
            monitor=SLOMonitor(gw.pool, window_s=window_s, min_samples=3),
            interval_s=interval_s, settle_ticks=1,
            breach_freeze_factor=5.0, freeze_cooldown_s=2.0)
        tuned_post, tuned_served = drive(gw)
        tuner.stop()
        trail = tuner.trail(200)
        tuned_final_linger = gw.pool.get("app").engine.batch_timeout_ms
        gw.pool.shutdown()
    finally:
        flight_recorder.disable()

    sp99, tp99 = p99(static_post), p99(tuned_post)
    reg = _reg()
    moves = {oc: int(reg.counter("serving_tuner_moves_total")
                     .total(outcome=oc))
             for oc in ("applied", "kept", "reverted", "neutral",
                        "refused")}
    # The decision trail rides the extras compacted (the full evidence
    # rows live in autotune_ledger.jsonl, keyed by the same seq).
    decision_trail = [
        {k: e[k] for k in ("seq", "kind", "knob", "outcome", "old",
                           "new", "reason") if k in e}
        for e in trail][-24:]
    return sp99 / max(tp99, 1e-9), {
        "clients": clients,
        "bulk_clients": bulk_clients,
        "run_s": run_s,
        "shift_s": shift_s,
        "standard_slo_ms": standard_slo_ms,
        "static_linger_ms": linger_ms,
        "tuned_final_linger_ms": round(float(tuned_final_linger), 3),
        "static_p99_ms": round(sp99, 2),
        "tuned_p99_ms": round(tp99, 2),
        "tuner_win": bool(tp99 < sp99),
        "post_shift_requests": {"static": len(static_post),
                                "tuned": len(tuned_post)},
        "served_requests": {"static": static_served,
                            "tuned": tuned_served},
        "tuner_moves": moves,
        "tuner_reverts": int(reg.counter(
            "serving_tuner_reverts_total").total()),
        "tuner_freezes": int(reg.counter(
            "serving_tuner_freezes_total").total()),
        "tuner_frozen": int(reg.gauge("serving_tuner_frozen").value()),
        "decision_trail": decision_trail,
    }


def bench_serving_quant(clients=4, requests_per_client=40, batch_limit=16,
                        n_in=1024, hidden=2048):
    """Quantized-serving A/B (docs/serving.md §quantized): ONE gateway,
    three precision arms driven through the REAL swap plane. The fp32
    arm serves the published checkpoint as-is; then `swap(quantize=
    "int8")` and `swap(quantize="bf16")` promote quantized trees behind
    the same golden-batch canary production uses, and the identical
    client load re-runs against each. The model is deliberately
    matmul-heavy (n_in->hidden->hidden->10 dense) so the arms measure
    the quantized kernels, not framing overhead. Headline is the int8
    arm's requests/sec; extras carry every arm's rps + client-side p99,
    the speedups, the golden-batch max drift each precision introduced
    vs the fp32 outputs (the same quantity `canary_max_drift` budgets),
    and the measured quant_matmul dispatch verdict. Honesty rule: all
    three arms stay standing — the ledger row records the loser too."""
    import queue as _queue
    import tempfile
    import threading
    from deeplearning4j_tpu import (Adam, DenseLayer, InputType,
                                    MultiLayerNetwork,
                                    NeuralNetConfiguration, OutputLayer,
                                    WeightInit)
    from deeplearning4j_tpu import native_quant
    from deeplearning4j_tpu.ops import pallas_kernels
    from deeplearning4j_tpu.optimize.resilience import CheckpointManager
    from deeplearning4j_tpu.serving import ServingGateway

    conf = (NeuralNetConfiguration.builder().seed(42)
            .updater(Adam(1e-3)).weight_init(WeightInit.XAVIER).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    golden = rng.standard_normal((batch_limit, n_in)).astype(np.float32)
    payloads = [rng.standard_normal(
        (1 + (i % batch_limit), n_in)).astype(np.float32)
        for i in range(16)]

    def drive(gw):
        errors: "_queue.Queue" = _queue.Queue()
        lat_ms = [[] for _ in range(clients)]

        def client(ci):
            try:
                for j in range(requests_per_client):
                    t1 = time.perf_counter()
                    gw.predict("default",
                               payloads[(ci + j) % len(payloads)])
                    lat_ms[ci].append((time.perf_counter() - t1) * 1e3)
            except Exception as e:
                errors.put(e)

        # unmeasured seeding pass: touches every pow2 row bucket so a
        # freshly-swapped precision's first-trace compile (the
        # PrecompiledDispatch fall-through) is outside the clock
        for p in payloads:
            gw.predict("default", p)
        _beat(repeat=1, phase="measure")
        t0 = time.perf_counter()
        ts = [threading.Thread(target=client, args=(i,))
              for i in range(clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        if not errors.empty():
            raise errors.get()
        flat = sorted(x for c in lat_ms for x in c)
        p99 = flat[min(len(flat) - 1, int(len(flat) * 0.99))] if flat \
            else 0.0
        return clients * requests_per_client / dt, round(p99, 2)

    with tempfile.TemporaryDirectory(prefix="dl4jtpu_bench_quant_") as d:
        mgr = CheckpointManager(d)
        mgr.save(net)
        gw = ServingGateway()
        gw.add_model("default", net, checkpoints=mgr,
                     batch_limit=batch_limit, queue_limit=1024,
                     golden_batch=golden)
        gw.warmup()
        ref = np.asarray(gw.predict("default", golden), np.float32)
        arms = {}
        for precision in ("fp32", "int8", "bf16"):
            if precision != "fp32":
                res = gw.swap("default", quantize=precision)
                if res.get("swapped") is not True:
                    raise RuntimeError(
                        f"quantized swap to {precision} did not promote: "
                        f"{res}")
            rps, p99 = drive(gw)
            out = np.asarray(gw.predict("default", golden), np.float32)
            arms[precision] = dict(
                rps=rps, p99_ms=p99,
                max_drift=float(np.max(np.abs(out - ref))))
        gw.pool.shutdown()

    fp32_rps = max(arms["fp32"]["rps"], 1e-9)
    return arms["int8"]["rps"], {
        "clients": clients,
        "model": f"dense {n_in}x{hidden}x{hidden}x10",
        "fp32_rps": round(arms["fp32"]["rps"], 1),
        "int8_rps": round(arms["int8"]["rps"], 1),
        "bf16_rps": round(arms["bf16"]["rps"], 1),
        "quant_speedup_int8": round(arms["int8"]["rps"] / fp32_rps, 2),
        "quant_speedup_bf16": round(arms["bf16"]["rps"] / fp32_rps, 2),
        "p99_ms_fp32": arms["fp32"]["p99_ms"],
        "p99_ms_int8": arms["int8"]["p99_ms"],
        "p99_ms_bf16": arms["bf16"]["p99_ms"],
        "max_drift_int8": round(arms["int8"]["max_drift"], 6),
        "max_drift_bf16": round(arms["bf16"]["max_drift"], 6),
        "quant_matmul_impl": pallas_kernels.select_quant_impl(),
        "native_vnni": bool(native_quant.available()
                            and native_quant.vnni()),
    }


def bench_serving_decode(clients=6, prompts_per_client=4,
                         max_new_tokens=48, vocab=256, layers=4,
                         heads=4, head_dim=32, ff=512, max_context=256,
                         max_decode_batch=8):
    """Autoregressive decode A/B (docs/serving.md §decode): the SAME
    causal LM decodes greedily through two arms. The KV-cached arm is
    the real serving path — concurrent clients POST-shaped generate()
    calls through the gateway's DecodeEngine, prompts admitted via the
    packed prefill, then token-granularity continuous batching over the
    paged KV cache (steps are O(1) in sequence length). The naive arm
    re-runs the FULL sequence through the prefill executable for every
    token (O(t) per token, no cache, sequential) — the cost model the
    decode plane exists to beat. Headline is the KV-cached arm's
    tokens/sec; extras carry both arms, the speedup ratio, the engine's
    inter-token p99, and the paged cache's utilization receipt (real
    tokens / allocated block capacity). Honesty rule: both arms decode
    identical prompt sets with identical greedy semantics — token
    parity between the arms is asserted, so the speedup can never come
    from the cached arm doing different (or wrong) work."""
    import queue as _queue
    import threading
    from deeplearning4j_tpu.optimize.metrics import registry as _registry
    from deeplearning4j_tpu.serving import ServingGateway
    from deeplearning4j_tpu.serving import decode as serving_decode

    model = serving_decode.TransformerDecoder(
        vocab=vocab, layers=layers, heads=heads, head_dim=head_dim,
        ff=ff, max_context=max_context, seed=7)
    gw = ServingGateway()
    pack_bucket = min(128, max_context)
    entry = gw.add_decode_model(
        "lm", model, max_decode_batch=max_decode_batch,
        pack_bucket=pack_bucket,
        kv_block_tokens=16,
        kv_max_blocks=max(64, (max_context // 16) * max_decode_batch * 2))
    gw.warmup()
    cache = entry.engine.adapter.cache
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, size=ln).tolist()
               for ln in rng.integers(4, 33, size=clients
                                      * prompts_per_client)]

    errors: "_queue.Queue" = _queue.Queue()
    results: Dict[int, list] = {}
    kv_util = [0.0]
    stop_sampling = threading.Event()

    def sample_kv():
        while not stop_sampling.is_set():
            kv_util[0] = max(kv_util[0], cache.utilization())
            time.sleep(0.005)

    def client(ci):
        try:
            for j in range(prompts_per_client):
                pi = ci * prompts_per_client + j
                results[pi] = gw.generate(
                    "lm", prompts[pi], max_new_tokens=max_new_tokens)
        except Exception as e:
            errors.put(e)

    # unmeasured seeding pass so the clock starts hot on both arms
    gw.generate("lm", prompts[0], max_new_tokens=2)
    _beat(repeat=1, phase="measure")
    sampler = threading.Thread(target=sample_kv, daemon=True)
    sampler.start()
    t0 = time.perf_counter()
    ts = [threading.Thread(target=client, args=(i,))
          for i in range(clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    stop_sampling.set()
    sampler.join(timeout=1.0)
    if not errors.empty():
        raise errors.get()
    total_tokens = clients * prompts_per_client * max_new_tokens
    cached_tps = total_tokens / dt

    # engine-side inter-token tail over the measured window
    itl_vals = []
    for labels, child in _registry().histogram(
            "serving_inter_token_ms",
            "Wall time between a request's consecutive tokens "
            "(step + between-step scheduling)").items():
        if labels.get("model") == "lm":
            itl_vals = sorted(child.window_values(dt + 5.0))
    itl_p99 = itl_vals[min(len(itl_vals) - 1,
                           int(len(itl_vals) * 0.99))] if itl_vals else 0.0

    # naive arm: sequential full-recompute decode of the same prompts
    # (a subset scaled back up — O(t) per token makes the full set
    # prohibitively slow, which is the point)
    naive_n = min(len(prompts), max(2, clients))
    _beat(repeat=2, phase="measure")
    t0 = time.perf_counter()
    naive_out = [serving_decode.naive_generate(
        model, prompts[i], max_new_tokens, pad_to=pack_bucket)
        for i in range(naive_n)]
    naive_dt = time.perf_counter() - t0
    naive_tps = naive_n * max_new_tokens / max(naive_dt, 1e-9)
    for i in range(naive_n):
        if results.get(i) != naive_out[i]:
            raise RuntimeError(
                f"decode arms diverged on prompt {i}: the speedup would "
                "be measuring different work")
    gw.pool.shutdown()
    return cached_tps, {
        "clients": clients,
        "model": (f"decoder L{layers} H{heads}x{head_dim} "
                  f"ctx{max_context}"),
        "max_new_tokens": max_new_tokens,
        "tokens_per_sec": round(cached_tps, 1),
        "naive_tokens_per_sec": round(naive_tps, 1),
        "kv_cache_speedup": round(cached_tps / max(naive_tps, 1e-9), 2),
        "inter_token_p99_ms": round(itl_p99, 3),
        "kv_utilization": round(kv_util[0], 4),
        "kv_block_tokens": cache.block_tokens,
        "kv_max_blocks": cache.max_blocks,
        "arms_token_exact": True,
    }


def bench_serving_federation(clients=8, measure_s=4.0, chaos_s=3.0,
                             batch_limit=2, linger_ms=40.0):
    """Replica-federation scaling + chaos (docs/serving.md §"Replica
    federation"): a front-end routing over replica SUBPROCESSES, three
    arms on one fleet.

    CPU-ONLY, and the row says so in its metric name: every replica is
    pinned to JAX_PLATFORMS=cpu below, whatever backend this process
    has. A chip belongs to one process at a time, so replica
    subprocesses cannot share one (ROADMAP S7); federation has not run
    on the chip, and this row measures routing fan-out over throttled
    CPU replicas, nothing about an accelerator.

    Honesty note for this 1-core rig: aggregate rps cannot honestly
    scale with CPU-bound work (two processes sharing one core sum to
    one core). So each replica is configured DEVICE-BUDGET-bound
    instead: single-row requests always pay the collector linger, so a
    replica's ceiling is ~batch_limit/linger (~50 rps at 2/40 ms) while
    its CPU sits ~idle between forwards — the shape of a real
    accelerator-bound replica, where the forward budget, not the host,
    caps throughput. The front-end's pipeline cap (~300+ rps here) sits
    far above both arms, so the measured ratio is routing fan-out, not
    host contention.

    Arms: (1) one HEALTHY replica -> single_replica_rps; (2) two
    -> aggregate_rps, ratio = aggregate/single (the >=1.8x scaling
    claim); (3) chaos — SIGKILL one replica mid-storm: every client
    outcome must be 200 or a TYPED error body (non_typed_failures is
    asserted 0 by the scoreboard contract), and the eviction +
    failover-retry counters must actually fire."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request
    from deeplearning4j_tpu.optimize.metrics import registry as _registry
    from deeplearning4j_tpu.parallel.cluster_health import HealthConfig
    from deeplearning4j_tpu.serving.federation import (DEAD,
                                                       FederationFrontEnd,
                                                       spawn_replica)

    replica_env = {"JAX_PLATFORMS": "cpu",
                   "DL4JTPU_REPLICA_BATCH_LIMIT": str(int(batch_limit)),
                   "DL4JTPU_REPLICA_BATCH_TIMEOUT_MS": str(float(linger_ms))}
    n_in = 16  # default_builder geometry
    x = np.random.default_rng(0).standard_normal(
        (1, n_in)).astype(np.float32).tolist()  # single row: linger binds

    def post(url, payload, timeout=30.0):
        body = _json.dumps(payload).encode()
        req = urllib.request.Request(url, body,
                                     {"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, _json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, _json.loads(e.read())

    fe = FederationFrontEnd(
        health=HealthConfig(interval_s=0.25, timeout_s=2.0))
    fe.start()
    procs = []

    def storm(duration_s, on_mid=None):
        """Drive `clients` synchronous posters for duration_s. Returns
        (ok_count, typed_count, non_typed_count)."""
        stop = threading.Event()
        ok = [0] * clients
        typed = [0] * clients
        non_typed = [0] * clients

        def client(i):
            while not stop.is_set():
                try:
                    code, body = post(fe.url + "/predict",
                                      {"model": "default", "features": x})
                except Exception:
                    non_typed[i] += 1       # connection/parse error
                    continue
                if code == 200:
                    ok[i] += 1
                elif "reason" in body or "error" in body:
                    typed[i] += 1
                else:
                    non_typed[i] += 1       # non-200 without a type
        ts = [threading.Thread(target=client, args=(i,))
              for i in range(clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        if on_mid is not None:
            time.sleep(duration_s / 3.0)
            on_mid()
            time.sleep(duration_s * 2.0 / 3.0)
        else:
            time.sleep(duration_s)
        stop.set()
        for t in ts:
            t.join(timeout=30)
        dt = time.perf_counter() - t0
        return sum(ok), sum(typed), sum(non_typed), dt

    try:
        # ---- arm 1: single replica ------------------------------------
        procs.append(spawn_replica(0, fe.url, env=replica_env))
        if not fe.wait_for_replicas(1, timeout=240):
            raise RuntimeError("replica 0 never became healthy")
        storm(0.5)                          # unmeasured warm pass
        _beat(repeat=1, phase="measure")
        ok1, _, nt1, dt1 = storm(measure_s)
        single_rps = ok1 / dt1

        # ---- arm 2: two replicas --------------------------------------
        procs.append(spawn_replica(1, fe.url, env=replica_env))
        if not fe.wait_for_replicas(2, timeout=240):
            raise RuntimeError("replica 1 never became healthy")
        storm(0.5)
        _beat(repeat=2, phase="measure")
        ok2, _, nt2, dt2 = storm(measure_s)
        aggregate_rps = ok2 / dt2

        # ---- arm 3: chaos — SIGKILL one mid-storm ---------------------
        evc = _registry().counter("serving_replica_evictions_total", "")
        rtc = _registry().counter("serving_failover_retries_total", "")
        ev0, rt0 = evc.total(), rtc.total()
        _beat(repeat=3, phase="measure")
        ok3, typed3, nt3, _ = storm(chaos_s,
                                    on_mid=lambda: procs[1].kill())
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with fe._lock:
                if fe._replicas[1].state == DEAD:
                    break
            time.sleep(0.05)
        with fe._lock:
            evicted_dead = fe._replicas[1].state == DEAD
        evictions = evc.total() - ev0
        failover_retries = rtc.total() - rt0
        non_typed = nt1 + nt2 + nt3
        if not evicted_dead:
            raise RuntimeError("killed replica was never evicted")
        if evictions < 1:
            raise RuntimeError("chaos arm fired no eviction")
        return aggregate_rps, {
            "clients": clients,
            "replica_budget": f"{batch_limit} rows / {linger_ms} ms",
            "aggregate_rps": round(aggregate_rps, 1),
            "single_replica_rps": round(single_rps, 1),
            "scaling_ratio": round(aggregate_rps / max(single_rps, 1e-9),
                                   2),
            "chaos_ok": ok3,
            "chaos_typed": typed3,
            "evictions": int(evictions),
            "failover_retries": int(failover_retries),
            "non_typed_failures": int(non_typed),
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)
        fe.stop()


def bench_quant_matmul_ab(batch=8, k=1024, n=1024, repeats=50):
    """Op-level int8-matmul A/B (docs/perf_pallas.md honesty rule): time
    every standing arm — XLA `dot_general(preferred_element_type=s32)`,
    the native VNNI GEMM as an XLA typed-FFI custom call, and (TPU only) the
    Pallas kernel — at a serving-shaped [batch,k]x[n,k] problem, plus
    the fp32 matmul the quantized path replaces. Headline is the
    winning int8 arm's speedup over fp32; extras carry each arm's
    microseconds, the `select_quant_impl()` verdict the serving path
    actually dispatches on, and a bit-exactness cross-check between the
    int8 arms (they share one contract; disagreement is a kernel bug,
    not a tolerance)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu import native_quant
    from deeplearning4j_tpu.ops import pallas_kernels

    rng = np.random.default_rng(0)
    x_q = jnp.asarray(rng.integers(-127, 128, (batch, k), dtype=np.int8))
    w_q = jnp.asarray(rng.integers(-127, 128, (n, k), dtype=np.int8))
    x_f = jnp.asarray(rng.standard_normal((batch, k)).astype(np.float32))
    w_f = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))  # warm (trace+compile)
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return out, min(ts) * 1e6

    arms = {}
    ref, arms["xla_us"] = timed(
        jax.jit(pallas_kernels.int8_matmul_xla), x_q, w_q)
    _, arms["fp32_us"] = timed(jax.jit(jnp.matmul), x_f, w_f)
    agree = True
    if native_quant.available():
        out_n, arms["native_us"] = timed(
            jax.jit(pallas_kernels.int8_matmul_native), x_q, w_q)
        agree = agree and bool(jnp.array_equal(out_n, ref))
    if jax.default_backend() == "tpu" and \
            pallas_kernels.int8_pallas_available():
        out_p, arms["pallas_us"] = timed(
            jax.jit(pallas_kernels.int8_matmul_pallas), x_q, w_q)
        agree = agree and bool(jnp.array_equal(out_p, ref))
    int8_us = min(v for kk, v in arms.items()
                  if kk not in ("fp32_us",))
    winner = min((kk for kk in arms if kk != "fp32_us"),
                 key=lambda kk: arms[kk])
    speedup = arms["fp32_us"] / max(int8_us, 1e-9)
    return speedup, {
        "shape": f"{batch}x{k}x{n}",
        **{kk: round(v, 1) for kk, v in arms.items()},
        "winner": winner.replace("_us", ""),
        "dispatch_verdict": pallas_kernels.select_quant_impl(),
        "int8_arms_bit_exact": agree,
        "native_vnni": bool(native_quant.available()
                            and native_quant.vnni()),
    }


def _vs_baseline(metric, value, backend):
    """Track best-so-far per metric in BENCH_baseline.json (atomic
    write, corrupt-file tolerant, backend-namespaced keys — all via
    optimize/scoreboard; unsuffixed keys are TPU numbers, so a CPU-host
    run never scores against them)."""
    if "tiny" in metric:
        # smoke/test workloads must not pollute the scoreboard baseline
        return 1.0
    from deeplearning4j_tpu.optimize import scoreboard
    key = scoreboard.baseline_key(metric, backend)
    table = scoreboard.load_baseline()
    baseline = table.get(key)
    if baseline is None or value > baseline:
        table[key] = value
        scoreboard.save_baseline(table)
    return value / (baseline if baseline else value)


def _mfu(rate, flops_per_unit):
    """est_mfu against the published peak of the device that ran. Off
    TPU there is no such number: None (JSON null, "not measured"). A
    TPU whose device_kind is missing from PEAK_BF16_FLOPS is an error —
    add it to the table with its source, never a default."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in PEAK_BF16_FLOPS:
        raise SystemExit(
            f"bench: no published bf16 peak for device_kind "
            f"{dev.device_kind!r} in PEAK_BF16_FLOPS")
    return round(rate * flops_per_unit / PEAK_BF16_FLOPS[dev.device_kind],
                 3)


def run_once(workload: str, arg):
    """One in-process measurement at the workload's full config. Returns
    (metric, value, unit, extra). est_mfu accompanies every MXU workload
    (all dtypes: f32 convs/matmuls run default-precision — bf16
    multiplies, f32 accumulate — so the bf16 peak is the honest
    denominator for them too)."""
    _LAST_RAW_TIMES[:] = []
    metric, value, unit, extra = _dispatch_once(workload, arg)
    extra = dict(extra)
    if _LAST_RAW_TIMES:
        extra["raw_times_s"] = [round(t, 4) for t in _LAST_RAW_TIMES]
    return metric, value, unit, extra


def _dispatch_once(workload: str, arg):
    if workload == "lenet":
        ips, _ = bench_lenet()
        return "lenet_mnist_images_per_sec", ips, "images/sec", {}
    if workload == "lenet_tiny":
        # Deliberately small: the compile-cache smoke and the bench
        # survivability tests need a workload whose steady-state cost is
        # seconds, so what they measure is startup/compile behavior.
        ips, _ = bench_lenet(batch=64, steps=5, repeats=2)
        return "lenet_tiny_images_per_sec", ips, "images/sec", {}
    if workload == "lstm":
        ips = bench_lstm()
        return ("graveslstm_charrnn_tokens_per_sec", ips, "tokens/sec",
                {"est_mfu": _mfu(ips, LSTM_TRAIN_FLOPS_PER_TOKEN)})
    if workload == "w2v":
        if arg == "large":
            # production scale: 1M vocab x 10M tokens; embedding tables
            # 2 x 1M x 128 f32 = ~1.02 GB HBM + 40 MB corpus
            ips = bench_w2v(vocab=1_000_000, sentences=250_000)
            return ("word2vec_skipgram_ns_words_per_sec_1m_vocab", ips,
                    "words/sec", {"vocab": 1_000_000,
                                  "corpus_tokens": 10_000_000,
                                  "est_hbm_tables_mb": 1024})
        ips = bench_w2v()
        return "word2vec_skipgram_ns_words_per_sec", ips, "words/sec", {}
    if workload == "vgg16":
        ips = bench_vgg16()
        return ("vgg16_imagenet_bf16_images_per_sec_per_chip", ips,
                "images/sec", {"est_mfu": _mfu(ips, VGG16_TRAIN_FLOPS_PER_IMAGE)})
    if workload == "attention":
        ips = bench_attention()
        return ("selfattention_charmodel_tokens_per_sec", ips,
                "tokens/sec",
                {"est_mfu": _mfu(ips, ATTENTION_TRAIN_FLOPS_PER_TOKEN)})
    if workload == "googlenet":
        ips = bench_googlenet()
        return ("googlenet_imagenet_bf16_images_per_sec_per_chip", ips,
                "images/sec",
                {"est_mfu": _mfu(ips, GOOGLENET_TRAIN_FLOPS_PER_IMAGE)})
    if workload == "alexnet":
        ips = bench_alexnet(use_pallas=False)
        return ("alexnet_imagenet_bf16_images_per_sec_per_chip", ips,
                "images/sec",
                {"est_mfu": _mfu(ips, ALEXNET_TRAIN_FLOPS_PER_IMAGE)})
    if workload == "alexnet_pallaslrn":
        ips = bench_alexnet(use_pallas=True)
        return ("alexnet_imagenet_bf16_pallaslrn_images_per_sec_per_chip",
                ips, "images/sec",
                {"est_mfu": _mfu(ips, ALEXNET_TRAIN_FLOPS_PER_IMAGE)})
    if workload == "etl":
        ips = bench_etl()
        return "host_image_etl_images_per_sec", ips, "images/sec", {}
    if workload == "serving":
        rps, ext = bench_serving()
        return ("serving_gateway_requests_per_sec", rps, "requests/sec",
                ext)
    if workload == "serving_multimodel":
        rps, ext = bench_serving_multimodel()
        return ("serving_multimodel_requests_per_sec", rps,
                "requests/sec", ext)
    if workload == "serving_autotune":
        spd, ext = bench_serving_autotune()
        return ("serving_autotune_p99_speedup", spd, "x", ext)
    if workload == "serving_quant":
        rps, ext = bench_serving_quant()
        return ("serving_quant_int8_requests_per_sec", rps,
                "requests/sec", ext)
    if workload == "serving_decode":
        tps, ext = bench_serving_decode()
        return ("serving_decode_tokens_per_sec", tps, "tokens/sec", ext)
    if workload == "serving_federation":
        rps, ext = bench_serving_federation()
        return ("serving_federation_cpu_replicas_aggregate_rps", rps,
                "requests/sec", ext)
    if workload == "quant_matmul_ab":
        spd, ext = bench_quant_matmul_ab()
        return ("quant_matmul_ab_int8_speedup_vs_fp32", spd,
                "x", ext)
    if workload == "lenet_hostfed":
        ips, ext = bench_lenet_hostfed()
        return "lenet_mnist_hostfed_images_per_sec", ips, "images/sec", ext
    if workload == "attention_longctx":
        seq = int(arg) if arg else 8192
        tps, ext = bench_attention_longctx(seq_len=seq)
        return (f"attention_longctx_seq{seq}_tokens_per_sec", tps,
                "tokens/sec", ext)
    if workload == "attention_ab":
        seq = int(arg) if arg else 4096
        tps, ext = bench_attention_ab(seq_len=seq)
        return (f"attention_ab_seq{seq}_tokens_per_sec", tps,
                "tokens/sec", ext)
    if workload == "attention_packed":
        bucket = int(arg) if arg else 4096
        tps, ext = bench_attention_packed(bucket=bucket)
        return (f"attention_packed_seq{bucket}_tokens_per_sec", tps,
                "tokens/sec", ext)
    if workload == "resnet50":
        ips = bench_resnet50(batch=int(arg) if arg else 1024)
        return ("resnet50_imagenet_bf16_images_per_sec_per_chip", ips,
                "images/sec",
                {"est_mfu": _mfu(ips, RESNET50_TRAIN_FLOPS_PER_IMAGE)})
    if workload == "googlenet_pool_ab":
        batch = int(arg) if arg else 512
        ips, ext = bench_googlenet_pool_ab(batch=batch)
        return (f"googlenet_pool_ab_b{batch}_images_per_sec", ips,
                "images/sec", ext)
    raise SystemExit(
        f"Unknown workload {workload!r}; use resnet50 [batch] | vgg16 | "
        "googlenet | googlenet_pool_ab [batch] | attention | "
        "attention_longctx [seq] | "
        "attention_ab [seq] | attention_packed [bucket] | alexnet | "
        "alexnet_pallaslrn | lenet | lenet_tiny | lstm | w2v [scale] | "
        "etl | lenet_hostfed | serving | serving_multimodel | "
        "serving_autotune | serving_quant | serving_decode | "
        "serving_federation | quant_matmul_ab | check [metric...] | "
        "report")


def _register_metric_families():
    """Pre-register every subsystem's metric families at 0 so BENCH
    snapshots distinguish "never fired" from "absent"."""
    from deeplearning4j_tpu.data import padding as data_padding
    from deeplearning4j_tpu.nn.graph import fusion as graph_fusion
    from deeplearning4j_tpu.ops import pooling as pooling_ops
    from deeplearning4j_tpu.optimize import resilience, scoreboard
    from deeplearning4j_tpu.parallel import cluster_health
    from deeplearning4j_tpu.serving import autotuner as serving_autotuner
    from deeplearning4j_tpu.serving import breaker as serving_breaker
    from deeplearning4j_tpu.serving import decode as serving_decode
    from deeplearning4j_tpu.serving import federation as serving_federation
    from deeplearning4j_tpu.serving import flight_recorder
    from deeplearning4j_tpu.serving import gateway as serving_gateway
    from deeplearning4j_tpu.serving import model_pool as serving_pool
    from deeplearning4j_tpu.serving import scheduler as serving_scheduler
    # Recovery counters (rollbacks/retries — docs/robustness.md),
    # serving-resilience families (breaker states, batch failures,
    # canary rejections — docs/serving.md), cluster-health families
    # (peer beat-age/step-lag, desync/grace — docs/robustness.md
    # §cluster-health), round-6 dispatch families (pooling_impl/
    # sibling-fusion selections), and the round-11 bench scoreboard
    # families (bench_rows_total{status} et al).
    resilience.register_metrics()
    serving_breaker.register_metrics()
    serving_decode.register_metrics()
    serving_federation.register_metrics()
    serving_scheduler.register_metrics()
    serving_pool.register_metrics()
    serving_gateway.register_metrics()
    serving_autotuner.register_metrics()
    flight_recorder.register_metrics()
    cluster_health.register_metrics()
    pooling_ops.register_metrics()
    graph_fusion.register_metrics()
    scoreboard.register_metrics()
    data_padding.register_packing_metrics()


def _append_ledger(row):
    """Best-effort ledger append: the ledger must never take down the
    artifact (the artifact line on stdout is the contract; the ledger is
    the history). Schema violations are loud on stderr."""
    from deeplearning4j_tpu.optimize import scoreboard
    try:
        scoreboard.append_row(row)
    except (OSError, ValueError) as e:
        sys.stderr.write(f"bench: ledger append failed: {e}\n")


def _main_once(workload, arg):
    import jax
    from deeplearning4j_tpu.optimize import (compile_cache, scoreboard,
                                             telemetry)
    from deeplearning4j_tpu.optimize.metrics import registry
    from deeplearning4j_tpu.optimize.telemetry import CompilationTracker
    # Persistent XLA cache (docs/perf_compile_cache.md): a warm dir
    # turns each child's minutes-of-compile into deserialization.
    compile_cache.enable()
    _register_metric_families()
    # Liveness: beat thread + explicit (repeat, phase) beats from
    # _measure, read by the parent watchdog (no-op unless the parent
    # armed DL4JTPU_BENCH_HB_FILE).
    scoreboard.start_child_heartbeat(workload)
    with CompilationTracker() as trk:
        metric, ips, unit, extra = run_once(workload, arg)
    # XLA compilations the measurement triggered: warm-up should own
    # them all; steady-state recompiles (ragged shapes) show up here.
    # The full registry snapshot rides along so the BENCH artifact
    # carries device memory, ETL splits, and step counters without a
    # scrape endpoint (docs/observability.md).
    print(json.dumps({"metric": metric, "value": round(ips, 1),
                      "unit": unit, **extra,
                      "backend": jax.default_backend(),
                      "device_kind": jax.devices()[0].device_kind,
                      "device_count": jax.device_count(),
                      "xla_compilations": trk.count,
                      "compile_cache": compile_cache.status(),
                      "recompile_churn": telemetry.churn_offenders(),
                      "metrics": registry().snapshot()}))


def _main_check_report(argv):
    """`bench.py check [metric...]` — regression sentinel over the
    ledger (non-zero exit on regression); `bench.py report` — the
    round-over-round trajectory per metric."""
    from deeplearning4j_tpu.optimize import scoreboard
    from deeplearning4j_tpu.optimize.metrics import registry
    cmd, metrics = argv[0], argv[1:] or None
    rows = scoreboard.read_ledger()
    baseline = scoreboard.load_baseline()
    if cmd == "report":
        print(scoreboard.render_report(rows, baseline))
        return
    failures, lines = scoreboard.check_rows(rows, baseline,
                                            metrics=metrics)
    print("\n".join(lines) if lines else "  --  no scored rows")
    if failures:
        scoreboard.register_metrics()
        registry().counter("bench_regressions_total").inc(len(failures))
        print(f"bench check: {len(failures)} regression(s): "
              + ", ".join(failures))
        raise SystemExit(1)
    print("bench check: ok")


def main():
    argv = [a for a in sys.argv[1:] if a != "--once"]
    once = "--once" in sys.argv[1:]
    if argv and argv[0] in ("check", "report"):
        _main_check_report(argv)
        return
    workload = argv[0] if argv else "resnet50"
    arg = argv[1] if len(argv) > 1 else None

    if once:
        _main_once(workload, arg)
        return

    from deeplearning4j_tpu.optimize import scoreboard

    # Process-level repeats in FRESH processes. With the shared compile
    # cache below, the FIRST child pays compile and later children
    # measure run/placement variance (on backends without a persistent
    # cache every child pays compile, and the spread covers that too).
    # Motivation either way: the round-4 6852-vs-7014 "regression" was
    # run-to-run drift with no spread recorded to prove it.
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    # Total wall budget: cold per-child compiles can run minutes. Once
    # at least one child has measured, stop early (reporting the actual
    # n) rather than blow the budget.
    budget = float(os.environ.get("BENCH_TIME_BUDGET_S", "420"))
    # Watchdog knobs: a child whose heartbeats stop for BENCH_STALL_S is
    # wedged (killed, typed row); one still beating at its deadline is
    # alive-but-slow and may extend to deadline * (1 + BENCH_EXTEND_FRAC).
    stall_s = float(os.environ.get("BENCH_STALL_S", "180"))
    extend_frac = float(os.environ.get("BENCH_EXTEND_FRAC", "0.5"))
    # Children share the persistent compile cache (each --once child
    # calls compile_cache.enable(): JAX_COMPILATION_CACHE_DIR where set,
    # else the fixed in-checkout directory) — repeats then measure run
    # variance, not recompiles.
    sent_pre = scoreboard.host_sentinel_ms()

    # Device liveness BEFORE the first child: an unreachable device
    # reports as such in seconds instead of hanging the first child for
    # the whole budget. DL4JTPU_BENCH_PROBE=0 skips (tests, known-good
    # local backends).
    probe = None
    if os.environ.get("DL4JTPU_BENCH_PROBE", "1") != "0":
        probe = scoreboard.probe_device(timeout_s=float(
            os.environ.get("BENCH_PROBE_TIMEOUT_S", "120")))
        if probe.get("device") == "dead":
            _append_ledger(scoreboard.make_row(
                workload, "dead_device", timeout=True, probe=probe,
                failure="device dead at probe"))
            raise SystemExit(
                f"bench: device probe failed, nothing measured: "
                f"{probe.get('error')}")

    runs = []
    timed_out = False
    wedge_failure = None
    t_start = time.perf_counter()
    for i in range(repeats):
        elapsed = time.perf_counter() - t_start
        per_child = elapsed / max(1, len(runs)) if runs else 0.0
        if runs and elapsed + per_child > budget:
            sys.stderr.write(
                f"bench: stopping after {len(runs)} repeats "
                f"({elapsed:.0f}s elapsed, budget {budget:.0f}s)\n")
            break
        # hard per-child wall limit: a hung compile must not
        # blow the budget between checks (the child gets whatever
        # budget remains, never less than the floor so the first child
        # can always compile; BENCH_CHILD_MIN_S lets tests and tiny
        # rigs shrink the floor)
        child_floor = float(os.environ.get("BENCH_CHILD_MIN_S", "120"))
        child_limit = max(budget - elapsed, child_floor)
        res = scoreboard.run_child(
            [sys.executable, os.path.abspath(__file__), *argv, "--once"],
            deadline_s=child_limit, stall_timeout_s=stall_s,
            hard_cap_s=child_limit * (1.0 + extend_frac),
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
        if res.status in ("wedged", "timeout"):
            # Keep what completed at the full config; with nothing
            # completed there is no measurement, and the run fails.
            timed_out = True
            last = f"last beat {res.last_beat}" if res.last_beat \
                else "no beats"
            detail = (f"child {i} {res.status} after "
                      f"{res.duration_s:.0f}s ({res.beats} beats, {last})")
            sys.stderr.write(f"bench: {detail}\n")
            if res.status == "wedged":
                wedge_failure = "wedged"
            if runs:  # keep what we have; report the smaller n
                sys.stderr.write(
                    f"bench: reporting {len(runs)} repeats\n")
                break
            _append_ledger(scoreboard.make_row(
                workload, res.status, timeout=True, failure=detail,
                probe=probe))
            raise SystemExit(f"bench: nothing measured: {detail}")
        lines = res.stdout.strip().splitlines()
        if res.status == "failed" or not lines:
            sys.stderr.write(res.stderr[-2000:])
            raise SystemExit(
                f"bench subprocess failed (rc={res.returncode}, "
                f"{len(lines)} stdout lines)")
        runs.append(json.loads(lines[-1]))
    repeats = len(runs)
    # bracket the measurement window: the sentinel is re-sampled AFTER
    # the (minutes-long) repeats so contention arising mid-measurement
    # shows up; report the WORST bracket
    sent_post = scoreboard.host_sentinel_ms()
    sent_med = max(sent_pre[0], sent_post[0])
    sent_min = min(sent_pre[1], sent_post[1])
    vals = sorted(r["value"] for r in runs)
    med = runs[[r["value"] for r in runs].index(vals[len(vals) // 2])]
    vs = _vs_baseline(med["metric"], med["value"], med.get("backend"))
    row = {
        "metric": med["metric"],
        "value": med["value"],
        "unit": med["unit"],
        "vs_baseline": round(vs, 3),
        **{k: v for k, v in med.items()
           if k not in ("metric", "value", "unit")},
        "spread": {"n": repeats, "min": vals[0], "max": vals[-1]},
        "host_sentinel_ms": round(sent_med, 1),
        "host_sentinel_min_ms": round(sent_min, 1),
    }
    if timed_out:
        row["timeout"] = True
        if wedge_failure:
            row["failure"] = wedge_failure
    if vs < 0.97:
        # loud: the median of N fresh processes is >3% below the best
        # recorded run — check host_sentinel_ms (median vs min) before
        # blaming the program
        row["regression"] = True
    scoreboard.register_metrics()
    # A/B workloads (serving_multimodel fused-vs-independent) carry the
    # comparison into the ledger row itself — `bench.py report` and the
    # regression sentinel see the ratio without re-parsing artifacts.
    ledger_extras = {"raw_times_s": med.get("raw_times_s", [])}
    for k in ("fused_speedup", "independent_rps", "fused_group",
              "tier_latency_ms", "tier_sheds", "starvation_total",
              "fp32_rps", "int8_rps", "bf16_rps",
              "quant_speedup_int8", "quant_speedup_bf16",
              "max_drift_int8", "max_drift_bf16",
              "quant_matmul_impl", "winner", "dispatch_verdict",
              "int8_arms_bit_exact", "native_vnni",
              "static_p99_ms", "tuned_p99_ms", "tuner_win",
              "decision_trail", "tuner_moves", "tuner_freezes",
              "tokens_per_sec", "naive_tokens_per_sec",
              "kv_cache_speedup", "inter_token_p99_ms", "kv_utilization",
              "aggregate_rps", "single_replica_rps", "scaling_ratio",
              "chaos_ok", "chaos_typed", "evictions", "failover_retries",
              "non_typed_failures", "replica_budget", "clients"):
        if k in med:
            ledger_extras[k] = med[k]
    _append_ledger(scoreboard.make_row(
        workload, "wedged" if wedge_failure else "ok", med["metric"],
        float(med["value"]), med["unit"], timeout=timed_out,
        failure=wedge_failure,
        repeats=[float(r["value"]) for r in runs], probe=probe,
        spread=row["spread"], vs_baseline=row["vs_baseline"],
        backend=med.get("backend"), extras=ledger_extras))
    print(json.dumps(row))


if __name__ == "__main__":
    main()
