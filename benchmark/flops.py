"""Operations and bytes that the algorithms need, from shapes. The
yardstick's half of every utilization and roofline share: a PR that claims
a gain cannot edit these.

Conventions: a multiply-add is 2 operations; recomputed operations do not
count; a training step is forward + input gradient + filter gradient (the
first convolution has no input gradient); bytes are each operand read once
and each result written once in the type the configuration serves.
"""
from __future__ import annotations

from benchmark.reference import decoder as decoder_ref
from benchmark.reference import resnet50 as resnet50_ref


def resnet50_conv_work(cfg: dict) -> list:
    """Per convolution (dense head included) of one training step at one
    row: {name, flops} for forward, input gradient and filter gradient
    together."""
    out = []
    for i, s in enumerate(resnet50_ref.conv_specs(tuple(cfg["image"]),
                                                  cfg["labels"])):
        oh, ow = s["out_hw"]
        macs = s["k"] ** 2 * s["cin"] * s["cout"] * oh * ow
        passes = 2 if i == 0 else 3
        out.append(dict(name=s["name"], flops=2 * macs * passes))
    return out


def resnet50_train_flops_per_sample(cfg: dict) -> float:
    """Model FLOPs of one training step per image: the convolutions and
    the dense head (BatchNorm, ReLU, pooling and the updater are not
    matrix work and are left out, as MFU's convention has it). For the
    zoo model at 224x224 this is 3 x 2.22 GFLOP - not the paper's 3 x
    8.18, because the zoo's stage 2 strides by 2 (see the reference)."""
    return float(sum(c["flops"] for c in resnet50_conv_work(cfg)))


def decoder_matmul_params(cfg: dict) -> int:
    """Parameters that a token multiplies: the six matrices of each layer
    and the tied unembedding once (the embedding lookup is a gather)."""
    per_layer = sum(a * b for a, b in decoder_ref.leaf_shapes(cfg).values())
    return cfg["num_hidden_layers"] * per_layer \
        + cfg["vocab_size"] * cfg["hidden_size"]


def decoder_flops_per_token(cfg: dict, context: float = 0.0) -> float:
    """2 x matrix parameters, plus attention's 4 x hidden x context per
    layer (QK^T and PV over `context` keys)."""
    return 2.0 * decoder_matmul_params(cfg) \
        + 4.0 * cfg["hidden_size"] * context * cfg["num_hidden_layers"]


def decoder_step_bytes(cfg: dict, rows: int, kv_tokens: int,
                       elem_bytes: int = 4) -> float:
    """Bytes one decode step must read: every matrix weight once, and the
    K and V views of `rows` rows at `kv_tokens` tokens once."""
    kv = 2 * rows * kv_tokens * cfg["num_hidden_layers"] * cfg["hidden_size"]
    return float((decoder_matmul_params(cfg) + kv) * elem_bytes)
