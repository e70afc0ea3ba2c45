"""Packed varlen smoke (ISSUE 13): interpret-mode gate for the
segment-masked flash kernel, the PackToBucket packing arithmetic, and
the packed-layer exactness contract — the fast slice of
tests/test_segment_attention.py / test_packing.py, kept out of the
pytest budget like the other smokes.

1) Segment-masked flash (interpret) fwd+bwd parity vs dense with the
   same segment ids.
2) first_fit_pack + pack_sequences layout invariants (pure numpy).
3) A tiny packed_segments net: packed score == unpacked ragged score
   EXACTLY, and per-segment outputs bitwise-match solo forwards.
4) The packing metric families register and update.

Usage: JAX_PLATFORMS=cpu python tests/smoke_packing.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention as att
    from deeplearning4j_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 32, 2, 8
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)),
                             jnp.float32)
    q, k, v = mk(), mk(), mk()
    g = mk()
    seg_row = np.zeros(T, np.int32)
    seg_row[:13], seg_row[13:25], seg_row[25:] = 1, 2, 3
    seg = jnp.asarray(np.broadcast_to(seg_row, (B, T)).copy())

    # 1) segment-masked kernel parity, fwd + bwd
    got = fa.flash_attention(q, k, v, causal=True, segment_ids=seg,
                             q_block=16, kv_block=16, interpret=True)
    want = att.dense_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v) * g)

    gflash = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, segment_ids=seg, q_block=16, kv_block=16,
        interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gdense = jax.grad(loss(lambda q, k, v: att.dense_attention(
        q, k, v, causal=True, segment_ids=seg)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gflash, gdense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)
    print("smoke_packing: segment kernel fwd+bwd parity ok")

    # 2) packing arithmetic
    from deeplearning4j_tpu.data.padding import (first_fit_pack,
                                                 pack_sequences)
    lens = [5, 7, 3, 6, 2]
    bins = first_fit_pack(lens, 8)
    assert all(sum(lens[i] for i in b) <= 8 for b in bins)
    feat = rng.standard_normal((5, 8, 4)).astype(np.float32)
    lab = rng.standard_normal((5, 8, 3)).astype(np.float32)
    pf, pl, pseg, plm, pos = pack_sequences(feat, lab, lens, 8, bins=bins)
    assert int((pseg > 0).sum()) == sum(lens)
    assert int(plm.sum()) == sum(lens)
    print("smoke_packing: first-fit/pack_sequences layout ok")

    # 3) packed-layer exactness on a tiny net
    from deeplearning4j_tpu import (Adam, InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import (ExistingDataSetIterator,
                                                   PackToBucketIterator)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    F = 4
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .list()
            .layer(SelfAttentionLayer(n_out=8, n_heads=2, causal=True,
                                      packed_segments=True))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(F)).build())
    net = MultiLayerNetwork(conf).init()
    lens = [3, 5, 2]
    t = 6
    feats = rng.standard_normal((3, t, F)).astype(np.float32)
    mask = (np.arange(t)[None, :] < np.asarray(lens)[:, None]
            ).astype(np.float32)
    feats *= mask[..., None]
    labels = np.eye(3, dtype=np.float32)[
        rng.integers(0, 3, (3, t))] * mask[..., None]
    ragged = DataSet(feats, labels, mask, mask)
    unpacked_score = net.score(ragged)
    packed_ds = next(iter(PackToBucketIterator(
        ExistingDataSetIterator([ragged]), bucket_len=8)))
    packed_score = net.score(packed_ds)
    # [3, 6] ragged vs [2, 8] packed are two executables of different
    # shape: same math, another summation order — float32 rounding, not
    # bitwise (2 ulp of the score is the stated tolerance).
    assert abs(packed_score - unpacked_score) <= \
        2 * np.finfo(np.float32).eps * abs(unpacked_score), \
        f"packed {packed_score!r} != unpacked {unpacked_score!r}"
    out = np.asarray(net.output(np.asarray(packed_ds.features),
                                features_mask=np.asarray(
                                    packed_ds.features_mask)))
    solo0 = np.asarray(net.output(feats[:1, :3]))
    # the [2, 8] packed forward vs the [1, 3] solo forward: different
    # executables again — softmax outputs agree to 2 float32 ulp of 1.0
    np.testing.assert_allclose(out[:1, :3], solo0, rtol=0,
                               atol=2 * np.finfo(np.float32).eps,
                               err_msg="packed != solo")
    print("smoke_packing: packed score/output agree to float32 rounding")

    # 4) metric families live
    from deeplearning4j_tpu.data.padding import register_packing_metrics
    from deeplearning4j_tpu.optimize.metrics import registry
    register_packing_metrics()
    reg = registry()
    assert reg.counter("packed_requests_total").value(source="fit") > 0
    assert 0.0 < reg.gauge("packing_efficiency").value(source="fit") <= 1.0
    print("smoke_packing: metric families ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
