"""Attention-kernel smoke: interpret-mode gate for the fused Pallas
flash kernel and its dispatch (docs/perf_attention.md, ISSUE 7).

Runs the REAL kernels (fwd AND bwd) in interpret mode on CPU against
the dense_attention reference, then exercises the dispatch: the auto
rule, a requested pallas off-TPU raising instead of running another
impl, and the selection counter family on the metrics registry.

Run by runtests.sh as a separate step (no test_ prefix on purpose —
this is the end-to-end kernel gate, kept out of the pytest budget).
Exits nonzero on any failed expectation.

Usage: JAX_PLATFORMS=cpu python tests/smoke_attention.py
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
    from deeplearning4j_tpu.optimize.metrics import registry

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 64, 4, 16
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)),
                             jnp.float32)
    q, k, v = mk(), mk(), mk()
    g = mk()
    km = jnp.asarray(rng.random((B, T)) > 0.3, jnp.float32)

    # 1) fwd parity, causal + mask
    got = fa.flash_attention(q, k, v, causal=True, key_mask=km,
                             q_block=16, kv_block=16, interpret=True)
    want = att.dense_attention(q, k, v, causal=True, key_mask=km)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("smoke_attention: fwd parity ok")

    # 2) bwd parity through the custom_vjp Pallas backward kernels
    gf = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, q_block=16, kv_block=16,
        interpret=True) * g), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(att.dense_attention(
        q, k, v, causal=True) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)
    print("smoke_attention: bwd parity ok")

    # 3) dispatch: auto rule; a requested pallas that cannot run raises
    assert att.select_attention_impl(64, 16) == "dense"
    assert att.select_attention_impl(4096, 128,
                                     interpret=True) == "pallas"
    assert att.select_attention_impl(4096, 128) == "blockwise"
    try:
        att.single_device_attention(q, k, v, causal=True, impl="pallas")
    except ValueError as e:
        assert "'pallas' requested" in str(e), e
    else:
        raise AssertionError("impl='pallas' off-TPU ran something else")
    print("smoke_attention: dispatch ok (no silent fallback)")

    # 4) the selection counter family is on the scrape surface
    text = registry().prometheus_text()
    if "attention_kernel_selected_total" not in text:
        print("smoke_attention: counter family missing from registry")
        return 1
    print("smoke_attention: selection counter on scrape surface")
    return 0


if __name__ == "__main__":
    sys.exit(main())
