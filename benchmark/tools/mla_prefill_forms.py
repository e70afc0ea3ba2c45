#!/usr/bin/env python3
"""Builder's tool, on the chip; not run by the driver. Times one layer's
latent attention of a prefill chunk in its two forms at a configuration's
widths, over cached contexts of several lengths:

    python3 benchmark/tools/mla_prefill_forms.py <config> <chunk> <ctx> [<ctx> ...]

expanded (what `TransformerDecoder._chunk_forward` runs: the cached
entries and the chunk's own up-projected to keys of nope + rope and
values per head, then `prefill_attention_latent`) against absorbed (the
keys' up-projection multiplied into the queries, every head over the
entries themselves as one shared key of the entry's width and value of
the latent's, the values' up-projection after). Prints a JSON line a
context: device milliseconds of each (median of 5 after a warm call,
host clock around `block_until_ready`) and how far the two results lie
apart."""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(config: str, chunk: int, contexts) -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import manifest
    from benchmark.models import kanana2
    from deeplearning4j_tpu.ops.flash_attention import prefill_attention
    cfg = manifest.data_file("configs", config)
    cfg = dict(cfg, num_hidden_layers=2, vocab_size=1024)   # a sparse layer
    model = kanana2.build(cfg, 1)
    lp = model.params_tree["layers"][1]
    dev = jax.devices()[0]
    print(f"info platform={dev.platform} kind={dev.device_kind!r}",
          flush=True)
    key = jax.random.PRNGKey(0)
    rank, nope = model.rank, model.nope

    def absorbed(q, entries, lp, **where):
        w_uk, w_uv = model._up_projections(lp)
        q_lat = jnp.einsum("thd,chd->thc", q[..., :nope], w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(q.dtype)
        pad = jnp.zeros(q.shape[:2] + (model.latent_width - rank
                                       - model.rope_dim,), q.dtype)
        o_lat = prefill_attention(
            jnp.concatenate([q_lat, q[..., nope:], pad], -1),
            entries[:, None, :], entries[:, None, :rank],
            scale=model.attn_scale, name="prefill_attention_absorbed",
            **where)
        return jnp.einsum("thc,chd->thd", o_lat, w_uv,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    for n_ctx in contexts:
        ks = jax.random.split(jax.random.fold_in(key, n_ctx), 2)
        q = jax.random.normal(ks[0], (chunk, model.heads, model.head_dim),
                              jnp.bfloat16)
        entries = jax.random.normal(ks[1], (n_ctx + chunk,
                                            model.latent_width), jnp.bfloat16)
        entries = entries.at[:, rank + model.rope_dim:].set(0)
        line = np.arange(chunk, dtype=np.int32)
        where = dict(
            q_pos=jnp.asarray(line), q_seg=jnp.ones((chunk,), jnp.int32),
            kv_pos=jnp.asarray(np.concatenate(
                [np.arange(n_ctx, dtype=np.int32) - n_ctx, line])),
            kv_seg=jnp.ones((n_ctx + chunk,), jnp.int32))
        out, row = {}, {"context": n_ctx, "chunk": chunk}
        for name, fn in (("expanded", model._attend_expanded),
                         ("absorbed", absorbed)):
            run = jax.jit(lambda q, e, lp, fn=fn: fn(q, e, lp, **where))
            out[name] = run(q, entries, lp).block_until_ready()
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                run(q, entries, lp).block_until_ready()
                times.append((time.perf_counter() - t0) * 1e3)
            row[name + "_ms"] = statistics.median(times)
        a, b = (np.asarray(out[k], np.float32) for k in out)
        row["max_abs_diff"] = float(np.abs(a - b).max())
        row["max_abs"] = float(np.abs(a).max())
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]),
                  [int(a) for a in sys.argv[3:]]))
