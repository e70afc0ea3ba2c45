#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip, no subprocess. Drives the main path once through
the entry points a user would call, at the full width of the model every
old record is about (zoo ResNet50, 224x224x3, 1,000 labels, bf16), with
random weights and data made from seeds:

  train     ComputationGraph.fit over a DataSetIterator, 32 steps
  serve     the trained net behind ServingGateway, real HTTP POST /predict
  generate  TransformerDecoder behind ServingGateway, real HTTP POST /generate
  kernels   every Pallas kernel the package can dispatch, compiled by
            Mosaic (interpret=False) at the shapes its workload uses and
            compared with its in-tree reference

    python chip_smoke.py                 # on a TPU; anything else exits non-zero
    python chip_smoke.py --devices 4     # four-chip host: data-parallel train only
    python chip_smoke.py --rehearse      # CPU, tiny sizes, kernels interpreted

The plain invocation never runs on a CPU, never interprets a kernel and
never substitutes a reference for a kernel that failed to compile; a
failed phase raises, and the process exits non-zero. Seconds are printed
for the builder's chip budget, not as metrics: nothing here is a rate, a
utilization or an idle share.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

# What "equal" means below. Two executables of different shape do not agree
# bitwise on the MXU (bf16 passes, different tilings): equality is required
# only within one executable, everything else is a stated tolerance, and
# tokens are compared only through one.
#
# serve: bitwise, within one executable. Across executables no tolerance can
# be stated for this net: a ResNet50 with random weights is chaotic. A
# relative input perturbation of 1e-4 moves the argmax of 3 rows in 4 and
# log p by 11 (CPU), and on the chip the same rows through the 8-row and the
# 1-row executable differ by 21 in log p - one bf16 ulp somewhere in 53
# layers is enough. So a served answer must equal net.output on the same
# rows BITWISE through one of the warmed executables (rows are independent
# at inference, so the executable is all that matters), and the spread
# across executables is printed, not judged.
#
# generate: the slack by which a token (served, or picked by the cached
# path called directly) may trail the recomputed logits' argmax (f32
# params, the MXU's default precision on both sides).
GEN_LOGIT_TOL = 5e-2
# kernels: max|got - ref| / max|ref| against the lax reference computed
# at "highest" matmul precision. Operands are bf16, or f32 run at the MXU's
# default precision (bf16 passes, in Mosaic as in XLA): a few bf16 ulps
# (2^-8) either way. Measured on the chip: 2e-3 to 7e-3.
KERNEL_TOL = 3e-2


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Sizes: the real ones, and the rehearsal's.
# ---------------------------------------------------------------------------
REAL = dict(
    image=(224, 224, 3), labels=1000, batch_per_device=256,
    distinct_batches=4, epochs=8,
    serve_sizes=(1, 2, 3, 5, 8, 1, 4, 2, 3, 1, 1, 1), serve_clients=3,
    serve_batch_limit=8,
    # a small causal LM: 4 layers of 4 heads x 32, 8 decode rows, 128-token
    # prefill chunks, 16-token KV blocks
    lm=dict(vocab=256, layers=4, heads=4, head_dim=32, ff=512,
            max_context=256),
    lm_decode_batch=8, lm_pack=128, lm_kv_block=16,
    prompt_lens=(4, 9, 17, 32, 12, 25), new_tokens=24,
    # long context: 4,096 positions, 4 heads x 128 (one MXU tile a head)
    attn_seq=4096, attn_heads=4, attn_head_dim=128, attn_batch=2,
    attn_layer_batch=8,
    int8=(8, 1024, 1024),
    # zoo AlexNet's two LRN inputs (after conv1 and conv2), bf16
    lrn_shapes=((32, 55, 55, 64), (32, 14, 14, 192)),
)
REHEARSAL = dict(
    REAL,
    image=(32, 32, 3), labels=10, batch_per_device=4,
    distinct_batches=2, epochs=2,
    serve_sizes=(1, 2, 1, 2), serve_clients=2, serve_batch_limit=2,
    lm=dict(vocab=64, layers=2, heads=2, head_dim=8, ff=32,
            max_context=16),
    lm_decode_batch=2, lm_pack=16, lm_kv_block=8,
    prompt_lens=(3, 7), new_tokens=6,
    attn_seq=256, attn_heads=2, attn_head_dim=32, attn_batch=1,
    attn_layer_batch=1,
    int8=(8, 128, 256),
    lrn_shapes=((2, 5, 5, 64),),
)


# ---------------------------------------------------------------------------
# Phase clock: wall seconds split into compile and run.
# ---------------------------------------------------------------------------
class CompileClock:
    """Sums jax's own trace / lower / backend-compile durations (every
    thread), so a phase's wall time splits into compile and the rest."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "/compile/" in event:
            with self._lock:
                self.seconds += duration


def run_phase(name: str, clock: CompileClock, fn, *args):
    from deeplearning4j_tpu.optimize.telemetry import compilation_count
    log(f"[{name}] start")
    t0, c0, n0 = time.perf_counter(), clock.seconds, compilation_count()
    try:
        out = fn(*args)
    except BaseException:
        log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
        raise
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    log(f"[{name}] ok wall={wall:.1f}s compile={comp:.1f}s "
        f"run={max(0.0, wall - comp):.1f}s "
        f"xla_compilations={compilation_count() - n0}")
    return out


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
# The zoo's ResNet50 trains with RmsProp(0.1) from a normal(0, 0.5) init.
# RmsProp's normalizer has no bias correction, so its first step moves every
# weight by 0.1/sqrt(1-0.96) = 0.5, the init's own scale, and later steps by
# 0.1-0.5 more. BatchNorm's running statistics (decay 0.9) trail the weights
# by ~10 steps; with weights moving that fast, inference normalizes every one
# of the 53 BN layers with stale statistics, activations grow layer over layer
# (2e12 at the last block after 5 steps on CPU; past f32 on the chip, where
# one executable served NaN and another did not), and no comparison of served
# outputs means anything. So the smoke keeps the model and its updater and
# lowers the learning rate, and takes enough steps (32 = 8 epochs over 4
# batches) for the running statistics to stop reflecting their init
# (0.9^32 = 3%): the served logits then stay in the tens.
SMOKE_LEARNING_RATE = 1e-3


def _smoke_resnet50(cfg):
    import dataclasses

    import jax.numpy as jnp
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    conf = ResNet50(num_labels=cfg["labels"],
                    input_shape=cfg["image"]).conf()
    for node in conf.nodes.values():
        if node.is_layer() and node.layer.updater is not None:
            node.layer.updater = dataclasses.replace(
                node.layer.updater, learning_rate=SMOKE_LEARNING_RATE)
    return ComputationGraph(conf).init(dtype=jnp.bfloat16)


def _seeded_batches(cfg, batch: int, seed: int = 0):
    """A DataSetIterator over a few seeded random batches (one batch of
    224x224x3 f32 at 1,024 rows is 0.6 GB of host memory)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    sets = []
    for i in range(cfg["distinct_batches"]):
        rng = np.random.default_rng(seed + i)
        x = rng.standard_normal((batch, *cfg["image"]), dtype=np.float32)
        y = np.eye(cfg["labels"], dtype=np.float32)[
            rng.integers(0, cfg["labels"], batch)]
        sets.append(DataSet(x, y))
    return ExistingDataSetIterator(sets)


class _StepRecorder:
    """Fit-loop listener: per step, the loss (fetched, so the step has
    really finished), the process-wide compile count, and how the staged
    input batches sit on the devices."""

    def __init__(self, batch_shape):
        self.losses, self.compiles, self.batch_devices = [], [], []
        self._batch_shape = tuple(batch_shape)

    def iteration_done(self, model, iteration):
        import jax
        from deeplearning4j_tpu.optimize.telemetry import compilation_count
        self.losses.append(float(model.score_value))
        self.compiles.append(compilation_count())
        for a in jax.live_arrays():
            if tuple(a.shape) == self._batch_shape:
                self.batch_devices.append(
                    sorted((s.device.id, s.data.shape[0])
                           for s in a.addressable_shards))


def phase_train(cfg, n_devices: int, rehearse: bool):
    import jax

    batch = cfg["batch_per_device"] * n_devices
    steps = cfg["distinct_batches"] * cfg["epochs"]
    net = _smoke_resnet50(cfg)
    rec = _StepRecorder((batch, *cfg["image"]))
    net.listeners.append(rec)
    it = _seeded_batches(cfg, batch)
    probe_leaf = "output"
    b0 = np.asarray(net.params_tree[probe_leaf]["b"], np.float32).copy()

    if n_devices == 1:
        net.fit(it, epochs=cfg["epochs"])
    else:
        from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                                 data_parallel_mesh)
        ParallelWrapper(net, mesh=data_parallel_mesh(n_devices)).fit(
            it, epochs=cfg["epochs"])
    net.listeners.remove(rec)

    log(f"  batch={batch} steps={steps} losses="
        + " ".join(f"{v:.2f}" for v in rec.losses))
    check(len(rec.losses) == steps,
          f"fit ran {len(rec.losses)} steps, wanted {steps}")
    check(all(np.isfinite(rec.losses)), f"non-finite loss: {rec.losses}")
    check(rec.losses[0] != rec.losses[-1],
          "loss did not move between the first and the last step")
    check(int(net.iteration) == steps,
          f"iteration is {net.iteration}, wanted {steps}")
    late = rec.compiles[-1] - rec.compiles[0]
    check(late == 0, f"{late} XLA compilation(s) after the first step of "
                     "the one batch shape")

    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        if not rehearse:
            check("peak_bytes_in_use" in stats,
                  f"{d} reports no peak_bytes_in_use")
            check(stats.get("bytes_in_use", 0) > 0,
                  f"{d} holds no bytes after training")
        log(f"  {d}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')} "
            f"bytes_in_use={stats.get('bytes_in_use', 'not reported')}")

    if n_devices > 1:
        # The batch as the fit loop staged it: one equal slice per device.
        check(rec.batch_devices, "no staged input batch was seen live")
        per = batch // n_devices
        for placement in rec.batch_devices:
            check(len(placement) == n_devices
                  and all(rows == per for _, rows in placement),
                  f"batch not split over {n_devices} devices: {placement}")
        # The gradients' all-reduce: every device applied the same
        # update, and it was an update.
        leaf = net.params_tree[probe_leaf]["b"]
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n_devices,
              f"params live on {len({s.device for s in shards})} devices")
        replicas = [np.asarray(s.data, np.float32) for s in shards]
        for r in replicas[1:]:
            check(np.array_equal(replicas[0], r),
                  "replicas disagree after training: the gradient "
                  "reduction did not span the mesh")
        check(not np.array_equal(replicas[0], b0),
              "parameters did not change")
        log(f"  batch split {rec.batch_devices[0]}; {probe_leaf}/b "
            f"identical on {n_devices} devices and changed")
    return net


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _post(url: str, payload: dict):
    req = urllib.request.Request(
        url, json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:  # a typed error body; checked later
        return e.code, json.loads(e.read())


def phase_serve(cfg, net):
    from deeplearning4j_tpu.optimize.telemetry import CompilationTracker
    from deeplearning4j_tpu.serving import ServingGateway

    sizes = cfg["serve_sizes"]
    rng = np.random.default_rng(99)
    xs = [rng.standard_normal((n, *cfg["image"]), dtype=np.float32)
          for n in sizes]
    gw = ServingGateway()
    try:
        gw.add_model("resnet50", net,
                     batch_limit=cfg["serve_batch_limit"])
        gw.warmup()
        # Reference: net.output on the same rows through every warmed
        # executable that can hold them (tail row repeated up to the
        # bucket, as the engine pads), computed before the compile-silent
        # window. Zero compilations: warmup made all of it.
        buckets = sorted(gw.pool.get("resnet50").engine.warmed_buckets)
        want = [{b: np.asarray(net.output(np.concatenate(
                    [x, np.repeat(x[-1:], b - len(x), axis=0)])),
                    np.float32)[:len(x)]
                 for b in buckets if b >= len(x)} for x in xs]

        results, errors = {}, []

        def client(ci):
            try:
                for j in range(ci, len(xs), cfg["serve_clients"]):
                    results[j] = _post(gw.url + "/predict",
                                       {"model": "resnet50",
                                        "features": xs[j].tolist()})
            except Exception as e:  # reported below; the phase fails
                errors.append(e)

        with gw, CompilationTracker() as trk:
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(cfg["serve_clients"])]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=900)
            check(not any(t.is_alive() for t in ts), "a client hung")
            late = trk.count
        check(not errors, f"client errors: {errors[:3]}")
        check(len(results) == len(xs),
              f"{len(results)} of {len(xs)} requests answered")
        served_by, spread = {}, 0.0
        for j, (code, body) in sorted(results.items()):
            check(code == 200 and body.get("status") == "ok",
                  f"request {j}: {code} {str(body)[:200]}")
            got = np.asarray(body["predictions"], np.float32)
            check(got.shape == (sizes[j], cfg["labels"]),
                  f"request {j}: shape {got.shape}")
            check(np.isfinite(got).all(), f"request {j}: non-finite output")
            check(np.allclose(got.sum(1), 1.0, atol=2e-2),
                  f"request {j}: rows do not sum to 1")
            match = [b for b, ref in want[j].items()
                     if np.array_equal(got, ref)]
            check(match, f"request {j} ({sizes[j]} rows) equals net.output "
                  "through none of the warmed executables; nearest is "
                  f"{min(float(np.abs(got - r).max()) for r in want[j].values()):.3g}"
                  " away in p")
            served_by[match[0]] = served_by.get(match[0], 0) + 1
            refs = list(want[j].values())
            spread = max([spread] + [float(np.abs(r - refs[0]).max())
                                     for r in refs[1:]])
        log(f"  {len(xs)} requests of {min(sizes)}-{max(sizes)} images, all "
            f"200, each bitwise equal to net.output through a warmed "
            f"executable (by bucket: {dict(sorted(served_by.items()))}); "
            f"the same rows across executables differ by up to {spread:.3g} "
            f"in p (random weights amplify rounding; printed, not judged); "
            f"{late} compilations after warmup")
        check(late == 0, f"{late} XLA compilation(s) after warmup")
    finally:
        gw.pool.shutdown()


# ---------------------------------------------------------------------------
# generate
# ---------------------------------------------------------------------------
def _logits(model, tokens, pad_to: int):
    """naive_generate's forward: the whole sequence through the plain
    forward at its [1, pad_to] signature, no cache. Returns logits [t,
    vocab] — row t holds the logits that choose token t+1."""
    t = len(tokens)
    row = np.zeros((1, pad_to), np.int32)
    seg = np.zeros((1, pad_to), np.int32)
    pos = np.zeros((1, pad_to), np.int32)
    row[0, :t], seg[0, :t], pos[0, :t] = tokens, 1, np.arange(t)
    return np.asarray(model.logits(row, seg, pos), np.float32)[0, :t]


def phase_generate(cfg):
    from deeplearning4j_tpu.optimize.telemetry import CompilationTracker
    from deeplearning4j_tpu.serving import ServingGateway
    from deeplearning4j_tpu.serving.decode import (TransformerDecoder,
                                                   naive_generate)

    lm = cfg["lm"]
    model = TransformerDecoder(seed=7, **lm)
    pack, n_new = cfg["lm_pack"], cfg["new_tokens"]
    gw = ServingGateway()
    try:
        entry = gw.add_decode_model(
            "lm", model, max_decode_batch=cfg["lm_decode_batch"],
            pack_bucket=pack, kv_block_tokens=cfg["lm_kv_block"],
            kv_max_blocks=max(64, (lm["max_context"] // cfg["lm_kv_block"])
                              * cfg["lm_decode_batch"] * 2))
        gw.warmup()
        adapter = entry.engine.adapter
        cache = adapter.cache
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, lm["vocab"], n).tolist()
                   for n in cfg["prompt_lens"]]
        results, errors = {}, []

        def client(i):
            try:
                results[i] = _post(gw.url + "/generate",
                                   {"model": "lm", "prompt": prompts[i],
                                    "max_new_tokens": n_new})
            except Exception as e:  # reported below; the phase fails
                errors.append(e)

        with gw, CompilationTracker() as trk:
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(len(prompts))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=900)
            check(not any(t.is_alive() for t in ts), "a client hung")
            late = trk.count
        check(not errors, f"client errors: {errors[:3]}")
        check(cache.blocks_in_use() == 0,
              f"{cache.blocks_in_use()} KV blocks still in use after the "
              "traffic drained")
        check(late == 0, f"{late} XLA compilation(s) after warmup")

        # Served tokens against the recomputed logits, teacher-forced: a
        # token may trail the reference argmax by the tolerance, no more
        # (equal tokens only where the top-2 margin exceeds it).
        worst_trail, exact = 0.0, 0
        for i, (code, body) in sorted(results.items()):
            check(code == 200 and body.get("status") == "ok",
                  f"prompt {i}: {code} {str(body)[:200]}")
            toks = body["tokens"]
            check(len(toks) == n_new,
                  f"prompt {i}: {len(toks)} tokens, wanted {n_new}")
            ref = _logits(model, prompts[i] + toks, pack)
            for j, tok in enumerate(toks):
                row = ref[len(prompts[i]) - 1 + j]
                worst_trail = max(worst_trail, float(row.max() - row[tok]))
            exact += toks == naive_generate(model, prompts[i], n_new,
                                            pad_to=pack)
        check(worst_trail <= GEN_LOGIT_TOL,
              f"a served token trails the recomputed argmax by "
              f"{worst_trail:.4f} logits, tolerance {GEN_LOGIT_TOL}")

        # The cached path's picks against naive recompute's logits, step
        # by step: the adapter's own calls (prefill into the device arena
        # -> block table -> step executable, launched and then fetched).
        # A pick stays on the device and is the next step's token, so
        # the reference follows the picks.
        worst_pick = 0.0
        for i, prompt in enumerate(prompts[:3]):
            rid = 1_000_000 + i
            toks = list(prompt)
            # the arenas and the step in flight are the lock's holder's
            with entry.engine.paused():
                adapter.prefill_group([(rid, np.asarray(prompt, np.int32))])
                got, fails = adapter.collect()
                try:
                    for _ in range(n_new):
                        check(not fails, f"the cached path failed: {fails}")
                        ref = _logits(model, toks, pack)[-1]
                        worst_pick = max(worst_pick,
                                         float(ref.max() - ref[got[rid]]))
                        toks.append(got[rid])
                        adapter.step([rid])
                        got, fails = adapter.collect()
                finally:
                    adapter.free(rid)
        check(cache.blocks_in_use() == 0, "the direct calls left KV blocks")
        log(f"  {len(prompts)} prompts of {min(cfg['prompt_lens'])}-"
            f"{max(cfg['prompt_lens'])} tokens x {n_new} new, all 200, "
            f"{exact}/{len(prompts)} token-identical to naive_generate; a "
            f"served token trails the recomputed argmax by at most "
            f"{worst_trail:.4f}; a cached-path pick trails it by at most "
            f"{worst_pick:.4f} (tolerance {GEN_LOGIT_TOL}); KV blocks "
            f"drained; {late} compilations after warmup")
        check(worst_pick <= GEN_LOGIT_TOL,
              f"a cached-path pick trails recompute's argmax by "
              f"{worst_pick:.4f} logits, tolerance {GEN_LOGIT_TOL}")
    finally:
        gw.pool.shutdown()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"shape {got.shape} vs {ref.shape}")
    check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12))


def phase_kernels(cfg, interpret: bool):
    """Every kernel is tried; each prints compiled yes/no and its error;
    the phase fails at the end if any did not compile or missed its
    tolerance."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.ops.attention import dense_attention
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    failed = []

    def kernel(name: str, tol: float, fn):
        t0 = time.perf_counter()
        try:
            errs = fn()
        except Exception as e:
            failed.append(name)
            log(f"  {name}: compiled=no  {type(e).__name__}: "
                f"{str(e).strip()[:2000]}")
            return
        worst = max(errs.values())
        ok = worst <= tol
        if not ok:
            failed.append(name)
        detail = " ".join(f"{k}={v:.2e}" for k, v in errs.items())
        log(f"  {name}: compiled=yes max_err={worst:.2e} tol={tol:.0e} "
            f"{'ok' if ok else 'OUT OF TOLERANCE'} [{detail}] "
            f"{time.perf_counter() - t0:.1f}s")

    # ---- flash attention: forward + both backward kernels ---------------
    b, t = cfg["attn_batch"], cfg["attn_seq"]
    h, d = cfg["attn_heads"], cfg["attn_head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                  for kk in keys)
    lens = np.linspace(t // 3, t, b).astype(np.int32)
    key_mask = jnp.asarray(
        (np.arange(t)[None, :] < lens[:, None]).astype(np.float32))
    # packed rows: three documents then a padded tail (segment 0)
    cuts = np.array([0, t * 3 // 8, t * 5 // 8, t * 7 // 8, t])
    seg_row = np.zeros(t, np.int32)
    for s in range(3):
        seg_row[cuts[s]:cuts[s + 1]] = s + 1
    seg = jnp.asarray(np.broadcast_to(seg_row, (b, t)).copy())

    def flash_case(**kw):
        def run():
            def loss(impl):
                def f(q, k, v):
                    return jnp.sum(impl(q, k, v).astype(jnp.float32)
                                   * g.astype(jnp.float32))
                return f
            fl = lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=interpret, **kw)
            de = lambda q, k, v: dense_attention(q, k, v, causal=True, **kw)
            out = jax.jit(fl)(q, k, v)
            dq, dk, dv = jax.jit(jax.grad(loss(fl), argnums=(0, 1, 2)))(
                q, k, v)
            with jax.default_matmul_precision("highest"):
                r_out = jax.jit(de)(q, k, v)
                r_dq, r_dk, r_dv = jax.jit(
                    jax.grad(loss(de), argnums=(0, 1, 2)))(q, k, v)
            return {"out": _rel_err(out, r_out), "dq": _rel_err(dq, r_dq),
                    "dk": _rel_err(dk, r_dk), "dv": _rel_err(dv, r_dv)}
        return run

    shape = f"b{b} t{t} h{h}x{d} bf16 causal"
    kernel(f"flash fwd+dq+dkv [{shape}]", KERNEL_TOL, flash_case())
    kernel(f"flash fwd+dq+dkv [{shape} key_mask]", KERNEL_TOL,
           flash_case(key_mask=key_mask))
    kernel(f"flash fwd+dq+dkv [{shape} segment_ids+key_mask]",
           KERNEL_TOL,
           flash_case(key_mask=(seg > 0).astype(jnp.float32),
                      segment_ids=seg))

    # ---- the serving kernels: the paged decode kernel (the cache read
    # through the block table; grouped KV heads; a window) and the prefill
    # chunk over its cached context, each against its dense arm. On the
    # chip at the widths of the served decoders that keep K and V per head
    # (the third has its query heads by kind of layer: 48 on a full layer
    # and 72 on a sliding one over 8 KV heads, groups of 6 and 9, a
    # block-diagonal query of [72, 1024]); rehearsed tiny, at a group of 9
    # too.
    from deeplearning4j_tpu.ops.flash_attention import (
        merge_attention, paged_decode_attention, prefill_attention)
    serving = [("f32 h2x8 bt8", jnp.float32, 2, 2, 8, 8, 4, None, 16),
               ("f32 h18/2x8 bt8", jnp.float32, 18, 2, 8, 8, 4, None, 16)] \
        if interpret else [
        ("f32 h32x64 bt16 (decoder-at-opt-1.3b)", jnp.float32, 32, 32, 64,
         16, 16, None, 128),
        ("bf16 h32/4x128 bt256 full", jnp.bfloat16, 32, 4, 128, 256, 8,
         None, 2048),
        ("bf16 h32/4x128 bt256 window1024", jnp.bfloat16, 32, 4, 128, 256,
         5, 1024, 2048),
        ("bf16 h48/8x128 bt256 full (laguna-s-2.1)", jnp.bfloat16, 48, 8, 128,
         256, 8, None, 2048),
        ("bf16 h72/8x128 bt256 window512 (laguna-s-2.1)", jnp.bfloat16, 72,
         8, 128, 256, 3, 512, 2048)]
    def chunk_line(chunk, n_ctx, ctx_len):
        """A chunk of two segments and a padded tail after `n_ctx` cached
        positions of which `ctx_len` are real: (the live queries, the
        positions and segments `prefill_attention` takes)."""
        line = np.arange(chunk)
        seg = np.where(line < chunk // 2, 1, 2)
        seg[-chunk // 8:] = 0
        real = np.arange(n_ctx) < ctx_len
        return seg > 0, dict(
            q_pos=jnp.asarray(line), q_seg=jnp.asarray(seg),
            kv_pos=jnp.asarray(np.concatenate(
                [np.where(real, np.arange(n_ctx) - ctx_len, 1 << 30), line])),
            kv_seg=jnp.asarray(np.concatenate([np.where(real, 1, -1), seg])))

    def in_parts(q, k_, v_, n_ctx, axis, kw):
        """The same attention as the served chunk takes it: the kernel
        over the cached keys and over the chunk's own, each with its
        rows' log-sum-exp, joined in float32."""
        cut = lambda a, lo, hi: jax.lax.slice_in_dim(a, lo, hi, axis=axis)
        part = lambda lo, hi: prefill_attention(
            q, cut(k_, lo, hi), cut(v_, lo, hi), impl="flash",
            interpret=interpret, return_lse=True, **dict(
                kw, kv_pos=kw["kv_pos"][lo:hi], kv_seg=kw["kv_seg"][lo:hi]))
        end = k_.shape[axis]
        return merge_attention(*part(n_ctx, end), *part(0, n_ctx))[0]

    for what, dt, hh, kvh, d, bt, w, window, chunk in serving:
        if window and interpret:
            continue

        def paged(dt=dt, hh=hh, kvh=kvh, d=d, bt=bt, w=w, window=window):
            ks = jax.random.split(jax.random.PRNGKey(w), 5)
            nrows, blocks = 8, 8 * w
            q = jax.random.normal(ks[0], (nrows, hh, d), dt)
            kn, vn = (jax.random.normal(k, (nrows, kvh * d), dt)
                      for k in ks[1:3])
            ak, av = (jax.random.normal(k, (2, blocks + 1, bt, kvh * d), dt)
                      for k in ks[3:])
            tables = jnp.asarray(np.random.default_rng(0).permutation(
                blocks).reshape(nrows, w), jnp.int32)
            lens = jnp.asarray(np.linspace(
                1, 3 * window if window else w * bt - 1, nrows), jnp.int32)
            starts = jnp.zeros((nrows,), jnp.int32)
            if window:      # tables that start where the window's block does
                starts = jnp.maximum(lens - window + 1, 0) // bt * bt
            args = (q, kn, vn, ak, av, 1, tables, starts, lens)
            got = jax.jit(lambda *a: paged_decode_attention(
                *a[:5], 1, *a[5:], window=window, impl="paged",
                interpret=interpret))(*args[:5], *args[6:])
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda *a: paged_decode_attention(
                    *a[:5], 1, *a[5:], window=window, impl="dense"))(
                        *args[:5], *args[6:])
            return {"out": _rel_err(got, ref)}
        kernel(f"paged_decode_attention [{what}]", KERNEL_TOL, paged)

        def chunked(dt=dt, hh=hh, kvh=kvh, d=d, bt=bt, w=w, window=window,
                    chunk=chunk):
            ks = jax.random.split(jax.random.PRNGKey(chunk), 3)
            n_ctx, ctx_len = w * bt, (w * bt * 3) // 4
            q = jax.random.normal(ks[0], (chunk, hh, d), dt)
            k_, v_ = (jax.random.normal(k, (n_ctx + chunk, kvh, d), dt)
                      for k in ks[1:])
            live, where = chunk_line(chunk, n_ctx, ctx_len)
            kw = dict(where, window=window)
            got = jax.jit(lambda *a: prefill_attention(
                *a, impl="flash", interpret=interpret, **kw))(q, k_, v_)
            parts = jax.jit(lambda *a: in_parts(*a, n_ctx, 0, kw))(q, k_, v_)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda *a: prefill_attention(
                    *a, impl="dense", **kw))(q, k_, v_)
            ref = np.asarray(ref, np.float32)[live]
            return {"out": _rel_err(np.asarray(got, np.float32)[live], ref),
                    "parts": _rel_err(np.asarray(parts)[live], ref)}
        kernel(f"prefill_attention [{what} chunk{chunk}]", KERNEL_TOL,
               chunked)

    # ---- the latent-attention kernels: the absorbed decode kernel over
    # the paged latents (one entry is key and value) and the prefill kernel
    # with a value head of its own size, each against its dense arm. On the
    # chip at the served decoder's widths (32 heads, an entry of 576 on 640
    # lanes, keys of 192 and values of 128); rehearsed tiny.
    from deeplearning4j_tpu.ops.flash_attention import latent_decode_attention
    hh, width, dv, dq, dvh, bt, w, chunk = (2, 24, 16, 12, 8, 8, 4, 16) \
        if interpret else (32, 640, 512, 192, 128, 256, 8, 2048)
    dt = jnp.float32 if interpret else jnp.bfloat16

    def latent_decode():
        ks = jax.random.split(jax.random.PRNGKey(33), 3)
        nrows, blocks = 8, 8 * w
        q = jax.random.normal(ks[0], (nrows, hh, width), dt) * 0.2
        new = jax.random.normal(ks[1], (nrows, width), dt)
        arena = jax.random.normal(ks[2], (2, blocks + 1, bt, width), dt)
        tables = jnp.asarray(np.random.default_rng(0).permutation(
            blocks).reshape(nrows, w), jnp.int32)
        lens = jnp.asarray(np.linspace(0, w * bt - 1, nrows), jnp.int32)
        kw = dict(v_width=dv, scale=dq ** -0.5)
        got = jax.jit(lambda *a: latent_decode_attention(
            *a[:3], 1, *a[3:], impl="paged", interpret=interpret, **kw))(
                q, new, arena, tables, lens)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a: latent_decode_attention(
                *a[:3], 1, *a[3:], impl="dense", **kw))(
                    q, new, arena, tables, lens)
        return {"out": _rel_err(got, ref)}
    kernel(f"latent_decode_attention [h{hh} entry{width} v{dv} bt{bt}]",
           KERNEL_TOL, latent_decode)

    def latent_prefill():
        ks = jax.random.split(jax.random.PRNGKey(34), 3)
        n_ctx, ctx_len = w * bt, (w * bt * 3) // 4
        q = jax.random.normal(ks[0], (hh, chunk, dq), dt)
        k_ = jax.random.normal(ks[1], (hh, n_ctx + chunk, dq), dt)
        v_ = jax.random.normal(ks[2], (hh, n_ctx + chunk, dvh), dt)
        live, where = chunk_line(chunk, n_ctx, ctx_len)
        kw = dict(where, scale=dq ** -0.5, heads_first=True)
        got = jax.jit(lambda *a: prefill_attention(
            *a, impl="flash", interpret=interpret,
            name="prefill_attention_latent", **kw))(q, k_, v_)
        parts = jax.jit(lambda *a: in_parts(
            *a, n_ctx, 1, dict(kw, name="prefill_attention_latent")))(
                q, k_, v_)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a: prefill_attention(
                *a, impl="dense", **kw))(q, k_, v_)
        ref = np.asarray(ref, np.float32)[:, live]
        return {"out": _rel_err(np.asarray(got, np.float32)[:, live], ref),
                "parts": _rel_err(np.asarray(parts)[:, live], ref)}
    kernel(f"prefill_attention [latent h{hh} qk{dq} v{dvh} chunk{chunk}]",
           KERNEL_TOL, latent_prefill)

    # ---- int8 matmul -----------------------------------------------------
    def int8_case():
        bb, kk, nn = cfg["int8"]
        ks = jax.random.split(jax.random.PRNGKey(3), 2)
        x = jax.random.randint(ks[0], (bb, kk), -127, 128, jnp.int8)
        w = jax.random.randint(ks[1], (nn, kk), -127, 128, jnp.int8)
        got = jax.jit(lambda x, w: pk.int8_matmul_pallas(
            x, w, interpret=interpret))(x, w)
        ref = pk.int8_matmul_xla(x, w)
        check(got.dtype == jnp.int32, f"dtype {got.dtype}")
        return {"out": float(np.abs(np.asarray(got, np.int64)
                                    - np.asarray(ref, np.int64)).max())}
    kernel("int8_matmul_pallas [%dx%dx%d] (exact)" % cfg["int8"], 0.0,
           int8_case)

    # ---- LRN forward + backward -------------------------------------------
    for shp in cfg["lrn_shapes"]:
        def run(shp=shp):
            ks = jax.random.split(jax.random.PRNGKey(5), 2)
            x = jax.random.normal(ks[0], shp, jnp.bfloat16)
            gg = jax.random.normal(ks[1], shp, jnp.bfloat16)
            args = (2.0, 1e-4, 0.75, 5)
            ker = lambda a: pk.lrn(a, *args, interpret)
            ref = lambda a: pk.lrn_reference(a.astype(jnp.float32), *args)
            y, vjp = jax.vjp(ker, x)
            r_y, r_vjp = jax.vjp(ref, x)
            return {"fwd": _rel_err(y, r_y),
                    "bwd": _rel_err(vjp(gg)[0],
                                    r_vjp(gg.astype(jnp.float32))[0])}
        kernel(f"lrn fwd+bwd [{'x'.join(map(str, shp))} bf16]",
               KERNEL_TOL, run)

    check(not failed, f"{len(failed)} kernel(s) failed: {failed}")


def phase_attention_layer(cfg, rehearse: bool):
    """One SelfAttentionLayer training step through the framework's fit at
    the long-context geometry (`attn_seq`, `attn_heads`): on a TPU the dispatch rule must
    pick the fused kernel by itself."""
    import jax.numpy as jnp
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration, RnnOutputLayer,
                                    Sgd)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.optimize.metrics import registry

    t, vocab = cfg["attn_seq"], 96
    width = cfg["attn_heads"] * cfg["attn_head_dim"]
    b = cfg["attn_layer_batch"]

    def attn():
        return SelfAttentionLayer(n_out=width, n_heads=cfg["attn_heads"],
                                  causal=True, activation="relu")
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.1))
            .list().layer(attn()).layer(attn())
            .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab)).build())
    net = MultiLayerNetwork(conf).init(dtype=jnp.bfloat16)
    idx = np.random.default_rng(0).integers(0, vocab, (b, t))
    eye = np.eye(vocab, dtype=np.float32)
    want = "dense" if rehearse else "pallas"
    counter = registry().counter(
        "attention_kernel_selected_total",
        "Attention implementations chosen at dispatch (trace) time")
    before = counter.value(impl=want)
    net.fit(DataSet(eye[idx], eye[np.roll(idx, -1, 1)]), epochs=1,
            batch_size=b)
    loss = float(net.score_value)
    picked = counter.value(impl=want) - before
    log(f"  SelfAttentionLayer x2 step [b{b} t{t} width{width} bf16]: "
        f"loss={loss:.4f}, attention_kernel_selected_total"
        f"{{impl=\"{want}\"}} +{int(picked)}")
    check(np.isfinite(loss), f"non-finite loss {loss}")
    check(picked >= 1, f"the dispatch never selected impl={want!r}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: same phases, tiny sizes, kernels "
                         "in interpret mode; proves nothing about a chip")
    ap.add_argument("--devices", type=int, default=1,
                    help="N > 1: only the train phase, data-parallel over N "
                         "devices (global batch N x 256)")
    args = ap.parse_args(argv)
    cfg = REHEARSAL if args.rehearse else REAL
    if args.rehearse and args.devices > 1 and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax
    import jaxlib
    from importlib import metadata
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} "
        f"python {sys.version.split()[0]} numpy {np.__version__}")
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"device_count={device['count']} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
              "nothing was run. --rehearse runs the tiny CPU rehearsal.",
              file=sys.stderr)
        return 2
    if device["count"] < args.devices:
        print(f"chip_smoke: --devices {args.devices} but only "
              f"{device['count']} {device['platform']} device(s) are visible",
              file=sys.stderr)
        return 2
    if args.rehearse:
        log('"rehearsal": true  (tiny sizes, interpreted kernels)')

    from deeplearning4j_tpu import native_etl, native_quant
    from deeplearning4j_tpu.optimize import compile_cache
    clock = CompileClock()
    cache_dir = compile_cache.enable()  # before the first compile
    log(f"compile cache dir={cache_dir} entries at start="
        f"{compile_cache.status()['entries']}")
    log(f"native_etl.available()={native_etl.available()} "
        f"native_quant.available()={native_quant.available()} "
        "(neither is on the TPU path)")

    t0 = time.perf_counter()
    net = run_phase("train", clock, phase_train, cfg, args.devices,
                    args.rehearse)
    if args.devices == 1:
        run_phase("serve", clock, phase_serve, cfg, net)
        run_phase("generate", clock, phase_generate, cfg)
        run_phase("kernels", clock, phase_kernels, cfg, args.rehearse)
        run_phase("attention-layer", clock, phase_attention_layer, cfg,
                  args.rehearse)
    st = compile_cache.status()
    log(f"compile cache dir={st['dir']} hits={st['hits']} "
        f"misses={st['misses']} entries={st['entries']}")
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s "
        f"(compile {clock.seconds:.1f}s)")
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
