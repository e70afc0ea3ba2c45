"""The decoder set to a latent-attention block (RMS norm, a latent wider
than a head with a rope part beside it, a leading dense SwiGLU layer,
then 8 SwiGLU experts with 2 a token under the sigmoid router and a
shared expert) against the plain reference of
`benchmark/reference/kanana2.py`, at a small size on the CPU with seeded
random weights: chunked prefill over cached latents and decoding in the
absorbed form through the latent cache, the two forms of the attention,
the rotary by permuted columns, the router's rule, the share of the
experts a chip holds, the cache's entry, and the two kernels."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.models import kanana2 as builder
from benchmark.models import seed_key
from benchmark.reference import kanana2 as ref
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.serving import decode
from deeplearning4j_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                               TransformerAdapter)
from chunk_context import check_chunk_over_context

PUBLISHED = manifest.data_file("configs", "kanana-2-30b-a3b-instruct-2601")
CHUNK, BT, PAD = 16, 4, 1024
TINY = dict(PUBLISHED, hidden_size=32, num_attention_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            vocab_size=96, num_hidden_layers=3, max_context=80,
            init_std=0.25, dtype="float32")
# Two float32 programs of different shape and form (chunks, a cache and
# the absorbed products here; one whole sequence expanded per head there)
# agree to rounding: logits some units wide, sums of a few hundred terms.
# The bfloat16 control must not pass it.
TOL = 2e-4


def _cache(model, blocks=96):
    return PagedKVCache(
        layers=model.n_layers, heads=model.kv_heads, head_dim=model.head_dim,
        dtype=model.dtype, layer_kinds=model.layer_kinds(),
        entry=model.cache_entry(), block_tokens=BT,
        max_blocks={"latent": blocks})


def _served(cfg, seed, prompts, new=6):
    """Serve `prompts` through chunked prefill and the cache, all rows
    in one batch. -> ({rid: tokens}, cache, adapter)."""
    model = builder.build(cfg, seed)
    cache = _cache(model)
    ad = TransformerAdapter(model, cache, pack_bucket=CHUNK,
                            max_rows=len(prompts))
    out = {r: [] for r in prompts}
    launches = [(ad.prefill_group, g)
                for g in ad.pack_groups(list(prompts.items()))] \
        + [(ad.step, list(prompts))] * (new - 1) + [(ad.collect,)]
    for n, (launch, *args) in enumerate(launches):
        got, fails = launch(*args)
        assert not fails and (n or not got)
        for r in got:
            out[r].append(got[r])
    return out, cache, ad


# shorter than a chunk and off a block's edge; ending on a chunk's edge
# (and a block's) and just past it; longer than two chunks, on and off
@pytest.mark.parametrize("lengths", [(5, 12), (16, 17), (32, 41, 7)])
def test_chunked_prefill_and_cached_decoding_agree_with_the_reference(
        lengths):
    rng = np.random.default_rng(sum(lengths))
    prompts = {i: rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
               for i, n in enumerate(lengths)}
    # 24 tokens a row: over fewer the bfloat16 control picks no other token
    served, cache, _ = _served(TINY, 11, prompts, new=24)
    weights = builder.make_weights(11, TINY)
    seqs = [(prompts[r].tolist(), served[r]) for r in prompts]
    gaps = ref.served_gaps(weights, 0, seqs, 0, lowp="bfloat16")
    for (gap, _), (prompt, _) in zip(gaps, seqs):
        # every served token is the reference's best, or level with it
        assert gap.max() <= TOL, (len(prompt), gap)
    # the nearest precision below fails the same tolerance somewhere
    assert max(c.max() for _, c in gaps) > TOL
    for r, p in prompts.items():
        assert cache.length(r) == len(p) + len(served[r]) - 1


# a slab of 32 cached positions (8 table entries): no context, half a
# slab, exactly one, and two and a half
@pytest.mark.parametrize("ctx", [0, 16, 32, 80])
def test_a_chunk_reads_its_cached_latents_a_slab_at_a_time(ctx, monkeypatch):
    monkeypatch.setattr(decode, "CONTEXT_SLAB", 32)
    model = builder.build(dict(TINY, max_context=128), 7)
    ad = check_chunk_over_context(model, _cache(model), CHUNK, ctx,
                                  TINY["vocab_size"], TOL)
    # 33 entries cover 128 positions and one more; the table is 5 slabs
    assert ad._ctx_widths == {"latent": 40}


def test_the_plain_forward_gives_the_references_logits():
    """The program's forward (rotary by halves on permuted columns, the
    sigmoid router, the shared expert, the dense layer) against the
    reference's (interleaved pairs), logit by logit."""
    rng = np.random.default_rng(3)
    model = builder.build(TINY, 5)
    t = 37
    toks = rng.integers(0, TINY["vocab_size"], t)
    row, seg, pos = (np.zeros((1, 48), np.int32) for _ in range(3))
    row[0, :t], seg[0, :t], pos[0, :t] = toks, 1, np.arange(t)
    got = np.asarray(model.logits(row, seg, pos))[0, :t]
    padded = jnp.zeros((PAD,), jnp.int32).at[:t].set(jnp.asarray(toks))
    want = np.asarray(ref.forward_all(seed_key(5), TINY, [padded],
                                      [slice(0, t)])[0])
    assert want.std() > 0.5                  # logits some units wide
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_rotary_by_halves_on_permuted_columns_is_the_interleaved_rotary():
    """q_pe . k_pe is the same sum when both are permuted alike, so
    turning halves of the evens-first columns gives the scores that
    turning the published pairs gives."""
    rng = np.random.default_rng(4)
    model = builder.build(TINY, 4)
    t, hh, nope, rope = 9, 4, 8, 4
    h = jnp.asarray(rng.normal(size=(t, 32)), jnp.float32)
    pos = jnp.asarray(rng.permutation(60)[:t], jnp.int32)
    published = ref.init_layer(seed_key(4), 1, TINY)
    taken = builder.halves_from_pairs(published, TINY)
    assert not np.array_equal(taken["wq"], published["wq"])
    np.testing.assert_array_equal(       # the nope columns stay in place
        taken["wq"].reshape(32, hh, -1)[..., :nope],
        published["wq"].reshape(32, hh, -1)[..., :nope])
    f32 = lambda lp: {k: v.astype(jnp.float32) for k, v in lp.items()}
    q, entry = model._latent_q_entry(h, f32(taken), pos)
    got = jnp.einsum("qhd,kd->hqk", q[..., nope:], entry[:, 16:16 + rope])

    def interleaved(a):        # the reference's rotary at these positions
        inv = 1e6 ** (-2.0 * jnp.arange(rope // 2) / rope)
        ang = pos[:, None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        even, odd = a[..., 0::2], a[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         -1).reshape(a.shape)

    w = f32(published)
    q_pe = interleaved((h @ w["wq"]).reshape(t, hh, -1)[..., nope:])
    k_pe = interleaved((h @ w["wkv_a"])[:, None, 16:])[:, 0]
    want = jnp.einsum("qhd,kd->hqk", q_pe, k_pe)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_the_absorbed_form_gives_what_the_expanded_form_gives():
    """One token a row over its cached latents: the keys' up-projection
    in the query and the values' after the sum, against keys and values
    expanded per head."""
    rng = np.random.default_rng(6)
    model = builder.build(TINY, 6)
    lp = model.params_tree["layers"][1]
    rows, w, blocks = 3, 3, 10
    lens = jnp.asarray([0, 5, 11], jnp.int32)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    arena = arr(1, blocks, BT, model.latent_width).at[..., 20:].set(0)
    arena = arena.at[:, 9].set(jnp.nan)      # a freed block's leavings
    tables = jnp.asarray([[9, 9, 9], [4, 2, 9], [7, 1, 3]], jnp.int32)
    q = arr(rows, 4, 12)
    entry = arr(rows, model.latent_width).at[:, 20:].set(0)
    got = model._attend_absorbed(q, entry, lp, arena, 0, tables, lens)
    for r in range(rows):
        n = int(lens[r])
        seen = jnp.concatenate([arena[0][tables[r]].reshape(
            w * BT, -1)[:n], entry[r:r + 1]])
        want = model._attend_expanded(
            q[r:r + 1], seen, lp, q_pos=jnp.asarray([n]),
            kv_pos=jnp.arange(n + 1), q_seg=jnp.ones((1,), jnp.int32),
            kv_seg=jnp.ones((n + 1,), jnp.int32))
        np.testing.assert_allclose(got[r:r + 1], want, atol=1e-5, rtol=1e-5)


def test_the_sigmoid_router():
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    s = jax.nn.sigmoid(h @ wr)
    w, idx = moe.route(h, wr, 3, scoring="sigmoid", scale=2.5)
    # the three largest scores, their own values over their sum, scaled
    order = np.argsort(-np.asarray(s), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(idx, order)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, rtol=1e-6)
    picked = np.take_along_axis(np.asarray(s), order, -1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # a bias moves the choice and never the weight
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 5.0], jnp.float32)
    wb, ib = moe.route(h, wr, 3, scoring="sigmoid", select_bias=bias)
    assert (np.asarray(ib)[:, 0] == 7).all()     # every token's first
    assert (np.asarray(idx) != 7).any(axis=-1).any()
    chosen = np.take_along_axis(np.asarray(s), np.asarray(ib), -1)
    np.testing.assert_allclose(
        wb, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    assert float(wb.max()) < 1.0                 # no 5 in any weight
    # ties go to the lower index
    _, tied = moe.route(jnp.zeros((2, 16)), wr, 3, scoring="sigmoid")
    np.testing.assert_array_equal(tied, [[0, 1, 2]] * 2)
    # the reference's rule is the same rule
    cfg = dict(TINY, num_experts_per_tok=3, routed_scaling_factor=2.5)
    wref, iref = ref.route(h, wr, bias, cfg)
    np.testing.assert_array_equal(iref, ib)
    np.testing.assert_allclose(wref, 2.5 * wb, rtol=1e-6)
    with pytest.raises(ValueError):
        moe.route(h, wr, 3, scoring="tanh")


# the guide's share test: what shares of 2 of the 8 routed experts give,
# with the shared expert (which every share computes) counted once, adds
# up to the uncut reference's layer
def test_the_parts_that_shares_of_the_experts_give_add_up_to_the_layer():
    key, rng = seed_key(9), np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(24, TINY["hidden_size"])), jnp.float32)
    whole = ref.init_layer(key, 2, TINY)
    uncut = ref.layer_forward(x, whole, TINY, "moe")
    silent = lambda lp, *names: dict(lp, **{
        n: jnp.zeros_like(lp[n]) for n in names})
    # the layer with no expert's part: the residual after attention
    after_attention = ref.layer_forward(x, silent(whole, "wd", "sd"), TINY,
                                        "moe")
    shared = ref.layer_forward(x, silent(whole, "wd"), TINY, "moe") \
        - after_attention
    assert float(jnp.abs(shared).max()) > 0.01
    h = ref._rms(after_attention, whole["ln2_s"].astype(jnp.float32),
                 TINY["rms_norm_eps"])
    w, idx = moe.route(h, whole["wr"].astype(jnp.float32), 2,
                       scoring="sigmoid", select_bias=whole["rb"],
                       scale=TINY["routed_scaling_factor"])
    parts_ref, parts_prog, assigned = [], [], 0
    for lo in range(0, 8, 2):
        share = dict(TINY, experts_held=[lo, lo + 1])
        lp = ref.init_layer(key, 2, share)
        for name in ("wg", "wu", "wd"):      # a share holds the same values
            np.testing.assert_array_equal(lp[name], whole[name][lo:lo + 2])
        np.testing.assert_array_equal(lp["sd"], whole["sd"])
        # a share's layer holds the shared expert too: taken off, so that
        # the sum counts it once
        parts_ref.append(ref.layer_forward(x, lp, share, "moe")
                         - after_attention - shared)
        f32 = {k: lp[k].astype(jnp.float32) for k in ("wg", "wu", "wd")}
        y, sums = moe.expert_ffn(h, w, idx, f32["wg"], f32["wu"], f32["wd"],
                                 n_experts=8, experts_held=(lo, lo + 1))
        parts_prog.append(y)
        assigned += int(sums[0])
    assert assigned == 24 * 2                # every assignment, once
    for parts in (parts_ref, parts_prog):
        np.testing.assert_allclose(after_attention + shared + sum(parts),
                                   uncut, atol=TOL, rtol=0)
    assert float(jnp.abs(parts_prog[0]).max()) > 0.01   # no share is idle


def test_a_share_of_the_experts_computes_the_shared_expert_once():
    """The decoder that holds 2 of the 8 experts: its layer is the
    reference's with the same share, shared expert and all."""
    share = dict(TINY, experts_held=[2, 5])
    rng = np.random.default_rng(12)
    model = builder.build(share, 12)
    t = 21
    toks = rng.integers(0, TINY["vocab_size"], t)
    row, seg, pos = (np.zeros((1, 32), np.int32) for _ in range(3))
    row[0, :t], seg[0, :t], pos[0, :t] = toks, 1, np.arange(t)
    got = np.asarray(model.logits(row, seg, pos))[0, :t]
    padded = jnp.zeros((PAD,), jnp.int32).at[:t].set(jnp.asarray(toks))
    want = np.asarray(ref.forward_all(seed_key(12), share, [padded],
                                      [slice(0, t)])[0])
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_latent_arena_holds_one_vector_a_token():
    published = dict(heads=32, d_model=2048, norm="rms", position="rotary",
                     attention="latent", kv_lora_rank=512,
                     qk_nope_head_dim=128, qk_rope_head_dim=64,
                     v_head_dim=128, layer_types=("latent",), layers=2,
                     rope={"latent": {"rope_theta": 1e6}},
                     dtype=jnp.bfloat16, params={})
    from deeplearning4j_tpu.serving.decode import TransformerDecoder
    m = TransformerDecoder(**published)
    assert m.cache_entry() == {"latent": (640,)} and m.head_dim == 192
    c = PagedKVCache(layers=2, heads=m.kv_heads, head_dim=m.head_dim,
                     dtype=m.dtype, layer_kinds=m.layer_kinds(),
                     entry=m.cache_entry(), block_tokens=8, max_blocks=3)
    (arena,), = c.arenas().values()
    assert arena.shape == (2, 4, 8, 640) and c.kinds == ("latent",)
    # 576 values (1,152 B) on 640 lanes: 1,280 B a token a layer, and no
    # K or V per head (32 x (192 + 128) values would be 20,480 B)
    assert c.token_bytes("latent") == 1280
    assert (m.rank + m.rope_dim) * 2 == 1152
    # the tables are the full kind's: every position from 0, none given back
    c.reserve(1, 20)
    c.advance(1, 20)
    assert c.held_from(1) == 0 and c.blocks_in_use("latent") == 3
    tables, starts, lens, starved = c.batch_view([1], 24)
    assert not starts and not starved and tables["latent"].shape == (1, 3)
    c.free(1)
    assert c.blocks_in_use() == 0
    # a pair's default is untouched
    pair = PagedKVCache(layers=1, heads=2, head_dim=4, block_tokens=4,
                        max_blocks=2)
    assert [a.shape[-1] for a in pair.arenas()["full"]] == [8, 8]
    with pytest.raises(ValueError):
        TransformerDecoder(**dict(published, layer_types=("full",)))


def test_the_engine_drains_the_latent_cache_and_counts_what_chunks_read(
        monkeypatch):
    from deeplearning4j_tpu.optimize.metrics import registry
    monkeypatch.setattr(decode, "CONTEXT_SLAB", 32)
    model = builder.build(TINY, 2)
    cache = _cache(model)
    ad = TransformerAdapter(model, cache, pack_bucket=CHUNK, max_rows=2)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).tolist() for n in (6, 50, 20, 33)]
    read, paid = ad._count["ctx_tokens"], ad._count["ctx_read"]
    before, paid_before = read.value(), paid.value()
    with DecodeEngine(ad, max_decode_batch=2) as eng:
        eng.warmup()
        out = {}
        ts = [threading.Thread(target=lambda i=i, p=p: out.__setitem__(
            i, eng.generate(p, max_new_tokens=8)))
            for i, p in enumerate(prompts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert sorted(out) == [0, 1, 2, 3] and all(len(v) == 8
                                               for v in out.values())
    assert cache.blocks_in_use() == 0
    # 50 = 16 + 16 + 16 + 2: the later chunks read 16, 32 and 48 cached
    # positions; 20 reads 16, 33 reads 16 and 32
    assert read.value() - before == (16 + 32 + 48) + 16 + (16 + 32)
    # and gathered them in whole slabs of 32: only the chunk over 48
    # takes two trips
    assert paid.value() - paid_before == 32 * ((1 + 1 + 2) + 1 + (1 + 1))
    assert registry().counter(
        "serving_decode_prefill_context_read_tokens_total", ""
    ).value() >= paid.value() >= read.value()
    # the families by kind are there before any traffic, `latent` too
    for name in ("serving_decode_kv_tokens_total",
                 "serving_kv_block_steps_total"):
        assert registry().counter(name, "").value(kind="latent") > 0
    weights = builder.make_weights(2, TINY)
    gaps = ref.served_gaps(weights, 0, [(prompts[i], out[i]) for i in out],
                           0)
    assert max(g.max() for g, _ in gaps) <= TOL


# the kernels themselves, interpreted
def test_the_latent_decode_kernel_agrees_with_its_dense_arm():
    rng = np.random.default_rng(0)
    rows, hh, width, dv, bt, w, blocks = 3, 8, 48, 32, 8, 4, 12
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    arena = arr(2, blocks, bt, width).at[:, 11].set(jnp.nan)
    args = (arr(rows, hh, width), arr(rows, width), arena, 1,
            jnp.asarray(rng.permutation(11)[:rows * w - 1].tolist() + [11],
                        jnp.int32).reshape(rows, w),
            # row 0 has nothing cached; row 2's last entry is the block of
            # NaNs, past its length
            jnp.asarray([0, 29, 23], jnp.int32))
    kw = dict(v_width=dv, scale=0.2)
    got = fa.latent_decode_attention(*args, impl="paged", interpret=True,
                                     **kw)
    want = fa.latent_decode_attention(*args, impl="dense", **kw)
    assert got.shape == (rows, hh, dv)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # a row with nothing cached returns its own entry's value part
    np.testing.assert_allclose(got[0], jnp.broadcast_to(
        args[1][0, :dv], (hh, dv)), atol=1e-6)


@pytest.mark.parametrize("heads_first", [False, True])
def test_the_prefill_kernel_takes_a_value_head_of_its_own_size(heads_first):
    """Keys of 12 and values of 8 a head, as the expanded latent
    attention has them, the scale given; in both orders of the axes."""
    rng = np.random.default_rng(1)
    tq, n_ctx, hh, d, dv, ctx_len = 16, 16, 4, 12, 8, 11
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    true = np.arange(n_ctx)
    real = true < ctx_len
    seg = np.where(np.arange(tq) < 9, 1, 2)
    seg[-2:] = 0
    kw = dict(
        q_pos=jnp.arange(tq), q_seg=jnp.asarray(seg), scale=0.3,
        kv_pos=jnp.asarray(np.concatenate([np.where(real, true - ctx_len,
                                                    1 << 30),
                                           np.arange(tq)])),
        kv_seg=jnp.asarray(np.concatenate([np.where(real, 1, -1), seg])))
    q, k, v = arr(tq, hh, d), arr(tq + n_ctx, hh, d), arr(tq + n_ctx, hh, dv)
    want = fa.prefill_attention(q, k, v, impl="dense", **kw)
    assert want.shape == (tq, hh, dv)
    turn = (lambda a: a.transpose(1, 0, 2)) if heads_first else (lambda a: a)
    got = turn(fa.prefill_attention(
        turn(q), turn(k), turn(v), impl="flash", interpret=True, q_block=8,
        kv_block=8, heads_first=heads_first,
        name="prefill_attention_latent", **kw))
    np.testing.assert_allclose(got[:-2], want[:-2], atol=1e-5, rtol=1e-5)
    dense = turn(fa.prefill_attention(turn(q), turn(k), turn(v),
                                      impl="dense", heads_first=heads_first,
                                      **kw))
    np.testing.assert_allclose(dense, want, atol=1e-6, rtol=1e-6)


def test_a_stopped_gateway_gives_its_model_and_cache_back():
    """The metrics registry outlives every gateway; it may keep none
    alive, or a stopped gateway's weights and arenas stay on the device
    (the benchmark's reference then has no room beside them)."""
    import gc
    import weakref
    from deeplearning4j_tpu.serving import ServingGateway
    gw = ServingGateway()
    entry = gw.add_decode_model(
        "lm", builder.build(TINY, 3), max_decode_batch=2, pack_bucket=CHUNK,
        kv_block_tokens=BT, kv_max_blocks={"latent": 16})
    assert len(gw.pool.get("lm").engine.generate([1, 2, 3],
                                                 max_new_tokens=2)) == 2
    alive = [weakref.ref(o) for o in (gw, entry.engine.adapter.cache,
                                      entry.engine.adapter.model)]
    gw.stop()
    del gw, entry
    gc.collect()
    assert [w() for w in alive] == [None, None, None]
