"""The decoder set to the block of a model that varies its query heads by
kind of layer (RMS norm, 6 query heads on a full layer and 10 on a
sliding one over 2 KV heads, a gate a head, rotary on half of a full
layer's head under YaRN and on the whole of a sliding one's, a leading
dense SwiGLU layer, then 8-way softmax routing with 3 a token of which a
share is held, and a shared expert) against the plain reference of
`benchmark/reference/laguna.py`, at a small size on the CPU with seeded
random weights: chunked prefill and decoding through the two kinds of
cache, the plain forward, the two kernels at a group of 9, the rotary
tables at the published numbers, the shares of the experts, the gate,
the engine at 64 rows and the counters."""
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.models import laguna as builder
from benchmark.models import seed_key
from benchmark.reference import laguna as ref
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.serving import decode
from deeplearning4j_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                               TransformerAdapter,
                                               TransformerDecoder,
                                               rope_inv_freq)
from chunk_context import check_chunk_over_context

PUBLISHED = manifest.data_file("configs", "laguna-s-2.1")
WINDOW, CHUNK, BT, PAD = 8, 16, 4, 1024
# the published shape at widths a CPU holds: groups of 3 and 5 (no power
# of two), half of a full layer's head turned, layers F S S S F S with a
# dense first one, 2 of 8 experts held, a slice of 96 rows
TINY = dict(PUBLISHED, hidden_size=32, head_dim=8, num_key_value_heads=2,
            num_attention_heads_per_layer=[6, 10, 10, 10] * 12,
            intermediate_size=64, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, num_experts_published=8,
            num_experts=2, experts_held=[2, 5], num_experts_per_tok=3,
            vocab_size=96, sliding_window=WINDOW, num_hidden_layers=6,
            max_context=80, init_std=0.25, dtype="float32")
# Two float32 programs of different shape (chunks and a cache here, one
# whole sequence there) agree to rounding: logits some units wide, sums of
# a few hundred terms. The bfloat16 control must not pass it.
TOL = 2e-4


def _cache(model, full=96, sliding=24):
    return PagedKVCache(
        layers=model.n_layers, heads=model.kv_heads, head_dim=model.head_dim,
        dtype=model.dtype, layer_kinds=model.layer_kinds(),
        window=model.window, entry=model.cache_entry(), block_tokens=BT,
        max_blocks={"full": full, "sliding": sliding})


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


def _plain_logits(model, toks, width=48):
    t = len(toks)
    row, seg, pos = (np.zeros((1, width), np.int32) for _ in range(3))
    row[0, :t], seg[0, :t], pos[0, :t] = toks, 1, np.arange(t)
    return np.asarray(model.logits(row, seg, pos))[0, :t]


def _reference_logits(cfg, seed, toks):
    padded = jnp.zeros((PAD,), jnp.int32).at[:len(toks)].set(
        jnp.asarray(toks))
    return np.asarray(ref.forward_all(seed_key(seed), cfg, [padded],
                                      [slice(0, len(toks))])[0])


# shorter than the window; longer than it; ending on a chunk's edge and
# off it; longer than two chunks
@pytest.mark.parametrize("lengths", [(5, 12), (16, 21), (32, 41, 7)])
def test_chunked_prefill_and_cached_decoding_agree_with_the_reference(
        lengths):
    rng = np.random.default_rng(sum(lengths))
    prompts = {i: rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
               for i, n in enumerate(lengths)}
    # 24 tokens a row: decoding runs past the window of 8, and over
    # fewer the bfloat16 control picks no other token
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


# a slab of 32 cached positions: no context, half a slab, one, two and a half
@pytest.mark.parametrize("ctx", [0, 16, 32, 80])
def test_a_chunk_reads_both_kinds_of_context_at_either_head_count(
        ctx, monkeypatch):
    monkeypatch.setattr(decode, "CONTEXT_SLAB", 32)
    model = builder.build(dict(TINY, max_context=128), 7)
    check_chunk_over_context(model, _cache(model), CHUNK, ctx,
                             TINY["vocab_size"], TOL)


def test_the_plain_forward_gives_the_references_logits():
    """The program's forward (heads by kind, the gate, half rotary under
    YaRN, the dense layer, the share of the experts, the shared expert,
    the sliced head) against the reference's, logit by logit."""
    toks = np.random.default_rng(3).integers(0, TINY["vocab_size"], 37)
    got = _plain_logits(builder.build(TINY, 5), toks)
    want = _reference_logits(TINY, 5, toks)
    assert want.shape == (37, 96) and want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_decoder_is_built_by_kind_of_layer():
    m = builder.build(TINY, 1)
    assert m.heads == {"full": 6, "sliding": 10} and m.kv_heads == 2
    assert [m.heads_of(k) for k in m.layer_kinds()] == [6, 10, 10, 10, 6, 10]
    shapes = [m._leaf_shapes(li) for li in range(6)]
    assert shapes[0]["wq"] == (32, 48) and shapes[1]["wq"] == (32, 80)
    assert shapes[4]["wo"] == (48, 32) and shapes[5]["wo"] == (80, 32)
    assert shapes[0]["wgate"] == (32, 6) and shapes[1]["wgate"] == (32, 10)
    # the cache does not change: both kinds hold 2 x 8 keys and values
    assert shapes[0]["wk"] == shapes[1]["wk"] == (32, 16)
    assert m.cache_entry() == {}
    c = _cache(m)
    assert {k: [a.shape[-1] for a in v] for k, v in c.arenas().items()} \
        == {"full": [16, 16], "sliding": [16, 16]}
    # its own draw follows the same shapes
    own = TransformerDecoder(
        vocab=96, layers=6, d_model=32, heads={"full": 6, "sliding": 10},
        kv_heads=2, head_dim=8, gate="head", norm="rms", position="rotary",
        layer_types=("full", "sliding", "sliding", "sliding"), window=8,
        rope={"full": {"rope_theta": 1e4, "partial_rotary_factor": 0.5},
              "sliding": {"rope_theta": 1e4}})
    drawn = own.params_tree["layers"]
    assert drawn[0]["wgate"].shape == (32, 6)
    assert drawn[3]["wq"].shape == (32, 80) and drawn[4]["wq"].shape == (32, 48)
    assert np.isfinite(_plain_logits(own, np.arange(20))).all()


@pytest.mark.parametrize("bad", [
    dict(heads={"full": 6}),                        # a kind without a count
    dict(heads={"full": 6, "sliding": 9}),          # no multiple of 2
    dict(heads={"full": 6, "sliding": 10}, d_model=None),
    dict(heads={"full": 6, "sliding": 10}, kv_heads=None),
    dict(gate="elementwise"),
    dict(rope={"full": {"rope_theta": 1e4, "partial_rotary_factor": 0.4},
               "sliding": {"rope_theta": 1e4}}),    # 3 values: not even
    dict(rope={"full": {"rope_theta": 1e4, "partial_rotary_factor": 2},
               "sliding": {"rope_theta": 1e4}}),    # wider than the head
])
def test_settings_that_do_not_fit_together_are_refused(bad):
    good = dict(vocab=96, layers=2, d_model=32,
                heads={"full": 6, "sliding": 10}, kv_heads=2, head_dim=8,
                gate="head", norm="rms", position="rotary",
                layer_types=("full", "sliding"), window=8, params={},
                rope={"full": {"rope_theta": 1e4,
                               "partial_rotary_factor": 0.5},
                      "sliding": {"rope_theta": 1e4}})
    TransformerDecoder(**good)
    with pytest.raises(ValueError):
        TransformerDecoder(**{**good, **bad})


def test_latent_attention_takes_neither_a_gate_nor_heads_by_kind():
    latent = dict(heads=4, d_model=32, norm="rms", position="rotary",
                  attention="latent", kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8, layer_types=("latent",),
                  rope={"latent": {"rope_theta": 1e6}}, params={})
    TransformerDecoder(**latent)
    for bad in (dict(gate="head"), dict(heads={"latent": 4}, kv_heads=4),
                dict(rope={"latent": {"rope_theta": 1e6,
                                      "partial_rotary_factor": 0.5}})):
        with pytest.raises(ValueError):
            TransformerDecoder(**{**latent, **bad})


# the tables at the published numbers, against the formulas written out
def test_the_half_rotary_yarn_table_is_the_published_one():
    rp = PUBLISHED["rope_parameters"]
    assert rp["full_attention"]["partial_rotary_factor"] == 0.5
    # sliding: the whole head of 128, plain, theta 10,000
    i = np.arange(64, dtype=np.float64)
    inv, factor = rope_inv_freq(128, rp["sliding_attention"])
    np.testing.assert_allclose(inv, 10000.0 ** (-2 * i / 128), rtol=1e-6)
    assert factor == 1.0
    # full: YaRN over the ROTATED width of 64, not over the head's 128
    i = np.arange(32, dtype=np.float64)
    plain = 500000.0 ** (-2 * i / 64)
    dim = lambda b: 64 * math.log(8192 / (2 * math.pi * b)) \
        / (2 * math.log(500000.0))
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (9, 18)
    r = np.clip((i - low) / (high - low), 0, 1)
    yarn = plain * (1 - r) + plain / 128 * r
    inv, factor = rope_inv_freq(64, rp["full_attention"])
    np.testing.assert_allclose(inv, yarn, rtol=1e-6)
    assert factor == 1.4852030263919618
    assert abs(factor - (0.1 * math.log(128) + 1)) < 1e-12
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
    # over 128 the correction dimensions would be 18 and 35: another table
    wrong, _ = rope_inv_freq(128, rp["full_attention"])
    assert wrong.shape == (64,)
    # the decoder at the published widths holds these, and the reference's
    # own table is the same one
    m = TransformerDecoder(
        vocab=8, layers=2, d_model=3072, heads={"full": 48, "sliding": 72},
        kv_heads=8, head_dim=128, norm="rms", position="rotary",
        layer_types=("full", "sliding"), window=512, params={},
        rope={"full": rp["full_attention"],
              "sliding": rp["sliding_attention"]})
    assert m._rotated == {"full": 64, "sliding": 128}
    np.testing.assert_allclose(m._rope["full"][0], yarn, rtol=1e-6)
    assert m._rope["sliding"][0].shape == (64,)
    for kind, width, want in (("sliding", 128, None), ("full", 64, yarn)):
        got_width, got, _ = ref.rope_table(PUBLISHED, kind)
        assert got_width == width
        if want is not None:
            np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)


def test_rotary_turns_the_first_values_of_a_head_and_passes_the_rest():
    m = builder.build(TINY, 1)
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.normal(size=(7, 6, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 2, 9, 30, 31, 60], jnp.int32)
    full, sliding = m._rotate(a, pos, "full"), m._rotate(a, pos, "sliding")
    np.testing.assert_array_equal(full[..., 4:], a[..., 4:])
    assert float(jnp.abs(full[1:, :, :4] - a[1:, :, :4]).max()) > 0.1
    assert float(jnp.abs(sliding[1:, :, 4:] - a[1:, :, 4:]).max()) > 0.1
    np.testing.assert_allclose(full[0], a[0] * np.concatenate(
        [np.full(4, PUBLISHED["rope_parameters"]["full_attention"][
            "attention_factor"]), np.ones(4)]), rtol=1e-6)
    # at positions 0.. the reference's rotary is the same
    line = jnp.arange(7, dtype=jnp.int32)
    for kind in ("full", "sliding"):
        np.testing.assert_allclose(
            m._rotate(a, line, kind), ref._rope(a, ref.rope_table(TINY, kind)),
            atol=1e-6, rtol=1e-6)


# the guide's share test: what the four shares of 2 of the 8 routed
# experts give, with the shared expert (which every share computes)
# counted once, adds up to the uncut reference's layer
def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    key, rng = seed_key(9), np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(24, TINY["hidden_size"])), jnp.float32)
    uncut_cfg = dict(TINY, experts_held=list(range(8)), num_experts=8)
    whole = ref.init_layer(key, 2, uncut_cfg)
    fwd = lambda lp, cfg: ref.layer_forward(x, lp, cfg, "sliding", "moe")
    uncut = fwd(whole, uncut_cfg)
    silent = lambda lp, *names: dict(lp, **{
        n: jnp.zeros_like(lp[n]) for n in names})
    # the layer with no expert's part: the residual after attention
    after_attention = fwd(silent(whole, "wd", "sd"), uncut_cfg)
    shared = fwd(silent(whole, "wd"), uncut_cfg) - after_attention
    assert float(jnp.abs(shared).max()) > 0.01
    h = ref._rms(after_attention, whole["ln2_s"].astype(jnp.float32),
                 TINY["rms_norm_eps"])
    w, idx = moe.route(h, whole["wr"].astype(jnp.float32), 3,
                       scale=TINY["moe_routed_scaling_factor"])
    wref, iref = ref.route(h, whole["wr"].astype(jnp.float32), TINY)
    np.testing.assert_array_equal(idx, iref)         # the same rule
    np.testing.assert_allclose(w, wref, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, rtol=1e-6)
    parts_ref, parts_prog, assigned = [], [], 0
    for lo in range(0, 8, 2):
        share = dict(TINY, experts_held=[lo, lo + 1])
        lp = ref.init_layer(key, 2, share)
        for name in ("wg", "wu", "wd"):      # a share holds the same values
            np.testing.assert_array_equal(lp[name], whole[name][lo:lo + 2])
        np.testing.assert_array_equal(lp["sd"], whole["sd"])
        np.testing.assert_array_equal(lp["wr"], whole["wr"])   # 8 outputs
        # a share's layer holds the shared expert too: taken off, so that
        # the sum counts it once
        parts_ref.append(fwd(lp, share) - after_attention - shared)
        f32 = {k: lp[k].astype(jnp.float32) for k in ("wg", "wu", "wd")}
        y, sums = moe.expert_ffn(h, w, idx, f32["wg"], f32["wu"], f32["wd"],
                                 n_experts=8, experts_held=(lo, lo + 1))
        parts_prog.append(y)
        assigned += int(sums[0])
    assert assigned == 24 * 3                # every assignment, once
    for parts in (parts_ref, parts_prog):
        np.testing.assert_allclose(after_attention + shared + sum(parts),
                                   uncut, atol=TOL, rtol=0)
    assert float(jnp.abs(parts_prog[0]).max()) > 0.01   # no share is idle


def test_the_gate_halves_the_attention_when_its_matrix_is_nought():
    """sigmoid(0) = 0.5: with `wgate` zeroed every head's output is
    halved before `wo`, which is the ungated model with `wo` halved, and
    not the ungated model."""
    toks = np.random.default_rng(8).integers(0, TINY["vocab_size"], 30)
    gated = builder.build(TINY, 8)
    drawn = _plain_logits(gated, toks)
    zeroed = jax.tree_util.tree_map(lambda a: a, gated.params_tree)
    zeroed["layers"] = [dict(lp, wgate=jnp.zeros_like(lp["wgate"]))
                        for lp in zeroed["layers"]]
    gated.params_tree = zeroed
    at_half = _plain_logits(gated, toks)
    plain = builder.build(TINY, 8)
    plain.gate = None
    plain._logits_fn = jax.jit(plain._logits_pure)
    ungated = _plain_logits(plain, toks)
    plain.params_tree = dict(plain.params_tree, layers=[
        dict(lp, wo=lp["wo"] * 0.5) for lp in plain.params_tree["layers"]])
    halved = _plain_logits(plain, toks)
    np.testing.assert_allclose(at_half, halved, atol=1e-5, rtol=0)
    assert np.abs(at_half - ungated).max() > 0.05
    assert np.abs(drawn - at_half).max() > 0.05     # and the drawn gate acts


def test_the_engine_drains_both_kinds_at_64_rows_and_counts_the_routing():
    model = builder.build(dict(TINY, max_context=256), 2)
    cache = _cache(model, full=64 * 56, sliding=64 * 4 + 16)
    ad = TransformerAdapter(model, cache, pack_bucket=CHUNK, max_rows=64)
    rng = np.random.default_rng(1)
    sizes = [6, 50, 20, 33] + [int(n) for n in rng.integers(4, 30, 66)]
    prompts = [rng.integers(0, 96, n).tolist() for n in sizes]
    names = ("routed", "assignments", "touched", "peak")
    before = {n: ad._count[n].value() for n in names}
    # long enough that the first rows still decode when the last join
    new, rows = 160, []
    with DecodeEngine(ad, max_decode_batch=64, queue_limit=128) as eng:
        eng.warmup()
        real = ad.step

        def watched(rids):
            rows.append(len(rids))
            return real(rids)

        ad.step = watched
        out = {}
        ts = [threading.Thread(target=lambda i=i, p=p: out.__setitem__(
            i, eng.generate(p, max_new_tokens=new)))
            for i, p in enumerate(prompts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert len(out) == 70 and all(len(v) == new for v in out.values())
    assert max(rows) == 64                   # the engine filled its rows
    assert cache.blocks_in_use() == 0
    rose = {n: ad._count[n].value() - before[n] for n in names}
    # a token's first comes from its prompt's chunk; every other one is a
    # row of a step, routed to 3 experts in each of the 5 sparse layers
    assert rose["routed"] == 70 * (new - 1) * 3 * 5 == sum(rows) * 15
    # 2 of the 8 experts are held: their part of the assignments, more
    # than none and fewer than all; at most 2 touched a layer a step
    assert 0 < rose["assignments"] < rose["routed"]
    assert 0 < rose["touched"] <= len(rows) * 5 * 2
    assert rose["peak"] <= rose["assignments"]
    from deeplearning4j_tpu.optimize.metrics import registry
    assert registry().counter("serving_moe_assignments_routed_total", ""
                              ).value() >= rose["routed"]
    weights = builder.make_weights(2, dict(TINY, max_context=256))
    some = [0, 1, 3, 69]
    gaps = ref.served_gaps(weights, 0, [(prompts[i], out[i]) for i in some],
                           0)
    assert max(g.max() for g, _ in gaps) <= TOL


# the kernels themselves, interpreted, at the groups the model has: 6
# query heads a KV head (48 over 8) and 9 (72 over 8), no power of two
@pytest.mark.parametrize("group,window", [(6, None), (9, 12), (9, None)])
def test_the_paged_decode_kernel_agrees_with_its_dense_arm(group, window):
    rng = np.random.default_rng(group)
    rows, kvh, d, bt, w, blocks = 3, 2, 16, 8, 4, 12
    hh = group * kvh
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    ak, av = arr(2, blocks, bt, kvh * d), arr(2, blocks, bt, kvh * d)
    ak = ak.at[:, 11].set(jnp.nan)          # a freed block's leavings
    args = (arr(rows, hh, d), arr(rows, kvh * d), arr(rows, kvh * d), ak, av,
            1, jnp.asarray(rng.permutation(11)[:rows * w - 1].tolist() + [11],
                           jnp.int32).reshape(rows, w),
            jnp.asarray([0, 8, 16], jnp.int32) if window else
            jnp.zeros((rows,), jnp.int32),
            # row 2's last table entry is the block of NaNs, past its length
            jnp.asarray([5, 29, 16 + 9 if window else 23], jnp.int32))
    got = fa.paged_decode_attention(*args, window=window, impl="paged",
                                    interpret=True,
                                    name="decode_attention_sliding")
    want = fa.paged_decode_attention(*args, window=window, impl="dense")
    assert got.shape == (rows, hh, d)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # head n read KV head n // group: with the keys of every KV head but
    # the last zeroed, only the last group's heads still tell rows apart
    by_head = fa.paged_decode_attention(
        args[0], args[1].at[:, :d].set(0), args[2],
        ak.at[..., :d].set(0), av, *args[5:], window=window, impl="dense")
    assert not np.allclose(by_head[:, :group], want[:, :group], atol=1e-3)
    np.testing.assert_allclose(by_head[:, group:], want[:, group:],
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("group,window", [(6, None), (9, 6), (9, None)])
def test_the_prefill_kernel_agrees_with_its_dense_arm(group, window):
    rng = np.random.default_rng(2 + group)
    tq, n_ctx, kvh, d, ctx_len, start = 16, 16, 2, 8, 11, 3
    hh = group * kvh
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    true = start + np.arange(n_ctx)
    real = true < ctx_len
    seg = np.where(np.arange(tq) < 9, 1, 2)
    seg[-2:] = 0
    kw = dict(
        q_pos=jnp.arange(tq), q_seg=jnp.asarray(seg), window=window,
        kv_pos=jnp.asarray(np.concatenate([np.where(real, true - ctx_len,
                                                    1 << 30),
                                           np.arange(tq)])),
        kv_seg=jnp.asarray(np.concatenate([np.where(real, 1, -1), seg])))
    q, k, v = arr(tq, hh, d), arr(tq + n_ctx, kvh, d), arr(tq + n_ctx, kvh, d)
    got = fa.prefill_attention(q, k, v, impl="flash", interpret=True,
                               q_block=8, kv_block=8,
                               name="prefill_attention_sliding", **kw)
    want = fa.prefill_attention(q, k, v, impl="dense", **kw)
    assert got.shape == (tq, hh, d)
    np.testing.assert_allclose(got[:-2], want[:-2], atol=1e-5, rtol=1e-5)
