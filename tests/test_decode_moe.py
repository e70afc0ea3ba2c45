"""The decoder set to a sparse, windowed block (RMS norm, rotary with a
plain and a YaRN table, grouped KV heads, 8 SwiGLU experts with 2 a
token, layers in the pattern S S S F, window 8) against the plain
reference of `benchmark/reference/mellum2.py`, at a small size on the
CPU with seeded random weights: chunked prefill and decoding through the
two kinds of cache, the share of the experts a chip holds, the rotary
tables at the published numbers, and the sliding layers' blocks."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.models import mellum2 as builder
from benchmark.models import seed_key
from benchmark.reference import mellum2 as ref
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.parallel.inference import KVCacheExhaustedError
from deeplearning4j_tpu.serving import decode
from deeplearning4j_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                               TransformerAdapter,
                                               rope_inv_freq)
from chunk_context import check_chunk_over_context

PUBLISHED = manifest.data_file("configs", "mellum2-12b-a2.5b-instruct")
WINDOW, CHUNK, BT, PAD = 8, 16, 4, 1024
TINY = dict(PUBLISHED, hidden_size=32, head_dim=8, num_attention_heads=4,
            num_key_value_heads=2, moe_intermediate_size=16, num_experts=8,
            num_experts_per_tok=2, vocab_size=96, sliding_window=WINDOW,
            num_hidden_layers=8, max_context=80, init_std=0.25,
            dtype="float32")
# Two float32 programs of different shape (chunks and a cache here, one
# whole sequence there) agree to rounding: logits some units wide, sums of
# a few hundred terms. The bfloat16 control must not pass it.
TOL = 2e-4


def _served(cfg, seed, prompts, new=6):
    """Serve `prompts` through chunked prefill and the cache, all rows
    in one batch. -> ({rid: tokens}, cache, adapter)."""
    model = builder.build(cfg, seed)
    cache = PagedKVCache(
        layers=model.n_layers, heads=model.kv_heads, head_dim=model.head_dim,
        dtype=model.dtype, layer_kinds=model.layer_kinds(),
        window=model.window, block_tokens=BT,
        max_blocks={"full": 96, "sliding": 24})
    ad = TransformerAdapter(model, cache, pack_bucket=CHUNK,
                            max_rows=len(prompts))
    out = {r: [] for r in prompts}
    # as the engine runs them: each chunk and step is launched before
    # the one before it is fetched, the tokens read from the device's
    # feed; what comes back is the work before's
    launches = [(ad.prefill_group, g)
                for g in ad.pack_groups(list(prompts.items()))] \
        + [(ad.step, list(prompts))] * (new - 1) + [(ad.collect,)]
    for n, (launch, *args) in enumerate(launches):
        got, fails = launch(*args)
        assert not fails and (n or not got)
        for r in got:
            out[r].append(got[r])
    return out, cache, ad


# (a) shorter than the window; longer than it; ending on a chunk's edge and
# off it; longer than two chunks
@pytest.mark.parametrize("lengths", [(5, 12), (16, 21), (32, 41, 7)])
def test_chunked_prefill_and_cached_decoding_agree_with_the_reference(
        lengths):
    rng = np.random.default_rng(sum(lengths))
    prompts = {i: rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
               for i, n in enumerate(lengths)}
    served, cache, _ = _served(TINY, 11, prompts)
    weights = builder.make_weights(11, TINY)
    seqs = [(prompts[r].tolist(), served[r]) for r in prompts]
    gaps = ref.served_gaps(weights, 0, seqs, 0, lowp="bfloat16")
    for (gap, control), (prompt, _) in zip(gaps, seqs):
        # every served token is the reference's best, or level with it
        assert gap.max() <= TOL, (len(prompt), gap)
    # the nearest precision below fails the same tolerance somewhere
    assert max(c.max() for _, c in gaps) > TOL
    for r, p in prompts.items():           # what a sliding layer still holds
        n = len(p) + len(served[r]) - 1
        assert cache.length(r) == n
        assert cache.held_from(r) == max(0, (n - WINDOW + 1) // BT) * BT


# a slab of 32 cached positions (8 table entries) for the full kind: no
# context, half a slab, exactly one, and two and a half; the sliding
# kind's whole table (a window's blocks and one) is one slab, read once
# or not at all
@pytest.mark.parametrize("ctx", [0, 16, 32, 80])
def test_a_chunk_reads_both_kinds_of_context_a_slab_at_a_time(
        ctx, monkeypatch):
    monkeypatch.setattr(decode, "CONTEXT_SLAB", 32)
    model = builder.build(dict(TINY, max_context=128), 7)
    cache = PagedKVCache(
        layers=model.n_layers, heads=model.kv_heads, head_dim=model.head_dim,
        dtype=model.dtype, layer_kinds=model.layer_kinds(),
        window=model.window, block_tokens=BT,
        max_blocks={"full": 48, "sliding": 12})
    ad = check_chunk_over_context(model, cache, CHUNK, ctx,
                                  TINY["vocab_size"], TOL)
    assert ad._ctx_widths == {"full": 40, "sliding": WINDOW // BT + 1}
    assert ad._slab_tokens == {"full": 32, "sliding": WINDOW + BT}


def test_the_plain_forward_gives_the_references_logits():
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


# (b) the guide's share test: what shares of 2 of the 8 experts give adds
# up to the uncut reference's layer
def test_the_parts_that_shares_of_the_experts_give_add_up_to_the_layer():
    key, rng = seed_key(9), np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(24, TINY["hidden_size"])), jnp.float32)
    whole = ref.init_layer(key, 3, TINY)
    uncut = ref.layer_forward(x, whole, TINY, "full")
    # the layer with no expert's part: the residual after attention
    after_attention = ref.layer_forward(
        x, dict(whole, wd=jnp.zeros_like(whole["wd"])), TINY, "full")
    h = ref._rms(after_attention, whole["ln2_s"].astype(jnp.float32),
                 TINY["rms_norm_eps"])
    w, idx = moe.route(h, whole["wr"].astype(jnp.float32), 2)
    parts_ref, parts_prog, assigned = [], [], 0
    for lo in range(0, 8, 2):
        share = dict(TINY, experts_held=[lo, lo + 1])
        lp = ref.init_layer(key, 3, share)
        for name in ("wg", "wu", "wd"):      # a share holds the same values
            np.testing.assert_array_equal(lp[name], whole[name][lo:lo + 2])
        parts_ref.append(ref.layer_forward(x, lp, share, "full")
                         - after_attention)
        f32 = {k: lp[k].astype(jnp.float32) for k in ("wg", "wu", "wd")}
        y, sums = moe.expert_ffn(h, w, idx, f32["wg"], f32["wu"], f32["wd"],
                                 n_experts=8, experts_held=(lo, lo + 1))
        parts_prog.append(y)
        assigned += int(sums[0])
    assert assigned == 24 * 2                # every assignment, once
    for parts in (parts_ref, parts_prog):
        np.testing.assert_allclose(after_attention + sum(parts), uncut,
                                   atol=TOL, rtol=0)
    assert float(jnp.abs(parts_prog[0]).max()) > 0.01   # no share is idle


# (c) the tables at the published numbers, against the formulas written out
def test_the_rotary_tables_are_the_published_ones():
    rp = PUBLISHED["rope_parameters"]
    i = np.arange(64, dtype=np.float64)
    plain = 500000.0 ** (-2 * i / 128)
    inv, factor = rope_inv_freq(128, rp["sliding_attention"])
    np.testing.assert_allclose(inv, plain, rtol=1e-6)
    assert factor == 1.0
    dim = lambda b: 128 * math.log(8192 / (2 * math.pi * b)) \
        / (2 * math.log(500000.0))
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (18, 35)
    r = np.clip((i - low) / (high - low), 0, 1)
    yarn = plain * (1 - r) + plain / 16 * r
    inv, factor = rope_inv_freq(128, rp["full_attention"])
    np.testing.assert_allclose(inv, yarn, rtol=1e-6)
    assert factor == 1.2772588722239782 == 0.1 * math.log(16) + 1
    # fast dimensions as published, slow ones a sixteenth; the reference's
    # own table is the same one
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    for kind, want in (("sliding", plain), ("full", yarn)):
        got, _ = ref.rope_table(PUBLISHED, kind)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)


# (d) a sliding layer's blocks are given back behind the window
def test_a_sliding_table_gives_back_the_blocks_behind_its_window():
    c = PagedKVCache(layers=4, heads=1, head_dim=2, block_tokens=4,
                     max_blocks={"full": 16, "sliding": 6},
                     layer_kinds=["sliding", "sliding", "sliding", "full"],
                     window=8)
    assert [a.shape[0] for a, _ in c.arenas().values()] == [1, 3]
    where = c.reserve(1, 10)
    assert c.blocks_in_use("full") == c.blocks_in_use("sliding") == 3
    assert where["sliding"][1].tolist() == [0, 1, 2, 3] * 2 + [0, 1]
    c.advance(1, 10)         # positions 0-2 are 8 or more behind: not yet a
    assert c.held_from(1) == 0 and c.blocks_in_use("sliding") == 3  # block
    c.extend(1, 12)
    c.advance(1, 2)          # length 12: block 0 (0-3) lies behind 12 - 8
    assert c.held_from(1) == 4 and c.blocks_in_use("sliding") == 2
    assert c.blocks_in_use("full") == 3
    tables, starts, lens, starved = c.batch_view([1], 16)
    assert not starved and lens.tolist() == [12]
    assert starts["sliding"].tolist() == [4]
    assert tables["full"].shape == (1, 4) and tables["sliding"].shape == (1, 3)
    # all or nothing across kinds: the sliding arena cannot take 7 blocks,
    # so the full one gives none either
    with pytest.raises(KVCacheExhaustedError):
        c.reserve(2, 28)
    assert c.blocks_in_use("full") == 4 and c.length(2) == 0
    c.free(1)
    assert c.blocks_in_use() == 0 and c.free_blocks() == 22


def test_the_engine_drains_both_kinds_and_steps_between_chunks():
    model = builder.build(TINY, 2)
    cache = PagedKVCache(
        layers=8, heads=2, head_dim=8, layer_kinds=model.layer_kinds(),
        window=WINDOW, block_tokens=BT,
        max_blocks={"full": 96, "sliding": 24})
    ad = TransformerAdapter(model, cache, pack_bucket=CHUNK, max_rows=2)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, n).tolist() for n in (6, 50, 20, 33)]
    with DecodeEngine(ad, max_decode_batch=2) as eng:
        eng.warmup()
        peak = []
        real = ad.step

        def watched(rids):
            peak.append(cache.blocks_in_use("sliding"))
            return real(rids)

        ad.step = watched
        import threading
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
    # two rows of a window and a block of slack, and one prompt in
    # prefill at a window and a chunk
    assert max(peak) <= 2 * (WINDOW // BT + 1) + (WINDOW + CHUNK) // BT + 1
    assert cache.blocks_in_use() == 0
    weights = builder.make_weights(2, TINY)
    gaps = ref.served_gaps(weights, 0, [(prompts[i], out[i]) for i in out],
                           0)
    assert max(g.max() for g, _ in gaps) <= TOL


# the kernels themselves, interpreted: grouped heads, the window, the table
@pytest.mark.parametrize("window", [None, 12])
def test_the_paged_decode_kernel_agrees_with_its_dense_arm(window):
    rng = np.random.default_rng(0)
    rows, hh, kvh, d, bt, w, blocks = 3, 8, 2, 16, 8, 4, 12
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
                                    interpret=True)
    want = fa.paged_decode_attention(*args, window=window, impl="dense")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _parts(window, **how):
    """Sixteen queries (two segments and padding) over sixteen cached
    positions from 3, of which eight lie below ctx_len, and their own:
    the operands and `prefill_attention`'s keywords."""
    rng = np.random.default_rng(2)
    tq, n_ctx, hh, kvh, d, ctx_len, start = 16, 16, 4, 2, 8, 11, 3
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
        kv_seg=jnp.asarray(np.concatenate([np.where(real, 1, -1), seg])),
        **how)
    return (arr(tq, hh, d), arr(tq + n_ctx, kvh, d),
            arr(tq + n_ctx, kvh, d)), kw, seg


@pytest.mark.parametrize("window", [None, 6])
def test_the_prefill_kernel_agrees_with_its_dense_arm(window):
    (q, k, v), kw, _ = _parts(window)
    got = fa.prefill_attention(q, k, v, impl="flash", interpret=True,
                               q_block=8, kv_block=8, **kw)
    want = fa.prefill_attention(q, k, v, impl="dense", **kw)
    np.testing.assert_allclose(got[:-2], want[:-2], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [None, 6])
def test_the_prefill_kernel_hands_back_the_rows_log_sum_exp(window):
    """Over the cached positions alone: segment 1 sees some of them,
    the other rows none, and both arms say so alike."""
    (q, k, v), kw, seg = _parts(window, return_lse=True)
    kw.update(kv_pos=kw["kv_pos"][:16], kv_seg=kw["kv_seg"][:16])
    got, got_lse = fa.prefill_attention(q, k[:16], v[:16], impl="flash",
                                        interpret=True, q_block=8,
                                        kv_block=8, **kw)
    want, want_lse = fa.prefill_attention(q, k[:16], v[:16], impl="dense",
                                          **kw)
    assert got.dtype == want.dtype == got_lse.dtype == jnp.float32
    assert got.shape == (16, 4, 8) and got_lse.shape == (16, 4)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_lse, want_lse, atol=1e-5, rtol=1e-5)
    blind = seg != 1
    if window:      # the last cached key lies at -1: 6 behind line 5
        blind = blind | (np.arange(16) >= window - 1)
    seen = np.asarray(got_lse) > fa.NEG / 2
    np.testing.assert_array_equal(seen, ~np.broadcast_to(blind[:, None],
                                                          (16, 4)))
    assert (np.asarray(got_lse)[blind] == fa.NEG).all()
    assert (np.asarray(got)[blind] == 0).all()
    # heads first: the same numbers, turned
    turn = lambda a: a.transpose(1, 0, 2)
    first, first_lse = fa.prefill_attention(
        turn(q), turn(k[:16]), turn(v[:16]), impl="dense", heads_first=True,
        **kw)
    np.testing.assert_allclose(turn(first), want, atol=1e-6)
    np.testing.assert_allclose(first_lse.T, want_lse, atol=1e-6)


@pytest.mark.parametrize("window", [None, 6])
def test_parts_of_a_key_range_merged_by_lse_are_the_whole(window):
    (q, k, v), kw, seg = _parts(window)
    whole = fa.prefill_attention(q, k, v, impl="dense", **kw)
    cut = lambda lo, hi: fa.prefill_attention(
        q, k[lo:hi], v[lo:hi], impl="dense", return_lse=True,
        **dict(kw, kv_pos=kw["kv_pos"][lo:hi], kv_seg=kw["kv_seg"][lo:hi]))
    own, context = cut(16, 32), cut(0, 16)
    o, lse = fa.merge_attention(*own, *context)
    np.testing.assert_allclose(o[:-2], whole[:-2], atol=1e-5, rtol=1e-5)
    # the rows of segment 2 and the padding see no cached key: the merge
    # leaves them as they were, bit for bit
    blind = seg != 1
    np.testing.assert_array_equal(np.asarray(o)[blind],
                                  np.asarray(own[0])[blind])
    np.testing.assert_array_equal(np.asarray(lse)[blind],
                                  np.asarray(own[1])[blind])
    # a part that no query sees at all (every key past ctx_len) changes
    # nothing, whatever its values hold
    none = fa.prefill_attention(
        q, k[:16], v[:16], impl="dense", return_lse=True, **dict(
            kw, kv_pos=jnp.full((16,), 1 << 30), kv_seg=-jnp.ones((16,),
                                                                  jnp.int32)))
    again, lse_again = fa.merge_attention(o, lse, *none)
    np.testing.assert_array_equal(again, o)
    np.testing.assert_array_equal(lse_again, lse)
    # and in the other order, from nothing
    flipped, _ = fa.merge_attention(*none, o, lse)
    np.testing.assert_array_equal(flipped, o)


def _context(tk, held):
    """A slab of `tk` cached positions of which `held` lie below
    ctx_len, as `_attend_chunk` hands them over: those before the row at
    their true distance in segment 1, the rest no key."""
    real = np.arange(tk) < held
    return (np.where(real, np.arange(tk) - held, 1 << 30),
            np.where(real, 1, -1))


def _block_case(what):
    """Operands at lane-multiple blocks, one case a kind of key block the
    kernel tells apart -> (q, k, v, keywords, blocks, rows that see no
    key)."""
    rng = np.random.default_rng(36)
    hh, kvh, d, dv, tq, window = 4, 2, 16, 16, 256, None
    blocks = dict(q_block=128, kv_block=128)
    seg = np.ones(tq, np.int64)
    if what == "whole slab":
        kp, ks = _context(512, 512)
    elif what == "last slab":           # the seam at 300 cuts a key block
        kp, ks = _context(512, 300)
    elif what == "blind rows":          # padding and segment 2 see no slab
        seg[150:200], seg[200:] = 2, 0
        kp, ks = _context(512, 300)
    elif what == "diagonal":
        kp, ks = np.arange(tq), seg
    elif what == "seam":                # cuts a q tile and a key block
        seg[200:], seg[-20:] = 2, 0
        kp, ks = np.arange(tq), seg
    elif what in ("window 512", "window 1024"):
        window, tq = int(what.split()[1]), 512
        blocks = dict(q_block=256, kv_block=256)
        seg = np.ones(tq, np.int64)
        own = what == "window 512"      # the chunk's own keys / a context
        kp, ks = (np.arange(tq), seg) if own else _context(1536, 1400)
    elif what == "keys 192 values 128":
        hh, kvh, d, dv = 2, 2, 192, 128
        kp, ks = _context(512, 450)
    elif what == "72 heads over 8":
        hh, kvh, tq = 72, 8, 128
        seg = np.ones(tq, np.int64)
        kp, ks = np.arange(tq), seg
    else:
        raise ValueError(what)
    tk = kp.size
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    k, v = arr(tk, kvh, d), arr(tk, kvh, dv)
    if ks.min() < 0:                    # a freed block's entries are zeroed
        k, v = (jnp.where(jnp.asarray(ks > 0)[:, None, None], a, 0)
                for a in (k, v))
    kw = dict(q_pos=jnp.arange(tq), q_seg=jnp.asarray(seg), window=window,
              kv_pos=jnp.asarray(kp), kv_seg=jnp.asarray(ks))
    seen = np.asarray(fa._visible(np.arange(tq), kp, seg, ks, window)
                      ).any(axis=1)
    return arr(tq, hh, d), k, v, kw, blocks, ~seen


BLOCK_CASES = ["whole slab", "last slab", "blind rows", "diagonal", "seam",
               "window 512", "window 1024", "keys 192 values 128",
               "72 heads over 8"]


@pytest.mark.parametrize("return_lse", [False, True], ids=["o", "o+lse"])
@pytest.mark.parametrize("what", BLOCK_CASES)
def test_the_prefill_kernel_by_class_of_key_block(what, return_lse):
    """The flash arm against the dense arm where the key blocks are
    skipped, whole and edge ones in turn; a row that sees no key gives
    zeros and an lse of NEG in both."""
    q, k, v, kw, blocks, blind = _block_case(what)
    classes = np.asarray(fa.key_block_classes(
        *(np.asarray(kw[n]) for n in ("q_pos", "kv_pos", "q_seg", "kv_seg")),
        kw["window"], blocks["q_block"], blocks["kv_block"]))
    want_classes = {"whole slab": {fa.KEY_WHOLE},
                    "72 heads over 8": {fa.KEY_EDGE}}.get(what)
    if want_classes:
        assert set(classes.ravel().tolist()) == want_classes
    elif what not in ("keys 192 values 128",):
        assert {fa.KEY_SKIPPED, fa.KEY_EDGE} <= set(classes.ravel().tolist())
    got = fa.prefill_attention(q, k, v, impl="flash", interpret=True,
                               return_lse=return_lse, **blocks, **kw)
    want = fa.prefill_attention(q, k, v, impl="dense",
                                return_lse=return_lse, **kw)
    if not return_lse:
        # a blind row's result is the dense arm's even softmax over NEG
        np.testing.assert_allclose(np.asarray(got)[~blind],
                                   np.asarray(want)[~blind],
                                   atol=2e-5, rtol=2e-5)
        assert (np.asarray(got)[blind] == 0).all()
        return
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert (np.asarray(got[1])[blind] == fa.NEG).all()
    assert (np.asarray(got[0])[blind] == 0).all()
    assert blind.any() == (what == "blind rows")


@pytest.mark.parametrize("what", ["last slab", "diagonal", "window 1024"])
def test_a_key_block_of_1024_gives_what_blocks_of_256_give(what):
    """One class for 1,024 keys against four for 256 each taken in one
    step, over 2,048 keys: the same rows, to rounding."""
    rng = np.random.default_rng(7)
    tq, tk, hh, d = 256, 2048, 2, 16
    window = 1024 if what == "window 1024" else None
    if what == "diagonal":
        tq = tk
        kp, ks = np.arange(tk), np.ones(tk, np.int64)
    else:
        kp, ks = _context(tk, 1500 if what == "last slab" else tk)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = arr(tq, hh, d), arr(tk, hh, d), arr(tk, hh, d)
    kw = dict(q_pos=jnp.arange(tq), q_seg=jnp.ones((tq,), jnp.int32),
              kv_pos=jnp.asarray(kp), kv_seg=jnp.asarray(ks), window=window,
              return_lse=True, interpret=True, impl="flash", q_block=256)
    assert fa.prefill_kernel_blocks(tq, tk, d, d, impl="flash", q_block=256,
                                    kv_block=256)[2] == 8
    one = fa.prefill_attention(q, k, v, kv_block=1024, **kw)
    four = fa.prefill_attention(q, k, v, kv_block=256, **kw)
    want = fa.prefill_attention(q, k, v, **dict(kw, impl="dense"))
    for a, b, c in zip(one, four, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(b, c, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 192])
def test_kernel_parts_joined_by_lse_are_the_whole(window):
    """A chunk's own keys and two slabs of its context, each through the
    kernel, joined as `_attend_chunk` joins them, against the dense arm
    over all the keys at once."""
    rng = np.random.default_rng(11)
    tq, n, held, hh, kvh, d = 256, 256, 400, 4, 2, 16
    seg = np.where(np.arange(tq) < 180, 1, 2)
    kp, ks = _context(2 * n, held)
    kv_pos, kv_seg = np.concatenate([kp, np.arange(tq)]), \
        np.concatenate([ks, seg])
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = arr(tq, hh, d), arr(2 * n + tq, kvh, d), arr(2 * n + tq, kvh, d)
    kw = dict(q_pos=jnp.arange(tq), q_seg=jnp.asarray(seg), window=window)
    part = lambda lo, hi: fa.prefill_attention(
        q, k[lo:hi], v[lo:hi], impl="flash", interpret=True, q_block=128,
        kv_block=128, return_lse=True, kv_pos=jnp.asarray(kv_pos[lo:hi]),
        kv_seg=jnp.asarray(kv_seg[lo:hi]), **kw)
    o, _ = fa.merge_attention(*fa.merge_attention(
        *part(2 * n, 2 * n + tq), *part(0, n)), *part(n, 2 * n))
    whole = fa.prefill_attention(q, k, v, impl="dense",
                                 kv_pos=jnp.asarray(kv_pos),
                                 kv_seg=jnp.asarray(kv_seg), **kw)
    np.testing.assert_allclose(o, whole, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 5, 40])
@pytest.mark.parametrize("keys", ["own", "context", "anything"])
def test_key_block_classes_against_every_pair(keys, window):
    """skipped => no pair of the block is visible, whole => every pair
    is; NumPy's and jax's arrays give the same table."""
    rng = np.random.default_rng(len(keys) + (window or 0))
    tq, qb, kb = 64, 16, 8
    q_seg = np.searchsorted([32, 50], np.arange(tq), side="right") + 1
    q_seg[-5:] = 0
    q_pos = np.arange(tq)
    if keys == "own":
        kv_pos, kv_seg = q_pos, q_seg
    elif keys == "context":
        kv_pos, kv_seg = _context(96, 53)
    else:                       # ids and places in no order at all
        kv_pos = rng.integers(-40, 80, 96)
        kv_seg = rng.integers(-1, 4, 96)
        q_pos, q_seg = rng.integers(0, 64, tq), rng.integers(0, 4, tq)
    args = (q_pos, kv_pos, q_seg, kv_seg)
    table = fa.key_block_classes(*args, window, qb, kb)
    assert isinstance(table, np.ndarray) and table.dtype == np.int32
    assert table.shape == (tq // qb, kv_pos.size // kb)
    on_jax = fa.key_block_classes(*map(jnp.asarray, args), window, qb, kb)
    np.testing.assert_array_equal(table, np.asarray(on_jax))
    ok = np.asarray(fa._visible(*args, window))
    tiles = ok.reshape(tq // qb, qb, -1, kb)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    assert not some[table == fa.KEY_SKIPPED].any()
    assert every[table == fa.KEY_WHOLE].all()
    if keys != "anything":      # and on a chunk's operands it is exact
        np.testing.assert_array_equal(table == fa.KEY_WHOLE, every)
        assert set(table.ravel().tolist()) == {0, 1, 2} or window == 5
