"""The query-key pairs a prefill chunk's attention has to compute, and
those the prefill kernel computes, counted on the host at each chunk's
launch (`TransformerAdapter._chunk_work`): in closed form a segment and a
part at a time, held here to a brute-force count of the masks
`_attend_chunk` hands `prefill_attention`, position by position, for
random chunks at the benchmark's widths (nothing of their size is made:
the decoders hold no weights)."""
import numpy as np
import pytest

from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.serving.decode import (PagedKVCache,
                                               TransformerAdapter,
                                               TransformerDecoder)

PACK, BT = 2048, 256
SETTINGS = {
    # Mellum's widths: three sliding layers (1,024) and a full one
    "full+sliding-1024": dict(
        heads=32, kv_heads=4, head_dim=128, d_model=2304,
        layer_types=("sliding", "sliding", "sliding", "full"), layers=4,
        window=1024),
    # Laguna's kinds: full, and sliding at 512
    "full+sliding-512": dict(
        heads={"full": 48, "sliding": 72}, kv_heads=8, head_dim=128,
        d_model=3072, layer_types=("full", "sliding"), layers=2, window=512),
    # kanana's latent attention
    "latent": dict(
        heads=32, d_model=2048, attention="latent", kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        layer_types=("latent",), layers=2, position="rotary",
        rope={"latent": {"rope_theta": 1e6}}),
}


def _adapter(setting):
    m = TransformerDecoder(params={}, vocab=128, ff=64, max_context=32768,
                           **SETTINGS[setting])
    cache = PagedKVCache(layers=m.n_layers, heads=1, head_dim=1,
                         layer_kinds=m.layer_kinds(), window=m.window,
                         block_tokens=BT, max_blocks=1)
    return TransformerAdapter(m, cache, pack_bucket=PACK, max_rows=32)


def _layouts(rng, window, n=6):
    """Random chunks: several prompts packed, padding after them, and in
    half of them a first segment that is a later slice over `ctx_len`
    cached positions (a sliding kind's table from a block that may
    already lie behind the window)."""
    for i in range(n):
        cuts = np.sort(rng.choice(np.arange(1, PACK), rng.integers(1, 8),
                                  replace=False))
        end = int(cuts[-1]) if i % 3 else PACK        # some fill the row
        seg = np.zeros(PACK, np.int32)
        for s, (a, b) in enumerate(zip([0, *cuts[:-1]], [*cuts[:-1], end])):
            seg[a:b] = s + 1
        ctx_len = int(rng.integers(1, 12000)) if i % 2 else 0
        start = 0
        if ctx_len and window:
            start = BT * int(rng.integers(0, max(1, (ctx_len - window) // BT)
                                          + 1))
        yield seg, ctx_len, start


def _parts(ad, kind, seg, ctx_len, start):
    """What `_attend_chunk` hands `prefill_attention` in a layer of
    `kind`: ((kv_pos, kv_seg), the part's kernel blocks or None) for the
    chunk's own keys and, with a context, for all its slabs at once."""
    line = np.arange(PACK, dtype=np.int32)
    own, slab = ad._kernel_blocks[kind]
    out = [((line, seg), own)]
    n = ad._slab_tokens[kind]
    true = start + np.arange(max(0, -(-(ctx_len - start) // n)) * n)
    if true.size:
        real = true < ctx_len
        out.append(((np.where(real, true - ctx_len, 1 << 30),
                     np.where(real, 1, -1)), slab))
    return out


def _brute(ad, seg, ctx_len, start):
    """{(kind, arm): visible pairs of real queries x layers}, {kind:
    non-skipped blocks x tile x block x layers}, from the masks and the
    kernel's own class table."""
    m, line = ad.model, np.arange(PACK, dtype=np.int32)
    pairs, run = {}, {}
    for kind, layers in ad._layers_of.items():
        window = m.window if kind == "sliding" else None
        starts = start if kind == "sliding" else 0
        for (kv_pos, kv_seg), blocks in _parts(ad, kind, seg, ctx_len,
                                               starts):
            ok = fa._visible(line, kv_pos, seg, kv_seg, window)[seg > 0]
            key = kind, "dense" if blocks is None else "kernel"
            pairs[key] = pairs.get(key, 0) + layers * int(ok.sum())
            if blocks is not None:
                qb, kb, _ = blocks
                table = fa.key_block_classes(line, kv_pos, seg, kv_seg,
                                             window, qb, kb)
                run[kind] = run.get(kind, 0) + layers * qb * kb * int(
                    (table != fa.KEY_SKIPPED).sum())
    return pairs, run


@pytest.mark.parametrize("setting", list(SETTINGS))
def test_visible_pairs_are_what_the_dense_arms_masks_leave(setting):
    """Off a TPU every part takes the dense arm: `pairs{arm=dense}` is
    the reference mask's count and the kernel runs nothing."""
    ad = _adapter(setting)
    rng = np.random.default_rng(37)
    for seg, ctx_len, start in _layouts(rng, ad.model.window):
        starts = {k: np.int32(start) for k in ad.cache.kinds
                  if k == "sliding"}
        work = ad._chunk_work(seg, ctx_len, starts)
        want, run = _brute(ad, seg, ctx_len, start)
        assert {k: v for k, v in work.pairs.items() if v} == \
            {k: v for k, v in want.items() if v}
        assert all(arm == "dense" for _, arm in work.pairs)
        assert work.pairs_run == run == {} and work.blocks == 0


@pytest.mark.parametrize("setting", list(SETTINGS))
def test_the_kernel_computes_its_blocks_and_no_fewer_pairs_than_visible(
        monkeypatch, setting):
    """Under the described v5e's block rule: the visible pairs by arm are
    still the masks' count, the pairs the kernel runs are its non-skipped
    blocks times the tile and the block, and they hold every visible
    pair of the parts it takes."""
    monkeypatch.setattr(fa, "flash_attention_available", lambda: True)
    ad = _adapter(setting)
    assert any(own for own, _ in ad._kernel_blocks.values())
    rng = np.random.default_rng(36)
    for seg, ctx_len, start in _layouts(rng, ad.model.window):
        starts = {k: np.int32(start) for k in ad.cache.kinds
                  if k == "sliding"}
        work = ad._chunk_work(seg, ctx_len, starts)
        want, run = _brute(ad, seg, ctx_len, start)
        assert {k: v for k, v in work.pairs.items() if v} == \
            {k: v for k, v in want.items() if v}
        assert work.pairs_run == run
        for kind, n in work.pairs_run.items():
            assert work.pairs.get((kind, "kernel"), 0) <= n
        # a block is at most tile x block pairs
        assert sum(work.pairs_run.values()) <= work.blocks * 512 * 1024


def _counts():
    c = registry().counter
    return ({(k, a): c("serving_decode_prefill_pairs_total").labels(
        kind=k, arm=a).value() for k in ("full", "sliding")
        for a in ("kernel", "dense")},
        c("serving_decode_prefill_chunks_total").value())


def test_a_chunk_counts_its_pairs_at_its_launch():
    """`prefill_group` adds what `_chunk_work` counts to the counters,
    once a chunk: two prompts packed, then a long one's later slice over
    its cached first chunk, under a window of 8."""
    m = TransformerDecoder(vocab=48, layers=2, heads=2, head_dim=8, ff=16,
                           max_context=96, seed=4,
                           layer_types=("sliding", "full"), window=8)
    cache = PagedKVCache(layers=2, heads=2, head_dim=8, block_tokens=4,
                         layer_kinds=m.layer_kinds(), window=8,
                         max_blocks=64)
    ad = TransformerAdapter(m, cache, pack_bucket=16, max_rows=3)
    before, chunks = _counts()
    prompts = [(1, np.arange(1, 6, dtype=np.int32)),
               (2, np.arange(2, 14, dtype=np.int32) % 40),
               (3, np.arange(3, 23, dtype=np.int32) % 40)]
    for group in ad.pack_groups(prompts):
        ad.prefill_group(group)
    ad.collect()
    after, chunks_after = _counts()
    got = {k: after[k] - before[k] for k in after}
    # 5 and 12 alone; 16 then 4 over 16 cached: full sees all, sliding
    # at most 8 keys
    tri = lambda n, w=None: sum(min(i + 1, w or n) for i in range(n))
    full = tri(5) + tri(12) + tri(16) + (tri(20) - tri(16))
    sliding = tri(5, 8) + tri(12, 8) + tri(16, 8) + (tri(20, 8)
                                                     - tri(16, 8))
    assert got == {("full", "dense"): full, ("sliding", "dense"): sliding,
                   ("full", "kernel"): 0, ("sliding", "kernel"): 0}
    assert chunks_after - chunks == len(ad.pack_groups(prompts))
