"""What the decoders' tests of a chunk over its cached context share
(test_decode_mla.py: the latent kind; test_decode_moe.py: the full and
the sliding kind): a prompt's last slice, read through the cache a slab
at a time, against the plain forward of the whole prompt."""
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.serving.decode import TransformerAdapter


def _plain(model, prompt, chunk):
    """The plain forward of `prompt` alone: (logits [t, vocab], the
    entries it would cache {kind: tuple of [layers of the kind, t,
    width]})."""
    t = len(prompt)
    row, seg, pos = (np.zeros((1, -(-t // chunk) * chunk), np.int32)
                     for _ in range(3))
    row[0, :t], seg[0, :t], pos[0, :t] = prompt, 1, np.arange(t)
    _, new, _ = model._chunk_forward(model.params_tree, row[0], seg[0],
                                     pos[0])
    return (np.asarray(model.logits(row, seg, pos))[0, :t],
            {k: tuple(np.stack(a)[:, :t] for a in arrays)
             for k, arrays in new.items()})


def check_chunk_over_context(model, cache, chunk, ctx, vocab, tol):
    """A prompt of `ctx` + 11 tokens: the chunks before its last go
    through the adapter into `cache`, every other position of which
    holds NaN (a freed block keeps its last owner's data, and so does
    the scratch block); the last chunk (the prompt's tail as segment 1,
    a whole short prompt beside it as segment 2, padding) then reads
    `ctx` cached positions. Its logits, the entries it hands the cache
    and the entries the earlier chunks wrote must be the plain
    forward's."""
    rng = np.random.default_rng(ctx)
    tail, other = 11, 4
    ad = TransformerAdapter(model, cache, pack_bucket=chunk, max_rows=2)
    cache.update(lambda a: (jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan), a),))
    long = rng.integers(0, vocab, ctx + tail).astype(np.int32)
    short = rng.integers(0, vocab, other).astype(np.int32)
    for group in ad.pack_groups([(0, long)])[:-1]:
        assert ad.prefill_group(group) == ({}, {})
    assert ad.collect() == ({}, {}) and cache.length(0) == ctx
    if ctx:
        tables, starts = cache.context(0, ad._ctx_widths)
    else:
        tables = {k: np.full((w,), cache.scratch_of[k], np.int32)
                  for k, w in ad._ctx_widths.items()}
        starts = {k: 0 for k in cache.kinds}
    n = tail + other
    tokens, seg, pos = (np.zeros((chunk,), np.int32) for _ in range(3))
    tokens[:tail], seg[:tail], pos[:tail] = long[ctx:], 1, \
        ctx + np.arange(tail)
    tokens[tail:n], seg[tail:n], pos[tail:n] = short, 2, np.arange(other)
    x, new, _ = jax.jit(model._chunk_forward)(
        model.params_tree, tokens, seg, pos,
        (cache.arenas(), tables, starts, np.int32(ctx)))
    got = np.asarray(model._head(model.params_tree, x))[:n]
    want_long, cached_long = _plain(model, long, chunk)
    want_short, cached_short = _plain(model, short, chunk)
    assert np.isfinite(got).all() and want_long.std() > 0.5
    np.testing.assert_allclose(got[:tail], want_long[ctx:], atol=tol, rtol=0)
    np.testing.assert_allclose(got[tail:], want_short, atol=tol, rtol=0)
    bt = cache.block_tokens
    for kind, arrays in new.items():
        for j, layers in enumerate(arrays):
            mine = np.stack(layers)
            np.testing.assert_allclose(
                mine[:, :tail], cached_long[kind][j][:, ctx:], atol=tol,
                rtol=0)
            np.testing.assert_allclose(
                mine[:, tail:n], cached_short[kind][j], atol=tol, rtol=0)
            # what the earlier chunks wrote, as far as the kind holds it
            held = np.arange(int(starts[kind]), ctx)
            blocks = tables[kind][(held - int(starts[kind])) // bt]
            np.testing.assert_allclose(
                np.asarray(cache.arenas()[kind][j])[:, blocks, held % bt],
                cached_long[kind][j][:, held], atol=tol, rtol=0)
    return ad
