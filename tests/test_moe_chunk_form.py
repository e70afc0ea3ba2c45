"""The experts' grouped product in a prefill chunk's form (`ops/moe.py`):
gmm takes the whole contraction at once, where a decode step keeps the
tiling it had. On the CPU both run through
`ragged_dot`: the layer must give the plain sum over its experts, the
rows it reports must be the rows gmm's own metadata runs at the call's
tiling, the tiling rule must give every served chunk its form and every
served step exactly what it had, and through `prefill_group` the two
counters of the chunks' expert layers rise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from benchmark import manifest
from benchmark.models import mellum2 as builder
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.serving.decode import (PagedKVCache,
                                               TransformerAdapter)

D, F = 16, 8


def _tiles_run(sizes, m, tm):
    """Row tiles gmm's kernel visits for groups of `sizes` in `m` rows."""
    n = len(sizes)
    return int(jax.jit(lambda s: make_group_metadata(
        group_sizes=s, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=n, visit_empty_groups=False)[1])(
            jnp.asarray(sizes, jnp.int32)))


def _choices(rng, t, experts, k, never=(), always=None):
    """Each of `t` tokens' `k` distinct experts at random, none of
    `never`, `always` among them where given; weights that sum to 1."""
    pool = [e for e in range(experts) if e not in never and e != always]
    idx = np.stack([rng.choice(pool, k, replace=False) for _ in range(t)])
    if always is not None:
        idx[:, 0] = always
    w = rng.uniform(0.1, 1.0, (t, k))
    return (jnp.asarray(w / w.sum(1, keepdims=True), jnp.float32),
            jnp.asarray(idx, jnp.int32))


def _case(name, rng):
    """-> (h, weights, experts, n_experts, experts_held, valid)."""
    # 128 tokens x 4 of 8 experts: 64 rows an expert, a chunk's form; a
    # step's: 16 tokens x 2, 4 rows an expert
    t, experts, k, held, valid = 128, 8, 4, None, None
    if name == "a step's shape":
        t, k = 16, 2
    if name == "an expert over several tiles":
        t = 512                 # all 512 pick expert 5: two tiles of 256
    h = jnp.asarray(rng.normal(size=(t, D)), jnp.float32)
    if name.endswith("router"):
        scoring = name.split()[0]
        wr = jnp.asarray(rng.normal(size=(D, experts)), jnp.float32)
        bias = jnp.asarray(rng.uniform(-0.1, 0.1, experts)) \
            if scoring == "sigmoid" else None
        w, idx = moe.route(h, wr, k, scoring=scoring, select_bias=bias,
                           scale=2.5)
        return h, w, idx, experts, held, valid
    if name == "a quarter held":
        experts, held = 16, (1, 6, 9, 14)
    never = (3,) if name == "an expert with no rows" else ()
    always = 5 if name == "an expert over several tiles" else None
    w, idx = _choices(rng, t, experts, k, never, always)
    if name == "a valid mask":
        valid = jnp.asarray(np.arange(t) % 3 != 0)
    return h, w, idx, experts, held, valid


CASES = ["all held", "a quarter held", "a valid mask",
         "an expert with no rows", "an expert over several tiles",
         "softmax router", "sigmoid router", "a step's shape"]


@pytest.mark.parametrize("name", CASES)
def test_the_layer_and_its_sums_at_the_calls_form(name):
    rng = np.random.default_rng(CASES.index(name))
    h, w, idx, experts, held, valid = _case(name, rng)
    n = experts if held is None else len(held)
    wg, wu = (rng.normal(size=(n, D, F)) * 0.3 for _ in range(2))
    wd = rng.normal(size=(n, F, D)) * 0.3
    y, stats = moe.expert_ffn(
        h, w, idx, *(jnp.asarray(a, jnp.float32) for a in (wg, wu, wd)),
        n_experts=experts, experts_held=held, valid=valid)
    # the plain sum over each token's held choices
    slot = np.full(experts, -1)
    slot[list(range(experts) if held is None else held)] = np.arange(n)
    s = slot[np.asarray(idx)]
    if valid is not None:
        s[~np.asarray(valid)] = -1
    x = np.asarray(h, np.float64)
    g = np.einsum("td,tkdf->tkf", x, wg[s])
    u = np.einsum("td,tkdf->tkf", x, wu[s])
    a = g / (1 + np.exp(-g)) * u
    want = np.einsum("tkf,tkfd->tkd", a, wd[s]) * np.asarray(w)[..., None]
    want = np.where((s >= 0)[..., None], want, 0).sum(1)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert np.abs(want).max() > 0.1
    sizes = np.bincount(s[s >= 0], minlength=n)
    assert stats[:3].tolist() == [sizes.sum(), (sizes > 0).sum(),
                                  sizes.max()]
    # the rows the gate and up products run: gmm's own tiles
    m = idx.size
    tm = moe.grouped_tiling(m, n, D, F)[0]
    assert tm == (256 if name != "a step's shape" else 32)
    assert moe.chunk_form(m, n) == (name != "a step's shape")
    assert int(stats[3]) == _tiles_run(sizes, m, tm) * tm
    assert stats[0] <= stats[3] <= stats[0] + (n - 1) * tm + tm
    if name == "an expert with no rows":
        assert sizes[3] == 0 and int(stats[1]) == n - 1
    if name == "an expert over several tiles":
        assert sizes[5] == 512


@pytest.mark.parametrize("tm", [8, 128, 256])
def test_the_rows_computed_are_the_tiles_gmm_runs(tm):
    """`rows_computed` against the kernel's own group metadata: every
    tile a group touches, a tile two groups share once for each."""
    rng, n, m = np.random.default_rng(tm), 24, 40 * tm
    tiles_of = jax.jit(lambda sizes: make_group_metadata(
        group_sizes=sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=n, visit_empty_groups=False)[1])
    rows_of = jax.jit(lambda sizes: moe.rows_computed(sizes, tm))
    for _ in range(20):
        sizes = rng.multinomial(int(rng.integers(0, m)),
                                rng.dirichlet(np.ones(n) * 0.5))
        sizes[rng.random(n) < 0.2] = 0
        sizes = jnp.asarray(sizes, jnp.int32)
        assert int(rows_of(sizes)) == int(tiles_of(sizes)) * tm


# (experts a token, held, hidden, width, rows a step) of the served cells:
# a chunk of 2,048 positions, a step at the cell's rows
SERVED = {"mellum": (8, 64, 2304, 896, 32), "kanana": (6, 128, 2048, 768, 32),
          "laguna": (10, 64, 3072, 1024, 64)}
CHUNK_TILING = {"mellum": ((256, 2304, 896), (256, 896, 768)),
                "kanana": ((256, 2048, 768), (256, 768, 1024)),
                "laguna": ((256, 3072, 512), (256, 1024, 1024))}
# what every step ran before the chunk had a form of its own
STEP_TILING = {"mellum": ((256, 768, 896), (256, 896, 768)),
               "kanana": ((64, 1024, 768), (64, 768, 1024)),
               "laguna": ((128, 1024, 1024), (128, 1024, 1024))}


@pytest.mark.parametrize("cell", list(SERVED))
def test_a_chunk_takes_its_form_and_a_step_keeps_its_tiling(cell):
    k, held, d, f, rows = SERVED[cell]
    chunk, step = 2048 * k, rows * k
    assert moe.chunk_form(chunk, held) and not moe.chunk_form(step, held)
    up, down = (moe.grouped_tiling(chunk, held, a, b) for a, b in
                ((d, f), (f, d)))
    assert (up, down) == CHUNK_TILING[cell]
    for (tm, tk, tn), kk in ((up, d), (down, f)):
        assert tk == kk                      # the whole contraction
        assert moe.gmm_vmem_bytes(tm, tk, tn, 2) <= moe.GMM_VMEM_BYTES
    assert (moe.grouped_tiling(step, held, d, f),
            moe.grouped_tiling(step, held, f, d)) == STEP_TILING[cell]


TINY = dict(manifest.data_file("configs", "mellum2-12b-a2.5b-instruct"),
            hidden_size=32, head_dim=8, num_attention_heads=4,
            num_key_value_heads=2, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, vocab_size=96, sliding_window=8,
            num_hidden_layers=4, max_context=160, init_std=0.25,
            dtype="float32")


def test_the_chunks_counters_rise_through_prefill_group():
    """A chunk of 64 positions routes 128 assignments a layer to 4
    experts: 32 rows an expert, the chunk's form, one row tile of 128."""
    model = builder.build(TINY, 3)
    assert moe.chunk_form(64 * 2, 4)
    cache = PagedKVCache(
        layers=model.n_layers, heads=model.kv_heads, head_dim=model.head_dim,
        dtype=model.dtype, layer_kinds=model.layer_kinds(),
        window=model.window, block_tokens=4,
        max_blocks={"full": 96, "sliding": 32})
    ad = TransformerAdapter(model, cache, pack_bucket=64, max_rows=4)
    names = ("prefill_assignments", "prefill_rows", "assignments")
    before = {n: ad._count[n].value() for n in names}
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 96, n) for n in (40, 17, 90)]
    groups = ad.pack_groups(list(enumerate(prompts)))
    for g in groups:
        ad.prefill_group(g)
    ad.collect()
    rose = {n: ad._count[n].value() - before[n] for n in names}
    # every real position, 2 experts in each of the 4 sparse layers
    assert rose["prefill_assignments"] == 147 * 2 * 4
    assert rose["prefill_assignments"] <= rose["prefill_rows"]
    # a chunk's layer runs its one tile once for each expert in it
    tm = moe.grouped_tiling(128, 4, 32, 16)[0]
    assert tm == 128 and rose["prefill_rows"] % tm == 0
    assert rose["prefill_rows"] <= len(groups) * 4 * 4 * tm
    assert rose["assignments"] == 0          # a step's, not a chunk's
