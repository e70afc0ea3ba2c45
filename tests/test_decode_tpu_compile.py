"""The decode step and the prefill chunk compiled for a described TPU
v5e at the benchmark's widths (two layers): no chip is attached and
nothing runs. What only the chip's compiler shows: the donated arenas
alias their outputs, no executable re-lays an arena out (with the layer
as a window axis of the scatter, or heads and head_dim as two trailing
axes, it copied all of it on every step; the gather inside the loop over
a chunk's context reads the arena where it lies too), the Pallas kernels
are in and Mosaic takes them. Two settings of the one decoder: OPT-1.3B's dense
float32 block, and a sparse bfloat16 block with grouped KV heads, a
sliding and a full layer side by side and 64 experts; and a latent
bfloat16 block (a dense layer and a sparse one with a shared expert)
whose one arena a kind holds [latent | k_pe] on 640 lanes; and a bfloat16
block with query heads by kind of layer (48 full, 72 sliding over 8 KV
heads), a gate a head, half rotary and a quarter of 256 experts. One file, the
topology described in a fixture: see the on-chip-measurement guide,
section 2."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.serving.decode import (PagedKVCache,
                                               TransformerAdapter,
                                               TransformerDecoder)

LAYERS = 2
DENSE = dict(
    model=dict(vocab=50272, layers=LAYERS, heads=32, head_dim=64, ff=8192,
               max_context=255),
    cache=dict(block_tokens=16, max_blocks=256), rows=8, kv=128, pack=128,
    step_kernels=LAYERS, prefill_kernels=0)
SPARSE = dict(
    model=dict(vocab=98304, layers=LAYERS, heads=32, kv_heads=4,
               head_dim=128, d_model=2304, ff=896, max_context=8320,
               norm="rms", position="rotary", mlp="moe", experts=64,
               experts_per_token=8, tied=False, dtype=jnp.bfloat16,
               layer_types=("sliding", "full"), window=1024,
               row_buckets="full",
               rope={"sliding": {"rope_theta": 500000.0},
                     "full": {"rope_type": "yarn", "rope_theta": 500000.0,
                              "factor": 16.0, "beta_fast": 32,
                              "beta_slow": 1,
                              "original_max_position_embeddings": 8192}}),
    cache=dict(block_tokens=256, max_blocks={"full": 1056, "sliding": 256}),
    rows=32, kv=8448, pack=2048,
    # a layer: the attention kernel and the grouped product's three; a
    # chunk's attention is two calls, over its own keys and over a slab
    # of its context inside the loop
    step_kernels=4 * LAYERS, prefill_kernels=5 * LAYERS,
    # a chunk's own temporaries: 2,048 tokens x 8 experts each, their
    # float32 products of width 2,304 gathered back into token order
    prefill_temporaries=2048 * 8 * 2304 * 12)
LATENT = dict(
    model=dict(vocab=128256, layers=LAYERS, heads=32, d_model=2048, ff=768,
               max_context=32768, norm="rms", position="rotary",
               attention="latent", kv_lora_rank=512, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128,
               layer_types=("latent",), mlp_types=("dense", "moe"),
               dense_ff=6144, shared_ff=1536, experts=128,
               experts_per_token=6, router="sigmoid", route_scale=2.448,
               tied=False, dtype=jnp.bfloat16, row_buckets="full",
               rope={"latent": {"rope_theta": 1000000.0}}),
    cache=dict(block_tokens=256, max_blocks={"latent": 4160}),
    rows=32, kv=33024, pack=2048,
    # the attention kernel a layer, and the sparse layer's grouped
    # product's three; a chunk's attention is two calls (its own keys,
    # and a slab of its context inside the loop)
    step_kernels=LAYERS + 3, prefill_kernels=2 * LAYERS + 3,
    # a chunk's own temporaries: one slab of 4,096 cached entries and
    # its 2,048 own expanded for 32 heads, a part at a time (k_nope 128
    # and the shared k_pe 64 broadcast, joined to keys on 256 lanes,
    # values on 128: 226 MB where the table's whole 33,024 + 2,048 were
    # 1.29 GB), beside the chunk's routed products (2,048 x 6 rows of
    # 2,048 in float32, gathered back: 302 MB)
    prefill_temporaries=(4096 + 2048) * 32 * (128 + 64 + 256 + 128) * 2
    + 2048 * 6 * 2048 * 12 + (64 << 20))
LAGUNA = dict(
    model=dict(vocab=25088, layers=LAYERS,
               heads={"full": 48, "sliding": 72}, kv_heads=8, head_dim=128,
               d_model=3072, gate="head", ff=1024, max_context=5376,
               norm="rms", position="rotary",
               layer_types=("full", "sliding"), window=512,
               mlp_types=("dense", "moe"), dense_ff=12288, shared_ff=1024,
               experts=256, experts_per_token=10,
               experts_held=tuple(range(64)), route_scale=2.5, tied=False,
               dtype=jnp.bfloat16, row_buckets="full",
               rope={"sliding": {"rope_theta": 10000.0,
                                 "partial_rotary_factor": 1},
                     "full": {"rope_type": "yarn", "rope_theta": 500000.0,
                              "factor": 128.0, "beta_fast": 32,
                              "beta_slow": 1,
                              "original_max_position_embeddings": 8192,
                              "attention_factor": 1.4852030263919618,
                              "partial_rotary_factor": 0.5}}),
    cache=dict(block_tokens=256, max_blocks={"full": 1344, "sliding": 272}),
    rows=64, kv=5632, pack=2048,
    # the attention kernel a layer (48 query heads on the full layer's
    # 1,024-lane rows, 72 on the sliding one's: a block-diagonal query of
    # [72, 1024]) and the sparse layer's grouped product's three; a
    # chunk's attention is two calls a layer, but the sliding layer's
    # context (3 table entries, 768 keys) is under the kernel's floor of
    # 1,024 keys and takes the dense arm
    step_kernels=LAYERS + 3, prefill_kernels=2 * LAYERS - 1 + 3,
    # a chunk's own temporaries: 2,048 tokens x 10 choices each, gathered
    # whether their expert is held here or not, their float32 products
    # of width 3,072 gathered back into token order
    prefill_temporaries=2048 * 10 * 3072 * 12 + (256 << 20))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(one_chip, monkeypatch):
    """`compile(fn, donate, *shapes)` for the described chip, with the
    persistent cache off (an executable for a chip that is not attached
    cannot be read back) and the kernel's dispatch steered as on a TPU."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(fa, "flash_attention_available", lambda: True)
    monkeypatch.setattr(moe, "grouped_kernel_available", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def run(fn, donate, *args):
        return jax.jit(fn, donate_argnums=donate).lower(
            *on_chip(args)).compile()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(setting):
    """The model, its parameter tree, the arenas and one call's host
    operands, all as shapes: nothing of the benchmark's size is made."""
    m = TransformerDecoder(params={}, **setting["model"])
    params = jax.eval_shape(lambda: m._draw(0))
    cache = jax.eval_shape(lambda: PagedKVCache(
        layers=m.n_layers, heads=m.kv_heads, head_dim=m.head_dim,
        dtype=m.dtype, layer_kinds=m.layer_kinds(), window=m.window,
        entry=m.cache_entry(), **setting["cache"]).arenas())
    return m, params, cache


def _adapter(setting, m):
    """The adapter over a cache of one block a kind (the host's side is
    the same at any arena size). -> (adapter, that cache)."""
    tiny = PagedKVCache(layers=m.n_layers, heads=1, head_dim=1,
                        layer_kinds=m.layer_kinds(), window=m.window,
                        block_tokens=setting["cache"]["block_tokens"],
                        max_blocks=1)
    return TransformerAdapter(m, tiny, pack_bucket=setting["pack"],
                              max_rows=setting["rows"]), tiny


def _host_operands(setting, m, which):
    """What the adapter hands the executable."""
    ad, tiny = _adapter(setting, m)
    i32 = lambda *shape: np.zeros(shape, np.int32)
    if which == "step":
        tables, starts, lens, _ = tiny.batch_view((), setting["kv"],
                                                  setting["rows"])
        return lens, lens, tables, starts, lens, ad._feed
    pb = ad.pack_bucket
    return (i32(pb), i32(pb), i32(pb),
            {k: (i32(pb), i32(pb)) for k in tiny.kinds},
            {k: i32(w) for k, w in ad._ctx_widths.items()},
            {k: np.int32(0) for k in tiny.kinds if k == "sliding"},
            np.int32(0), i32(ad.last_width), ad._feed, i32(ad.last_width))


@pytest.mark.parametrize("which", ["step", "prefill"])
@pytest.mark.parametrize("setting", [DENSE, SPARSE, LATENT, LAGUNA],
                         ids=["dense", "sparse", "latent", "heads-by-kind"])
def test_the_arenas_are_updated_where_they_lie(compiled, setting, which):
    m, params, arenas = _shapes(setting)
    ops = _host_operands(setting, m, which)
    if which == "step":
        exe = compiled(m._step_pure, (3, 7), params, *ops[:2], arenas,
                       *ops[2:])
    else:
        exe = compiled(m._prefill_pure, (4, 10), params, *ops[:3], arenas,
                       *ops[3:])
    text = exe.as_text()
    leaves = jax.tree_util.tree_leaves(arenas)
    alias = re.search(r"input_output_alias=\{([^\n]*?)\}, entry", text)
    # every arena, and the feed of the rows' next tokens
    assert alias and alias.group(1).count("may-alias") + \
        alias.group(1).count("must-alias") == len(leaves) + 1, \
        "an arena or the feed is not donated"
    arena_bytes = [int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves]
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(arena_bytes)
    assert mem.temp_size_in_bytes < setting.get(
        which + "_temporaries", min(arena_bytes)), \
        "the executable holds a copy of an arena"
    for a in leaves:
        dims = ",".join(str(d) for d in a.shape)
        name = {"float32": "f32", "bfloat16": "bf16"}[a.dtype.name]
        layouts = set(re.findall(r"%s\[%s\]\{([\d,]+)" % (name, dims), text))
        assert layouts == {"3,2,1,0"}, f"an arena is re-laid out: {layouts}"
    assert text.count("tpu_custom_call") == setting[which + "_kernels"]


KERNEL_SHAPES = {
    # heads, KV heads, key and value head size, window; each as a chunk
    # of 2,048 runs it: over its own keys and over a slab of its context
    "latent 192/128": (32, 32, 192, 128, None, (2048, 4096)),
    "mellum full": (32, 4, 128, 128, None, (2048, 4096)),
    "mellum sliding": (32, 4, 128, 128, 1024, (2048, 1280)),
    "laguna full 48 over 8": (48, 8, 128, 128, None, (2048, 4096)),
    "laguna sliding 72 over 8": (72, 8, 128, 128, 512, (2048,)),
}


@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
def test_mosaic_takes_the_prefill_kernel_inside_its_fast_memory(compiled,
                                                                shape):
    """The kernel with its three bodies at the block sizes the wrapper
    picks from the shapes, compiled under PREFILL_VMEM_LIMIT (Mosaic
    refuses a kernel that needs more than it is compiled with), and
    what the wrapper plans for those blocks lies under that limit."""
    hh, kvh, d, dv, window, keys = KERNEL_SHAPES[shape]
    tq = 2048
    for tk in keys:
        qb, kb, parts = fa.prefill_kernel_blocks(tq, tk, d, dv)
        # 2,048 keys a step: two blocks of 1,024, or a window's 1,280 whole
        assert (qb, kb, parts) == ((512, 1024, 2) if tk % 1024 == 0
                                   else (512, 256, 5))
        pad = lambda n: -(-n // 128) * 128
        planned = fa._prefill_vmem_bytes(qb, kb * parts, pad(d), pad(dv), 2,
                                         4)
        assert planned <= fa._PREFILL_VMEM_PLAN < fa.PREFILL_VMEM_LIMIT
        i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
        bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        exe = compiled(
            lambda q, k, v, qp, kp, qs, ks: fa.prefill_attention(
                q, k, v, q_pos=qp, kv_pos=kp, q_seg=qs, kv_seg=ks,
                window=window, heads_first=True, return_lse=True,
                name="prefill_attention_probe"),
            (), bf(hh, tq, d), bf(kvh, tk, d), bf(kvh, tk, dv), i32(tq),
            i32(tk), i32(tq), i32(tk))
        text = exe.as_text()
        assert text.count("tpu_custom_call") == 1
        assert "prefill_attention_probe" in text
        assert str(fa.PREFILL_VMEM_LIMIT) in text, \
            "the kernel is not compiled under PREFILL_VMEM_LIMIT"


# experts a token, held, hidden and expert width of the served sparse cells
GMM_SHAPES = {"mellum": (8, 64, 2304, 896), "kanana": (6, 128, 2048, 768),
              "laguna": (10, 64, 3072, 1024)}


@pytest.mark.parametrize("product", ["gate_up", "down"])
@pytest.mark.parametrize("cell", list(GMM_SHAPES))
def test_mosaic_takes_the_chunks_grouped_product_at_its_tiling(compiled,
                                                               cell, product):
    """gmm at the tiling a served chunk of 2,048 positions takes, the
    whole contraction: gmm's `pallas_call` passes no limit, so Mosaic
    holds its blocks to the scoped default and refuses more."""
    k, held, d, f = GMM_SHAPES[cell]
    m = 2048 * k
    kk, n = (d, f) if product == "gate_up" else (f, d)
    tiling = moe.grouped_tiling(m, held, kk, n)
    assert moe.chunk_form(m, held) and tiling[1] == kk
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    exe = compiled(lambda a, w, s: moe.grouped_dot(a, w, s, tiling), (),
                   bf(m, kk), bf(held, kk, n),
                   jax.ShapeDtypeStruct((held,), jnp.int32))
    assert exe.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("setting,named", [
    (SPARSE, ("prefill_attention_full", "prefill_attention_sliding")),
    (LATENT, ("prefill_attention_latent",)),
    (LAGUNA, ("prefill_attention_full", "prefill_attention_sliding"))],
    ids=["sparse", "latent", "heads-by-kind"])
def test_the_chunk_holds_its_kernels_under_their_names(compiled, setting,
                                                       named):
    """The readers of `kernels.prefill_attention_*_device_ms` find each
    kernel by the name its call carries inside `_prefill_pure`."""
    m, params, arenas = _shapes(setting)
    ops = _host_operands(setting, m, "prefill")
    text = compiled(m._prefill_pure, (4, 10), params, *ops[:3], arenas,
                    *ops[3:]).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name in named:
        assert any(name in ln for ln in calls), name


@pytest.mark.parametrize("setting", [SPARSE, LATENT, LAGUNA, DENSE],
                         ids=["sparse", "latent", "heads-by-kind", "dense"])
def test_the_key_block_counters_count_what_the_kernel_is_handed(
        monkeypatch, setting):
    """`TransformerAdapter._chunk_work` (host arithmetic on block ends)
    against `key_block_classes` on the arrays `_attend_chunk` builds,
    position by position, for the parts that take the kernel."""
    monkeypatch.setattr(fa, "flash_attention_available", lambda: True)
    m = TransformerDecoder(params={}, **setting["model"])
    ad, tiny = _adapter(setting, m)
    pb = ad.pack_bucket
    if setting is DENSE:        # prompts under the kernel's floor of keys
        assert ad._chunk_work(np.ones(pb, np.int32), 0, {})[:2] == (0, 0)
        return
    line = np.arange(pb, dtype=np.int32)
    for seg, ctx_len, start in (
            (np.ones(pb, np.int32), 0, 0),
            (np.ones(pb, np.int32), 4096, 0),           # whole slabs
            (np.ones(pb, np.int32), 6000, 4864),        # a partly real one
            (np.where(line < 700, 1, np.where(line < 1900, 2, 0)), 0, 0),
            (np.where(line < 1234, 1, 0).astype(np.int32), 2048, 1024)):
        starts = {k: np.int32(start) for k in tiny.kinds if k == "sliding"}
        ran = whole = 0
        for li in range(m.n_layers):
            kind = m.kind_of(li)
            window = m.window if kind == "sliding" else None
            n, first = ad._slab_tokens[kind], int(starts.get(kind, 0))
            true = first + np.arange(max(0, -(-(ctx_len - first) // n)) * n)
            real = true < ctx_len
            parts = [(line, seg)]
            if true.size:
                parts.append((np.where(real, true - ctx_len, 1 << 30),
                              np.where(real, 1, -1)))
            for kv_pos, kv_seg in parts:
                blocks = fa.prefill_kernel_blocks(
                    pb, min(kv_pos.size, n) if kv_pos is not line else pb,
                    m.head_dim, m.v_dim if kind == "latent" else m.head_dim)
                if blocks is None:
                    continue
                table = fa.key_block_classes(line, kv_pos, seg, kv_seg,
                                             window, *blocks[:2])
                ran += int((table != fa.KEY_SKIPPED).sum())
                whole += int((table == fa.KEY_WHOLE).sum())
        assert ad._chunk_work(seg, ctx_len, starts)[:2] == (ran, whole)
        # a chunk of one segment has whole blocks under its diagonal, a
        # packed one may have none
        assert whole < ran and (whole > 0 or seg.min() != 1)


def test_off_a_tpu_no_part_takes_the_kernel_and_the_counters_stay_0():
    m = TransformerDecoder(params={}, **LATENT["model"])
    ad, _ = _adapter(LATENT, m)
    assert ad._kernel_blocks == {"latent": (None, None)}
    assert ad._chunk_work(np.ones(2048, np.int32), 8192, {})[:2] == (0, 0)
