"""The decode step and the packed prefill compiled for a described TPU
v5e at the benchmark's widths (two layers): no chip is attached and
nothing runs. What only the chip's compiler shows: the donated arenas
alias their outputs, no executable re-lays an arena out (with the layer
as a window axis of the scatter, or heads and head_dim as two trailing
axes, it copied all of it on every step), and the Pallas kernel is in.
One file, the topology described in a fixture: see the
on-chip-measurement guide, section 2."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.serving.decode import (PagedKVCache,
                                               TransformerDecoder)

LAYERS, HEADS, HEAD_DIM, FF, VOCAB = 2, 32, 64, 8192, 50272
BLOCKS, BT, ROWS, KV, PACK = 256, 16, 8, 128, 128


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


def _shapes():
    """The parameter tree and an arena at the benchmark's widths, as
    shapes: a tiny model's tree with each of its sizes put up (the
    constructor draws its weights on the host)."""
    up = {4: HEADS * HEAD_DIM, 6: FF, 3: VOCAB}
    tiny = TransformerDecoder(vocab=3, layers=LAYERS, heads=2, head_dim=2,
                              ff=6, max_context=8)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(tuple(up[d] for d in a.shape),
                                       a.dtype), tiny.params_tree)
    arena = jax.eval_shape(lambda: PagedKVCache(
        layers=LAYERS, heads=HEADS, head_dim=HEAD_DIM, block_tokens=BT,
        max_blocks=BLOCKS).arenas()[0])
    return params, arena


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_the_arena_is_updated_where_it_lies(compiled, which):
    params, arena = _shapes()
    m = TransformerDecoder(vocab=2, layers=0, heads=HEADS,
                           head_dim=HEAD_DIM, ff=FF, max_context=255)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if which == "step":
        exe = compiled(m._step_pure, (3, 4), params, i32(ROWS), i32(ROWS),
                       arena, arena, i32(ROWS, KV // BT), i32(ROWS))
    else:
        exe = compiled(m._prefill_pure, (4, 5), params, i32(1, PACK),
                       i32(1, PACK), i32(1, PACK), arena, arena, i32(PACK),
                       i32(PACK), i32(PACK))
    text = exe.as_text()
    alias = re.search(r"input_output_alias=\{([^\n]*?)\}, entry", text)
    assert alias and alias.group(1).count("may-alias") + \
        alias.group(1).count("must-alias") == 2, "an arena is not donated"
    arena_bytes = int(np.prod(arena.shape)) * 4
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes, \
        "the executable holds a copy of an arena"
    dims = ",".join(str(d) for d in arena.shape)
    layouts = set(re.findall(r"f32\[%s\]\{([\d,]+)" % dims, text))
    assert layouts == {"3,2,1,0"}, f"an arena is re-laid out: {layouts}"
    assert text.count("tpu_custom_call") == \
        (LAYERS if which == "step" else 0)
