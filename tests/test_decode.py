"""Decode-plane tests (docs/serving.md §decode): paged KV cache
arithmetic, paged_decode_attention parity, adapter packing/validation, the
typed rnn_time_step state-reset contract, and (slow) engine end-to-end parity / chaos isolation."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (LSTM, ComputationGraph, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                RnnOutputLayer, Sgd)
from deeplearning4j_tpu.data.padding import next_pow2_bucket
from deeplearning4j_tpu.nn.multilayer import RnnStateMismatchError
from deeplearning4j_tpu.ops.flash_attention import paged_decode_attention
from deeplearning4j_tpu.optimize.telemetry import CompilationTracker
from deeplearning4j_tpu.optimize.metrics import registry
from deeplearning4j_tpu.parallel.inference import (DecodeStepError,
                                                   KVCacheExhaustedError,
                                                   NonFiniteOutputError)
from deeplearning4j_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                               RecurrentAdapter,
                                               TransformerAdapter,
                                               TransformerDecoder,
                                               naive_generate)
from deeplearning4j_tpu.utils import faults


def _cache(**kw):
    kw.setdefault("layers", 2)
    kw.setdefault("heads", 2)
    kw.setdefault("head_dim", 4)
    return PagedKVCache(**kw)


def _decoder(layers=2, heads=2, head_dim=4, **kw):
    kw.setdefault("vocab", 32)
    kw.setdefault("ff", 16)
    kw.setdefault("max_context", 64)
    return TransformerDecoder(layers=layers, heads=heads, head_dim=head_dim,
                              **kw)


def _arenas(cache):
    return tuple(np.asarray(a) for a in cache.arenas()["full"])


def _own(ad, launch, *args):
    """A launch's own outcome through the adapter's two halves: launch
    it with nothing in flight, then fetch and commit it."""
    earlier, fails = launch(*args)
    assert not earlier
    out, late = ad.collect()
    return out, {**fails, **late}


def _step(ad, rids):
    return _own(ad, ad.step, rids)


def _prefill(ad, group):
    return _own(ad, ad.prefill_group, group)


def _view(cache, *args):
    """A one-kind cache's step view: (the full table, lengths, starved)."""
    tables, starts, lens, starved = cache.batch_view(*args)
    assert list(tables) == ["full"] and not starts
    return tables["full"], lens, starved


class TestPagedKVCache:
    def test_block_arithmetic(self):
        c = _cache(block_tokens=16, max_blocks=8)
        assert c.block_tokens == 16
        assert c.blocks_needed(1) == 1
        assert c.blocks_needed(16) == 1
        assert c.blocks_needed(17) == 2
        # non-pow2 request is snapped through the ONE bucket rule
        assert _cache(block_tokens=12, max_blocks=2).block_tokens == 16
        # one block past max_blocks that no request can own; a token's
        # heads lie side by side on the last axis
        assert c.scratch_of["full"] == 8
        assert [a.shape for a in c.arenas()["full"]] == [(2, 9, 16, 8)] * 2

    def test_prefill_and_step_scatter_equal_a_numpy_model_of_the_arena(self):
        """The device arena after a packed prefill and three steps, and the
        view a step attends over, against the K/V the plain forward
        computes for the same tokens, laid out by the block tables."""
        m = _decoder()
        c = _cache(block_tokens=4, max_blocks=8)
        ad = TransformerAdapter(m, c, pack_bucket=16)
        rng = np.random.default_rng(0)
        prompts = {7: rng.integers(0, 32, 6), 9: rng.integers(0, 32, 3)}
        first, fails = _prefill(ad, list(prompts.items()))
        assert not fails and c.length(7) == 6 and c.blocks_of(7) == 2
        seqs = {r: list(p) + [first[r]] for r, p in prompts.items()}
        for _ in range(3):       # rid 7's third step opens its third block
            out, fails = _step(ad, [7, 9])
            assert not fails
            for r in seqs:
                seqs[r].append(out[r])
        assert c.length(7) == 9 and c.blocks_of(7) == 3
        assert c.length(9) == 6 and c.blocks_of(9) == 2
        got_k, got_v = _arenas(c)
        want_k, want_v = np.zeros_like(got_k), np.zeros_like(got_v)
        for r, seq in seqs.items():
            n = c.length(r)
            row = np.zeros((1, 16), np.int32)
            seg = np.zeros((1, 16), np.int32)
            pos = np.zeros((1, 16), np.int32)
            row[0, :n], seg[0, :n], pos[0, :n] = seq[:n], 1, np.arange(n)
            _, new, _ = m._chunk_forward(m.params_tree, row[0], seg[0],
                                         pos[0])
            ks, vs = (np.stack(a) for a in new["full"])
            tables, lens, starved = _view(c, [r], 16)
            assert lens.tolist() == [n] and not starved
            for t in range(n):
                blk, off = tables[0, t // 4], t % 4
                want_k[:, blk, off] = np.asarray(ks)[:, t]
                want_v[:, blk, off] = np.asarray(vs)[:, t]
        owned = sorted(b for r in seqs for b in _view(c, [r], 16)[0][0]
                       if b != c.scratch_of["full"])
        assert len(owned) == 5
        # two executables of different shape agree to rounding, not bitwise
        np.testing.assert_allclose(got_k[:, owned], want_k[:, owned],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got_v[:, owned], want_v[:, owned],
                                   rtol=1e-5, atol=1e-6)
        free = [b for b in range(8) if b not in owned]
        assert not got_k[:, free].any() and not got_v[:, free].any()

    def test_exhaustion_is_all_or_nothing(self):
        c = _cache(block_tokens=4, max_blocks=2)
        with pytest.raises(KVCacheExhaustedError):
            c.reserve(1, 12)  # needs 3 > 2 blocks
        assert c.blocks_in_use() == 0 and c.length(1) == 0
        # a failed GROW leaves the existing table intact, names the rid,
        # and its row rides the step as a pad row
        blk, off = c.reserve(2, 8)["full"]
        assert blk.tolist() == [blk[0]] * 4 + [blk[4]] * 4
        assert off.tolist() == [0, 1, 2, 3] * 2
        c.advance(2, 8)
        assert c.free_blocks() == 0
        tables, lens, starved = _view(c, [2], 16)
        assert isinstance(starved[2], KVCacheExhaustedError)
        assert c.length(2) == 8 and c.blocks_of(2) == 2
        assert (tables == c.scratch_of["full"]).all() and lens.tolist() == [0]
        with pytest.raises(ValueError):
            c.reserve(2, 1)  # already cached

    def test_free_is_idempotent(self):
        c = _cache(block_tokens=4, max_blocks=4)
        c.reserve(3, 5)
        assert c.blocks_in_use() == 2
        c.free(3)
        c.free(3)  # second free is a no-op, not a double-return
        assert c.blocks_in_use() == 0 and c.free_blocks() == 4

    def test_batch_view_is_a_table_whose_width_divides_the_bucket(self):
        c = _cache(block_tokens=4, max_blocks=4)
        c.reserve(1, 2)
        c.advance(1, 2)
        with pytest.raises(ValueError):
            _view(c, [1], 6)
        tables, lens, starved = _view(c, [1], 8, 2)
        assert tables.dtype == lens.dtype == np.int32 and not starved
        assert tables.shape == (2, 2) and lens.tolist() == [2, 0]
        # entries past a row's own blocks, and pad rows, are the scratch
        assert tables[0, 0] != c.scratch_of["full"]
        assert tables[0, 1] == tables[1, 0] == tables[1, 1] == c.scratch_of["full"]


class TestDecodeAttention:
    @pytest.mark.parametrize("tk", [8, 16])
    def test_matches_masked_softmax_reference(self, tk):
        """A row's cached keys, scattered over the arena's blocks, and its
        own new token, against a numpy softmax over them in order."""
        rng = np.random.default_rng(1)
        b, h, d, bt = 3, 2, 8, 4
        q = rng.standard_normal((b, h, d)).astype(np.float32)
        k = rng.standard_normal((b, tk + 1, h, d)).astype(np.float32)
        v = rng.standard_normal((b, tk + 1, h, d)).astype(np.float32)
        lens = np.array([0, tk // 2, tk], np.int32)   # cached, own apart
        w = tk // bt
        tables = rng.permutation(b * w).astype(np.int32).reshape(b, w)
        arena_k = np.full((1, b * w + 1, bt, h * d), np.nan, np.float32)
        arena_v = np.full_like(arena_k, np.nan)
        for i in range(b):
            for t in range(lens[i]):
                arena_k[0, tables[i, t // bt], t % bt] = k[i, t].ravel()
                arena_v[0, tables[i, t // bt], t % bt] = v[i, t].ravel()
        new = lambda a: np.stack([a[i, lens[i]].ravel() for i in range(b)])
        out = np.asarray(paged_decode_attention(
            q, new(k), new(v), arena_k, arena_v, 0, tables,
            np.zeros((b,), np.int32), lens))
        assert out.shape == (b, h, d) and np.isfinite(out).all()
        for i in range(b):
            n = lens[i] + 1
            for hh in range(h):
                s = q[i, hh] @ k[i, :n, hh].T / np.sqrt(d)
                p = np.exp(s - s.max())
                p /= p.sum()
                np.testing.assert_allclose(out[i, hh], p @ v[i, :n, hh],
                                           rtol=1e-4, atol=1e-5)

    def test_rejects_heads_that_do_not_share_the_arenas_row(self):
        z = np.zeros((1, 3, 4), np.float32)         # 3 heads over 2 KV heads
        arena = np.zeros((1, 2, 4, 8), np.float32)
        with pytest.raises(ValueError):
            paged_decode_attention(z, z[:, 0, :].repeat(2, -1),
                                   z[:, 0, :].repeat(2, -1), arena, arena, 0,
                                   np.zeros((1, 1), np.int32),
                                   np.zeros(1, np.int32),
                                   np.ones(1, np.int32))


def _stream_net(n_in=4, seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(LSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=n_in, activation="identity",
                                  loss="mse"))
            .set_input_type(InputType.recurrent(n_in)).build())
    return MultiLayerNetwork(conf).init()


class TestRnnStateReset:
    def test_mismatch_is_typed_and_resets_mln(self):
        net = _stream_net()
        rng = np.random.default_rng(0)
        net.rnn_time_step(rng.standard_normal((2, 4)).astype(np.float32))
        assert net._rnn_carry is not None
        with pytest.raises(RnnStateMismatchError):
            net.rnn_time_step(rng.standard_normal((3, 4)).astype(np.float32))
        # the stale carry is GONE: the next caller starts clean instead
        # of inheriting the poisoned state (the pre-fix behaviour)
        assert net._rnn_carry is None
        x = rng.standard_normal((3, 4)).astype(np.float32)
        fresh = _stream_net()
        np.testing.assert_allclose(net.rnn_time_step(x),
                                   fresh.rnn_time_step(x), rtol=1e-6)

    def test_mismatch_is_typed_and_resets_graph(self):
        conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
                .graph_builder()
                .add_inputs("in")
                .add_layer("lstm", LSTM(n_out=6, activation="tanh"), "in")
                .add_layer("out", RnnOutputLayer(n_out=4,
                                                 activation="identity",
                                                 loss="mse"), "lstm")
                .set_outputs("out")
                .set_input_types(InputType.recurrent(4)).build())
        g = ComputationGraph(conf).init()
        rng = np.random.default_rng(1)
        g.rnn_time_step(rng.standard_normal((2, 4)).astype(np.float32))
        with pytest.raises(RnnStateMismatchError):
            g.rnn_time_step(rng.standard_normal((5, 4)).astype(np.float32))
        assert g._rnn_carry is None
        g.rnn_time_step(rng.standard_normal((5, 4)).astype(np.float32))

    def test_is_a_value_error(self):
        # gateway maps ValueError -> 400; the typed subclass must ride it
        assert issubclass(RnnStateMismatchError, ValueError)


class TestTransformerAdapter:
    def _adapter(self, pack_bucket=16, **cache_kw):
        model = _decoder(layers=1)
        cache_kw.setdefault("block_tokens", 4)
        cache_kw.setdefault("max_blocks", 32)
        cache = PagedKVCache(layers=1, heads=2, head_dim=4, **cache_kw)
        return TransformerAdapter(model, cache, pack_bucket=pack_bucket)

    def test_validate_prompt(self):
        a = self._adapter()
        np.testing.assert_array_equal(a.validate_prompt([1, 2, 3]),
                                      np.array([1, 2, 3], np.int32))
        for bad in ([], [[1, 2]], [5, 99], [-1, 2]):
            with pytest.raises(ValueError):
                a.validate_prompt(bad)
        # longer than a chunk is no fault: it is prefilled in slices
        long = a.validate_prompt(list(range(17)))
        assert [(lo, p.size, final) for _, p, lo, final in
                sum(a.pack_groups([(0, long)]), [])] == \
            [(0, 16, False), (16, 1, True)]

    def test_pack_groups_first_fit(self):
        a = self._adapter(pack_bucket=16)
        items = [(i, np.zeros(n, np.int32))
                 for i, n in enumerate([10, 7, 5, 16, 1])]
        groups = a.pack_groups(items)
        packed = sorted(r for g in groups for r, *_ in g)
        assert packed == [0, 1, 2, 3, 4]  # nobody dropped
        for g in groups:
            assert sum(p.size for _, p, *_ in g) <= 16
        # 10+5+1 share a row, 7 and 16 ride alone -> 3 rows, not 5
        assert len(groups) == 3


# ---------------------------------------------------------------------------
# The device arena under the adapter and the engine
# ---------------------------------------------------------------------------
BT, PACK = 8, 32


def _adapter(max_blocks=32):
    model = TransformerDecoder(vocab=64, layers=2, heads=2, head_dim=8,
                               ff=32, max_context=64, seed=0)
    cache = PagedKVCache(layers=2, heads=2, head_dim=8, block_tokens=BT,
                         max_blocks=max_blocks)
    return TransformerAdapter(model, cache, pack_bucket=PACK)


def _prompts(lens, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, n).astype(np.int32) for n in lens]


def _link(way, phase):
    return registry().counter(
        f"serving_decode_{way}_bytes_total").labels(phase=phase).value()


def _changed_slots(before, after):
    """(block, offset) pairs at which either arena differs."""
    diff = np.zeros(before[0].shape[1:3], bool)
    for b, a in zip(before, after):
        diff |= (b != a).any(axis=(0, 3))
    return {(int(i), int(j)) for i, j in zip(*np.nonzero(diff))}


@pytest.mark.parametrize("what", ["warmup", "swap_warm", "pad_rows"])
def test_pad_rows_and_warm_up_write_only_the_scratch_block(what):
    ad = _adapter()
    c = ad.cache
    ps = _prompts((5, 9, 10, 7))
    first, fails = _prefill(ad, list(enumerate(ps)))
    assert not fails
    before = _arenas(c)
    in_use = c.blocks_in_use()
    may = {(c.scratch_of["full"], off) for off in range(BT)}
    if what == "warmup":
        ad.warmup(4, 64)
    elif what == "swap_warm":
        with DecodeEngine(ad, max_decode_batch=4) as eng:
            eng.swap_warm(3)
    else:
        # three riders in a bucket of four: one pad row; request 3 sits out
        out, fails = _step(ad, [0, 1, 2])
        assert not fails and sorted(out) == [0, 1, 2]
        for r in (0, 1, 2):
            tables, lens, _ = _view(c, [r], 16)
            n = int(lens[0]) - 1           # the slot this step filled
            may.add((int(tables[0, n // BT]), n % BT))
    changed = _changed_slots(before, _arenas(c))
    assert changed <= may
    assert (what == "pad_rows") == bool(changed - {(c.scratch_of["full"], 0)})
    assert c.blocks_in_use() == in_use     # the scratch block is not counted


def test_a_non_finite_row_fails_alone_and_its_blocks_poison_no_one():
    def serve(poison):
        ad = _adapter()
        c = ad.cache
        ps = _prompts((6, 11, 4))
        first, fails = _prefill(ad, list(enumerate(ps)))
        assert not fails
        if poison:
            victim = _view(c, [0], 8)[0][0, 0]
            c.update(lambda a: ({"full": (
                a["full"][0].at[:, victim].set(np.nan), a["full"][1])},))
        toks = {r: [first[r]] for r in first}
        errs = {}
        for _ in range(3):
            live = [r for r in toks if r not in errs]
            out, fails = _step(ad, live)
            for r, e in fails.items():
                errs[r] = e
                ad.free(r)                 # as the engine does
            for r, t in out.items():
                toks[r].append(t)
        return ad, toks, errs

    _, clean, errs = serve(False)
    assert not errs
    ad, got, errs = serve(True)
    assert list(errs) == [0] and isinstance(errs[0], NonFiniteOutputError)
    assert got[1] == clean[1] and got[2] == clean[2]
    # the next owner of the freed blocks attends over its own tokens only
    newcomer = _prompts((6,), seed=8)[0]
    first, fails = _prefill(ad, [(9, newcomer)])
    assert not fails
    out, fails = _step(ad, [9])
    assert not fails
    assert [first[9], out[9]] == naive_generate(ad.model, newcomer, 2,
                                                pad_to=PACK)


def test_a_step_run_again_at_the_same_lengths_rewrites_the_same_slots():
    """What a solo retry relies on: the scatter goes where the lengths
    point and the token comes from where the slots point, and lengths
    and feed move only with a launch that went through."""
    ad = _adapter()
    c, m = ad.cache, ad.model
    first, _ = _prefill(ad, list(enumerate(_prompts((6, 8)))))
    tables, lens, _ = _view(c, [0, 1], 16)   # request 1 grows here
    slot = np.asarray([ad._slot_of[0], ad._slot_of[1]], np.int32)
    feed = np.asarray(ad._feed)
    assert feed[slot].tolist() == [first[0], first[1]]
    runs = []
    for _ in range(2):
        picked, finite, _, after = c.update(lambda a: m.step(
            slot, lens, a, {"full": tables}, {}, lens, jnp.asarray(feed)))
        assert np.asarray(after)[slot].tolist() == \
            np.asarray(picked).tolist()
        runs.append((np.asarray(picked).tolist(), _arenas(c)))
    assert runs[0][0] == runs[1][0]
    assert not _changed_slots(runs[0][1], runs[1][1])
    assert c.length(0) == 6 and c.length(1) == 8


@pytest.mark.parametrize("riders", [1, 3])
def test_link_bytes_are_the_formula_over_the_shapes(riders):
    ad = _adapter()
    ps = _prompts((5, 9, 12)[:riders])
    h0, d0 = _link("h2d", "prefill"), _link("d2h", "prefill")
    first, _ = _prefill(ad, list(enumerate(ps)))
    # the packed row, its segments and positions; a block, an offset, a
    # last position and a row slot a slot; the context's table and its
    # length. Back: an int32 token and a flag a slot
    assert _link("h2d", "prefill") - h0 == \
        (7 * PACK + ad._ctx_widths["full"] + 1) * 4
    assert _link("d2h", "prefill") - d0 == PACK * 5
    h0, d0 = _link("h2d", "step"), _link("d2h", "step")
    rids = list(range(riders))
    rows, width = next_pow2_bucket(riders), ad.kv_bucket(rids) // BT
    ad.step(rids)
    # row slots, positions, lengths and `width` table entries a row go
    # up with the launch; the tokens come down with the fetch
    assert _link("h2d", "step") - h0 == rows * (3 + width) * 4
    assert _link("d2h", "step") - d0 == 0
    ad.collect()
    assert _link("d2h", "step") - d0 == rows * 5


@pytest.mark.parametrize("which", ["step", "prefill"])
def test_the_arenas_are_donated_and_the_modules_keep_their_names(which):
    ad = _adapter(max_blocks=4)
    c, m = ad.cache, ad.model
    arenas = c.arenas()
    k, v = arenas["full"]
    i32 = lambda *shape: np.zeros(shape, np.int32)
    feed = ad._feed
    if which == "step":
        tables, starts, lens, _ = c.batch_view((), 16, 2)
        args = (lens, lens, arenas, tables, starts, lens, feed)
        lowered = m._step_fn.lower(m.params_tree, *args)
        out = m.step(*args)
    else:
        scratch = c.scratch_of["full"]
        args = (i32(PACK), i32(PACK), i32(PACK), arenas,
                {"full": (np.full((PACK,), scratch, np.int32), i32(PACK))},
                {"full": np.full((ad._ctx_widths["full"],), scratch,
                                 np.int32)}, {}, np.int32(0), i32(PACK),
                feed, i32(PACK))
        lowered = m._prefill_fn.lower(m.params_tree, *args)
        out = m.prefill(*args)
    # the benchmark's device metrics find the runs by these names
    assert f"module @jit__{which}_pure" in lowered.as_text()
    assert "input_output_alias" in lowered.compile().as_text()
    assert k.is_deleted() and v.is_deleted() and feed.is_deleted()
    assert [a.shape for a in out[-1]["full"]] == [k.shape, v.shape]
    assert out[-2].shape == feed.shape and out[-2].dtype == np.int32
    assert out[0].dtype == np.int32 and out[1].dtype == np.bool_


MIXED = (3, 9, 17, 5, 15, 16)   # 20 new tokens cross blocks of 8 and the
NEW = 20                        # KV buckets 8, 16 and 32


@pytest.fixture(scope="module")
def traffic():
    """One warmed engine of four rows: six concurrent requests, then a
    pair under `test_chaos_step_isolation`'s fault."""
    ad = _adapter(max_blocks=64)
    prompts = [p.tolist() for p in _prompts(MIXED, seed=2)]

    def ask(eng, pool, out):
        def run(i):
            try:
                out[i] = eng.generate(pool[i], max_new_tokens=NEW)
            except DecodeStepError as e:
                out[i] = e
        ts = [threading.Thread(target=run, args=(i,))
              for i in range(len(pool))]
        # All are in before any runs. The loop takes its admits off the
        # queue before it asks for the step lock, so the first taken is
        # in neither count while the pause holds it.
        with eng.paused():
            for t in ts:
                t.start()
            deadline = time.monotonic() + 30.0
            while eng.queue_depth() < len(pool) - 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
        for t in ts:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in ts)

    with DecodeEngine(ad, max_decode_batch=4) as eng:
        eng.warmup()
        served = [None] * len(prompts)
        with CompilationTracker() as trk:
            ask(eng, prompts, served)
            compiles = trk.count
        left = ad.cache.blocks_in_use()
        chaos = [None] * 2
        # fail:3,4 = the batch attempt + the FIRST solo retry
        with faults.injected("serve.decode_step", "fail:3,4"):
            ask(eng, prompts[1:3], chaos)
    want = [naive_generate(ad.model, p, NEW, pad_to=64) for p in prompts]
    return dict(served=served, want=want, compiles=compiles, left=left,
                chaos=chaos, left_after_chaos=ad.cache.blocks_in_use())


@pytest.mark.parametrize("i", range(len(MIXED)),
                         ids=[f"prompt{n}" for n in MIXED])
def test_engine_tokens_equal_naive_generate(traffic, i):
    assert traffic["served"][i] == traffic["want"][i]


def test_no_compilation_after_warmup_and_the_blocks_drain(traffic):
    assert traffic["compiles"] == 0, "steady-state decode recompiled"
    assert traffic["left"] == 0 and traffic["left_after_chaos"] == 0


def test_survivors_of_a_failed_batch_step_serve_the_clean_runs_tokens(
        traffic):
    """The batch step fails, then the first solo retry: one rider dies
    typed; the other re-steps alone into the slot it would have written
    and goes on to serve every token of the clean run."""
    died = [o for o in traffic["chaos"] if isinstance(o, DecodeStepError)]
    lived = [(i, o) for i, o in enumerate(traffic["chaos"])
             if isinstance(o, list)]
    assert len(died) == 1 and len(lived) == 1
    i, tokens = lived[0]
    assert tokens == traffic["want"][1 + i]


# ---------------------------------------------------------------------------
# Heavy end-to-end: engine parity, zero-compile steady state, chaos
# ---------------------------------------------------------------------------
def _engine(max_decode_batch=4, kv_max_blocks=64):
    model = TransformerDecoder(vocab=64, layers=2, heads=2, head_dim=8,
                               ff=32, max_context=64, seed=0)
    cache = PagedKVCache(layers=2, heads=2, head_dim=8, block_tokens=8,
                         max_blocks=kv_max_blocks)
    adapter = TransformerAdapter(model, cache, pack_bucket=32)
    eng = DecodeEngine(adapter, max_decode_batch=max_decode_batch)
    eng.warmup()
    return eng, model, cache


@pytest.mark.slow
class TestDecodeEngineE2E:
    def test_concurrent_parity_zero_compile_kv_drains(self):
        eng, model, cache = _engine()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 64, n).tolist() for n in (3, 9, 17, 5)]
        try:
            with CompilationTracker() as trk:
                results = [None] * len(prompts)

                def run(i):
                    results[i] = eng.generate(prompts[i], max_new_tokens=12)

                ts = [threading.Thread(target=run, args=(i,))
                      for i in range(len(prompts))]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            assert trk.count == 0, "steady-state decode recompiled"
            for p, got in zip(prompts, results):
                assert got == naive_generate(model, p, 12, pad_to=32)
            assert cache.blocks_in_use() == 0  # every retire freed
        finally:
            eng.shutdown()

    def test_chaos_step_isolation(self):
        # fail:3,4 = the batch attempt + the FIRST solo retry: exactly
        # one rider dies typed, its batchmate keeps generating, blocks
        # drain, and the engine still serves afterwards.
        eng, model, cache = _engine()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, 5).tolist(),
                   rng.integers(0, 64, 7).tolist()]
        outcomes = [None] * 2
        try:
            with faults.injected("serve.decode_step", "fail:3,4"):

                def run(i):
                    try:
                        outcomes[i] = eng.generate(prompts[i],
                                                   max_new_tokens=12)
                    except DecodeStepError as e:
                        outcomes[i] = e

                ts = [threading.Thread(target=run, args=(i,))
                      for i in range(2)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            died = [o for o in outcomes if isinstance(o, DecodeStepError)]
            lived = [o for o in outcomes if isinstance(o, list)]
            assert len(died) == 1 and len(lived) == 1
            assert len(lived[0]) == 12  # survivor got every token
            assert cache.blocks_in_use() == 0  # victim's KV freed too
            # engine survives the chaos window
            assert eng.generate(prompts[0], max_new_tokens=4) == \
                naive_generate(model, prompts[0], 4, pad_to=32)
        finally:
            eng.shutdown()

    def test_recurrent_engine_matches_direct_stream(self):
        net = _stream_net()
        adapter = RecurrentAdapter(net, feature_dim=4)
        eng = DecodeEngine(adapter, max_decode_batch=4)
        eng.warmup()
        rng = np.random.default_rng(4)
        prompt = rng.standard_normal((3, 4)).astype(np.float32)
        try:
            got = np.asarray(eng.generate(prompt, max_new_tokens=5))
            ref_net = _stream_net()
            x = prompt
            ref = []
            for t in range(prompt.shape[0]):
                last = ref_net.rnn_time_step(x[t][None, :])[0]
            for _ in range(5):
                ref.append(last)
                last = ref_net.rnn_time_step(last[None, :])[0]
            np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5,
                                       atol=1e-6)
        finally:
            eng.shutdown()
