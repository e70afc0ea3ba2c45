"""Autoregressive decode serving: iteration-level continuous batching
over a paged KV cache (docs/serving.md §decode).

Every other serving path in the repo is one-shot: a request is one
forward. Generative decode is a LOOP — a prompt is prefilled once, then
the model produces one token per step until done — and batching it
naively (pad every request to the longest sequence, drain the batch to
admit a newcomer) wastes device time quadratically in sequence spread.
This module implements the two techniques that fix that:

* **Iteration-level scheduling** (Orca, OSDI '22): the decode batch is
  re-formed BETWEEN steps. A finished request leaves immediately; a
  queued prompt joins at the next step boundary after its prefill — no
  draining, no padding to the longest batchmate. The
  :class:`DecodeEngine` loop owns this.

* **Block-paged KV cache** (PagedAttention, SOSP '23): per-request KV
  state lives in fixed-size token blocks from one preallocated arena
  ON THE DEVICE (:class:`PagedKVCache`), so memory scales with
  tokens-in-flight, not ``max_seq × batch``. The arena is an operand of
  the step and prefill executables, donated and updated in place: a
  step sends a block table and the lengths, never K/V. Block size is
  forced through the shared ``next_pow2_bucket`` rule
  (data/padding.py) — the same rule buckets the decode-step row count
  and the KV view length, so the set of compiled step executables is
  the finite grid ``{row buckets} × {table widths}`` and a warmed
  engine compiles NOTHING at steady state.

Two model families decode through the one engine:

* :class:`TransformerAdapter` wraps :class:`TransformerDecoder` (a
  self-contained causal LM): prompts are admitted through the packed
  path — ragged prompts spliced into ONE segment-masked ``[1,
  pack_bucket]`` prefill row (PR 12's segment semantics; causal masking
  inside the packed row stays exact under global positions + segment
  equality) — and each step runs single-query-row attention against the
  paged cache via ``ops.flash_attention.decode_attention``.
* :class:`RecurrentAdapter` wraps a streaming ``MultiLayerNetwork``
  (``rnn_time_step``): per-request carry rows are gathered into a
  pow2-bucketed batch each step, so an LSTM generates through the same
  iteration-level loop with the same typed failure semantics.

WFQ tiers apply PER STEP: every decode step (and every prefill) holds
the shared DeviceScheduler slot, so a critical one-shot prompt preempts
a batch-tier generation between tokens. Gateway breaker / hot-swap /
canary semantics carry over unchanged (ModelPool.add_decode wires the
same hooks; swaps pause the loop via ``paused()`` between steps).

Chaos seam: each step attempt fires the ``serve.decode_step`` fault
point (utils/faults.py) before dispatch. A failing step is isolated by
solo retry — only requests whose SOLO step also fails get a typed
:class:`~..parallel.inference.DecodeStepError`, their KV blocks are
freed, and batchmates keep generating on the next step.
"""
from __future__ import annotations

import collections
import contextlib
import math
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..data.padding import next_pow2_bucket
from ..ops.attention import dense_attention
from ..ops.flash_attention import decode_attention
from ..optimize import tracing
from ..optimize.metrics import registry
from ..parallel.inference import (DeadlineExceededError, DecodeStepError,
                                  KVCacheExhaustedError, NonFiniteOutputError,
                                  QueueFullError, ServerClosedError)
from ..utils import faults

__all__ = ["PagedKVCache", "TransformerDecoder", "TransformerAdapter",
           "RecurrentAdapter", "DecodeEngine", "naive_generate",
           "register_metrics"]


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
# Inter-token gaps sit between sub-ms (hot step) and the step-queue tail.
INTER_TOKEN_BUCKETS_MS = (0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                          100.0, 250.0, 500.0, 1000.0)

_TOKENS_HELP = "Tokens generated by the decode loop"
_STEPS_HELP = "Iteration-level decode steps executed (all riders advance)"
_PREFILL_HELP = "Packed prefill forwards executed"
_ITL_HELP = ("Wall time between a request's consecutive tokens "
             "(step + between-step scheduling)")
_KV_BLOCKS_HELP = "KV cache blocks currently allocated"
_KV_UTIL_HELP = ("Fraction of allocated KV block capacity holding real "
                 "tokens (1.0 = no block-tail waste)")
_H2D_HELP = ("Bytes of the host arrays the decode adapters hand to the "
             "device (a step's tokens, positions, block table and lengths; "
             "a prefill's packed row and its cache slots; a stream's carry)")
_D2H_HELP = ("Bytes of the device outputs the decode adapters copy back "
             "to the host (picked tokens and finite flags; a stream's "
             "outputs and carry)")
_PHASES = ("step", "prefill")


def register_metrics() -> None:
    """Pre-register the decode/KV-cache families at 0 (bench --once
    pattern: a scrape must distinguish 'no decode traffic yet' from
    'families absent')."""
    reg = registry()
    reg.counter("serving_decode_tokens_total", _TOKENS_HELP)
    reg.counter("serving_decode_steps_total", _STEPS_HELP)
    reg.counter("serving_decode_prefills_total", _PREFILL_HELP)
    reg.histogram("serving_inter_token_ms", _ITL_HELP,
                  buckets=INTER_TOKEN_BUCKETS_MS)
    reg.gauge("serving_kv_blocks_in_use", _KV_BLOCKS_HELP)
    reg.gauge("serving_kv_utilization", _KV_UTIL_HELP)
    _register_link_metrics()


def _register_link_metrics() -> Dict[Tuple[str, str], Any]:
    """The link-byte counters' children by (`h2d`|`d2h`, phase): what
    each adapter increments as it hands arrays to the device and copies
    the outputs back. The counts follow from shapes alone, so they
    repeat exactly from run to run."""
    reg = registry()
    fams = {"h2d": reg.counter("serving_decode_h2d_bytes_total", _H2D_HELP),
            "d2h": reg.counter("serving_decode_d2h_bytes_total", _D2H_HELP)}
    return {(way, ph): fam.labels(phase=ph)
            for way, fam in fams.items() for ph in _PHASES}


def _nbytes(*arrays) -> int:
    """Bytes of the arrays, and of the leaves of any tree among them."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(arrays))


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------
class PagedKVCache:
    """Bucket-allocated KV arena on the device: fixed-size token blocks,
    per-request block tables, all-or-nothing grabs.

    One preallocated pair of device arrays ``[layers, max_blocks + 1,
    block_tokens, heads × head_dim]`` holds K and V for every in-flight
    request; a request owns ``ceil(len / block_tokens)`` blocks, so
    live memory scales with tokens-in-flight, never ``max_seq ×
    batch``. The arrays are operands of the decoder's step and prefill
    executables, which gather a row's view by its block table, scatter
    the new K/V in place (the operands are donated) and hand the arrays
    back: :meth:`update` lends them and takes the outputs.
    Layer-major with a token's heads side by side, so one layer's
    block is one contiguous ``block_tokens × (heads × head_dim)`` chunk
    for the gather and the last axis fills the device's lanes (a
    trailing ``head_dim`` of 64 would be laid out padded or
    transposed).

    Everything else is host integers and stays here: the free list, the
    tables, the lengths. A length advances only at :meth:`advance`,
    after the host has seen the step's outcome, so a step that is run
    again (a failed row, a solo retry) writes the same slot again.

    Block ``max_blocks`` (:attr:`scratch`) belongs to no request: pad
    rows, pad positions and warm-up calls point at it, so they can
    write nowhere else. ``max_blocks`` counts the blocks a request can
    own; ``blocks_in_use`` never counts the scratch block.
    ``block_tokens`` is forced through ``next_pow2_bucket`` — the SAME
    rule that buckets the step's view length, so every table width a
    growing request can need is already in the compiled set.

    Exhaustion is typed: an allocation that cannot be satisfied raises
    :class:`KVCacheExhaustedError` WITHOUT taking any blocks (the
    admission path requeues or sheds on it; a growth failure fails only
    the growing request). The bookkeeping is thread-safe under the one
    lock; the arrays belong to whoever holds the engine's step lock.
    """

    def __init__(self, *, layers: int, heads: int, head_dim: int,
                 block_tokens: int = 16, max_blocks: int = 256,
                 dtype=jnp.float32):
        self.block_tokens = next_pow2_bucket(block_tokens)
        self.max_blocks = int(max_blocks)
        self.scratch = self.max_blocks
        shape = (int(layers), self.max_blocks + 1, self.block_tokens,
                 int(heads) * int(head_dim))
        self._k = jnp.zeros(shape, dtype)
        self._v = jnp.zeros(shape, dtype)
        self._free: List[int] = list(range(self.max_blocks - 1, -1, -1))
        self._tables: Dict[int, List[int]] = {}
        self._lens: Dict[int, int] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ bookkeeping
    def blocks_needed(self, n_tokens: int) -> int:
        return max(1, -(-int(n_tokens) // self.block_tokens))

    def blocks_of(self, rid: int) -> int:
        with self._lock:
            return len(self._tables.get(rid, ()))

    def length(self, rid: int) -> int:
        with self._lock:
            return self._lens.get(rid, 0)

    def blocks_in_use(self) -> int:
        with self._lock:
            return self.max_blocks - len(self._free)

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def utilization(self) -> float:
        """Stored tokens / allocated block capacity (block-tail waste)."""
        with self._lock:
            used = self.max_blocks - len(self._free)
            if used == 0:
                return 0.0
            tokens = sum(self._lens.values())
            return tokens / float(used * self.block_tokens)

    def _grab_locked(self, n_blocks: int) -> List[int]:
        if n_blocks > len(self._free):
            raise KVCacheExhaustedError(
                f"KV cache needs {n_blocks} block(s), "
                f"{len(self._free)} free of {self.max_blocks} "
                f"(block_tokens={self.block_tokens})")
        return [self._free.pop() for _ in range(n_blocks)]

    def reserve(self, rid: int, n_tokens: int
                ) -> Tuple[np.ndarray, np.ndarray]:
        """Give `rid` the blocks of an `n_tokens` prompt and return where
        each position goes: ``(block, offset)``, int32 ``[n_tokens]``
        each, the prefill's scatter operands. The length stays 0 until
        :meth:`advance`. All-or-nothing: a raise leaves no blocks taken
        and no table entry."""
        with self._lock:
            if rid in self._tables:
                raise ValueError(f"rid {rid} already cached")
            table = self._grab_locked(self.blocks_needed(n_tokens))
            self._tables[rid] = table
            self._lens[rid] = 0
        at = np.arange(int(n_tokens))
        bt = self.block_tokens
        return (np.asarray(table, np.int32)[at // bt],
                (at % bt).astype(np.int32))

    def advance(self, rid: int, n_tokens: int = 1) -> None:
        """`rid`'s next `n_tokens` slots now hold what the device wrote
        there."""
        with self._lock:
            self._lens[rid] += int(n_tokens)

    def free(self, rid: int) -> None:
        """Return `rid`'s blocks to the free list (idempotent). What
        they held stays in the arena; a view masks it by length."""
        with self._lock:
            blocks = self._tables.pop(rid, None)
            self._lens.pop(rid, None)
            if blocks:
                self._free.extend(blocks)

    # ---------------------------------------------------------- the step's view
    def batch_view(self, rids: Sequence[int], bucket_t: int,
                   rows: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray,
                              Dict[int, BaseException]]:
        """Describe one decode step's view: the block table int32
        ``[rows, bucket_t / block_tokens]``, the valid lengths int32
        ``[rows]``, and the rids that could not be given room. A row
        whose tail block is full grows by one block here, before the
        launch; a failed grow takes nothing, leaves the row's table
        intact and names the rid in the third result. Such a row, every
        row past ``len(rids)`` and every table entry past a row's own
        blocks point at the scratch block, at length 0. ``bucket_t``
        must be a block multiple >= every row's length + 1 (room for
        the token the step is about to scatter in)."""
        bt = self.block_tokens
        if bucket_t % bt:
            raise ValueError(
                f"view bucket {bucket_t} not a multiple of "
                f"block_tokens {bt}")
        n = len(rids) if rows is None else int(rows)
        tables = np.full((n, bucket_t // bt), self.scratch, np.int32)
        lens = np.zeros((n,), np.int32)
        starved: Dict[int, BaseException] = {}
        with self._lock:
            for i, rid in enumerate(rids):
                table, ln = self._tables[rid], self._lens[rid]
                if ln >= len(table) * bt:
                    try:
                        table.extend(self._grab_locked(1))
                    except KVCacheExhaustedError as e:
                        starved[rid] = e
                        continue
                tables[i, :len(table)] = table
                lens[i] = ln
        return tables, lens, starved

    def arenas(self) -> Tuple[jax.Array, jax.Array]:
        """The K and V arrays as they stand, to read."""
        return self._k, self._v

    def update(self, fn: Callable) -> tuple:
        """Lend the arenas to ``fn(k, v) -> (*results, k, v)``, an
        executable that donates them, rebind to the two arrays it hands
        back, and return the results."""
        *results, self._k, self._v = fn(self._k, self._v)
        return tuple(results)


# ---------------------------------------------------------------------------
# Transformer arm: a self-contained causal LM
# ---------------------------------------------------------------------------
def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _sinusoid(pos, d_model):
    """Sinusoidal position encoding from int positions [..., t] →
    [..., t, d_model] (d_model even)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


class TransformerDecoder:
    """Minimal pre-LN causal transformer LM sized for serving tests and
    the decode bench: tied embedding/unembedding, sinusoidal positions
    computed from explicit position operands (so a packed prefill row
    and a mid-generation step agree on every token's encoding).

    Two jitted pure functions are the serving surface. Both take the
    paged cache's two arenas (layer-major
    ``[layers, blocks + 1, block_tokens, heads × head_dim]``) as DONATED
    operands, write the new K/V into them in place, pick the greedy
    token on the device and return ``(token, finite, arena_k,
    arena_v)`` — the cache rebinds to the two arrays
    (``PagedKVCache.update``), and only int32 tokens and bool flags
    cross the link:

    * ``prefill(tokens[1,T], seg[1,T], pos[1,T], arena_k, arena_v,
      blk[T], off[T], last[W])`` — one segment-masked packed row through
      ``dense_attention(causal=True, segment_ids=…)``. Padding is
      segment 0; packed prompts are 1..k. Position ``i``'s K/V go to
      ``(blk[i], off[i])`` (pads point at the scratch block); the pick
      and the finite flag are taken at the positions ``last`` (each
      prompt's last token, padded to a fixed width).
    * ``step(tok[b], pos[b], arena_k, arena_v, tables[b,w], lens[b])``
      — one token per row: each layer's view ``[b, w·block_tokens,
      heads, head_dim]`` is gathered from the arena by the block table,
      zero past ``lens``, with the new token's K/V at ``lens``;
      attention runs through ``decode_attention`` over ``lens+1``
      positions; the new K/V are scattered to ``(tables[row, lens //
      block_tokens], lens % block_tokens)``.

    ``logits(tokens, seg, pos)`` is the plain forward of the same
    packed row with no cache: what ``naive_generate``, the tests and
    chip_smoke.py compare the served path with.

    All close over static geometry only; params ride as an argument,
    so a hot-swap is a tree assignment, never a recompile.
    """

    def __init__(self, *, vocab: int = 128, layers: int = 2,
                 heads: int = 2, head_dim: int = 8, ff: int = 64,
                 max_context: int = 128, seed: int = 0):
        if head_dim * heads % 2:
            raise ValueError("d_model (heads*head_dim) must be even for "
                             "the sinusoidal position encoding")
        self.vocab, self.n_layers = int(vocab), int(layers)
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.d_model = self.heads * self.head_dim
        self.ff = int(ff)
        self.max_context = int(max_context)
        # Pool duck-compat: swap/describe read these on every entry.
        self.iteration = 0
        self.epoch = 0
        self.state_tree: Dict[str, Any] = {}
        rng = np.random.RandomState(seed)

        def w(*shape):
            return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.08)

        d, f = self.d_model, self.ff
        self.params_tree: Dict[str, Any] = {
            "emb": w(self.vocab, d),
            "lnf_s": jnp.ones((d,), jnp.float32),
            "lnf_b": jnp.zeros((d,), jnp.float32),
            "layers": [{
                "ln1_s": jnp.ones((d,), jnp.float32),
                "ln1_b": jnp.zeros((d,), jnp.float32),
                "wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
                "ln2_s": jnp.ones((d,), jnp.float32),
                "ln2_b": jnp.zeros((d,), jnp.float32),
                "w1": w(d, f), "b1": jnp.zeros((f,), jnp.float32),
                "w2": w(f, d), "b2": jnp.zeros((d,), jnp.float32),
            } for _ in range(self.n_layers)],
        }
        self._logits_fn = jax.jit(self._logits_pure)
        self._prefill_fn = jax.jit(self._prefill_pure, donate_argnums=(4, 5))
        self._step_fn = jax.jit(self._step_pure, donate_argnums=(3, 4))

    # ----------------------------------------------------------- pure forward
    def _embed(self, params, tok, pos):
        return params["emb"][tok] + _sinusoid(pos, self.d_model)

    def _mlp(self, h, lp):
        return jnp.maximum(h @ lp["w1"] + lp["b1"], 0.0) @ lp["w2"] \
            + lp["b2"]

    def _heads(self, a):  # [..., t, d_model] -> [..., t, H, Dh]
        return a.reshape(a.shape[:-1] + (self.heads, self.head_dim))

    def _head(self, params, x):
        """Final norm and tied unembedding: [..., d_model] → logits."""
        with jax.named_scope("head"):
            x = _layer_norm(x, params["lnf_s"], params["lnf_b"])
            return x @ params["emb"].T

    def _pick(self, params, x):
        """The greedy pick of x [n, d_model] → (first index of the
        largest logit int32 [n], all logits finite bool [n])."""
        logits = self._head(params, x)
        with jax.named_scope("head"):
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    jnp.isfinite(logits).all(axis=-1))

    @staticmethod
    def _scatter(arena_k, arena_v, blk, off, ks, vs):
        """ks/vs [layers, n, d_model] into slot (blk[i], off[i]) of each
        layer. The layer is an index like the other two, so the three
        indexed axes are the arena's leading ones and the update is a
        plain row write (with the layer as a window axis the compiler
        re-lays the whole arena out around the scatter)."""
        with jax.named_scope("kv_scatter"):
            layer = jnp.arange(ks.shape[0])[:, None]
            return (arena_k.at[layer, blk, off].set(ks),
                    arena_v.at[layer, blk, off].set(vs))

    def _packed_forward(self, params, tokens, seg, pos):
        """tokens/seg/pos [1, T] → (hidden [1, T, d_model] before the
        final norm, K and V [layers, T, d_model])."""
        with jax.named_scope("embed"):
            x = self._embed(params, tokens, pos)
        ks, vs = [], []
        for li, lp in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{li}/attn"):
                h = _layer_norm(x, lp["ln1_s"], lp["ln1_b"])
                q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
                # Causal within the packed row is exact under global
                # arange positions + segment equality
                # (ops/flash_attention.py docstring); pads are segment 0
                # and never read.
                a = dense_attention(self._heads(q), self._heads(k),
                                    self._heads(v), causal=True,
                                    segment_ids=seg)
                x = x + a.reshape(a.shape[:2] + (self.d_model,)) @ lp["wo"]
            with jax.named_scope(f"layer_{li}/mlp"):
                x = x + self._mlp(
                    _layer_norm(x, lp["ln2_s"], lp["ln2_b"]), lp)
            ks.append(k[0])
            vs.append(v[0])
        return x, jnp.stack(ks), jnp.stack(vs)

    def _logits_pure(self, params, tokens, seg, pos):
        """tokens/seg/pos [1, T] → logits [1, T, vocab]."""
        return self._head(
            params, self._packed_forward(params, tokens, seg, pos)[0])

    def _prefill_pure(self, params, tokens, seg, pos, arena_k, arena_v,
                      blk, off, last):
        """The packed row into the cache. blk/off [T]: the slot of each
        position; last [W]: the positions whose next token is wanted →
        (token int32 [W], finite bool [W], arena_k, arena_v)."""
        x, ks, vs = self._packed_forward(params, tokens, seg, pos)
        arena_k, arena_v = self._scatter(arena_k, arena_v, blk, off, ks, vs)
        tok, finite = self._pick(params, x[0, last])
        return tok, finite, arena_k, arena_v

    def _step_pure(self, params, tok, pos, arena_k, arena_v, tables, lens):
        """One decode step. tok/pos/lens [b]; tables [b, w] →
        (token int32 [b], finite bool [b], arena_k, arena_v)."""
        b, bt = tok.shape[0], arena_k.shape[2]
        kv = tables.shape[1] * bt
        at = jnp.arange(kv, dtype=jnp.int32)[None, :, None]
        is_new = at == lens[:, None, None]
        is_old = at < lens[:, None, None]

        def view(arena, li, new):
            # The row's blocks, then exactly the dense view a host copy
            # would build: real tokens below `lens`, the new one at it,
            # zero past it (a freed block keeps its last owner's data).
            got = arena[li, tables].reshape(b, kv, self.d_model)
            return self._heads(jnp.where(
                is_new, new[:, None], jnp.where(is_old, got, 0)))

        with jax.named_scope("embed"):
            x = self._embed(params, tok, pos)[:, None, :]  # [b, 1, d]
        k_toks, v_toks = [], []
        for li, lp in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{li}/attn"):
                h = _layer_norm(x, lp["ln1_s"], lp["ln1_b"])
                q = self._heads(h @ lp["wq"])               # [b, 1, H, Dh]
                k_new = (h @ lp["wk"])[:, 0]                # [b, d]
                v_new = (h @ lp["wv"])[:, 0]
                with jax.named_scope("kv_gather"):
                    k_l = view(arena_k, li, k_new)          # [b, kv, H, Dh]
                    v_l = view(arena_v, li, v_new)
                a = decode_attention(q, k_l, v_l, lens + 1)
                x = x + a.reshape(b, 1, self.d_model) @ lp["wo"]
            with jax.named_scope(f"layer_{li}/mlp"):
                x = x + self._mlp(
                    _layer_norm(x, lp["ln2_s"], lp["ln2_b"]), lp)
            k_toks.append(k_new)
            v_toks.append(v_new)
        # One scatter per arena, after every layer has read it: the
        # donated buffer's last use, so it is updated where it lies.
        arena_k, arena_v = self._scatter(
            arena_k, arena_v, tables[jnp.arange(b), lens // bt], lens % bt,
            jnp.stack(k_toks), jnp.stack(v_toks))
        picked, finite = self._pick(params, x[:, 0])
        return picked, finite, arena_k, arena_v

    # ------------------------------------------------------------ conveniences
    def logits(self, tokens, seg, pos):
        return self._logits_fn(self.params_tree,
                               np.asarray(tokens, np.int32),
                               np.asarray(seg, np.int32),
                               np.asarray(pos, np.int32))

    def prefill(self, tokens, seg, pos, arena_k, arena_v, blk, off, last):
        return self._prefill_fn(
            self.params_tree, np.asarray(tokens, np.int32),
            np.asarray(seg, np.int32), np.asarray(pos, np.int32),
            arena_k, arena_v, np.asarray(blk, np.int32),
            np.asarray(off, np.int32), np.asarray(last, np.int32))

    def step(self, tok, pos, arena_k, arena_v, tables, lens):
        return self._step_fn(
            self.params_tree, np.asarray(tok, np.int32),
            np.asarray(pos, np.int32), arena_k, arena_v,
            np.asarray(tables, np.int32), np.asarray(lens, np.int32))


def naive_generate(model: TransformerDecoder, prompt: Sequence[int],
                   n_tokens: int, *, pad_to: int) -> List[int]:
    """Full-recompute greedy decode — the baseline the KV-cached loop is
    compared with: every token re-runs the WHOLE sequence through the
    plain forward (padded to `pad_to`: one signature for every length)
    and takes numpy's argmax of the last position's logits."""
    toks = [int(t) for t in prompt]
    out: List[int] = []
    for _ in range(n_tokens):
        t = len(toks)
        if t > pad_to:
            raise ValueError(f"sequence {t} exceeds pad_to {pad_to}")
        row = np.zeros((1, pad_to), np.int32)
        seg = np.zeros((1, pad_to), np.int32)
        pos = np.zeros((1, pad_to), np.int32)
        row[0, :t] = toks
        seg[0, :t] = 1
        pos[0, :t] = np.arange(t)
        nxt = int(np.asarray(model.logits(row, seg, pos)[0, t - 1]).argmax())
        toks.append(nxt)
        out.append(nxt)
    return out


class TransformerAdapter:
    """Token-family adapter: packed prefill + paged-KV decode steps.

    The adapter owns nothing on the device: each call lends the cache's
    arenas to the model's executable and rebinds the cache to what comes
    back. What crosses the link is a step's tokens, positions, block
    table and lengths going up and one picked token and one finite flag
    a row coming back (``serving_decode_h2d|d2h_bytes_total`` count
    exactly those). Every served token passes through :meth:`_greedy`
    on the host.

    ``prefill_group``/``step`` report per-request outcomes as
    ``(results, failures)`` dicts instead of raising for partial
    trouble — the engine applies survivors and fails victims typed, so
    a KV-grow failure or a poisoned row never costs its batchmates a
    token. A row's length advances only once the host has accepted its
    token, so a row that failed, or that is stepped again alone after a
    failed batch step, writes the slot it wrote before.
    """

    kind = "token"

    def __init__(self, model: TransformerDecoder, cache: PagedKVCache,
                 *, pack_bucket: int = 64, check_finite: bool = True):
        self.model = model
        self.cache = cache
        self.pack_bucket = next_pow2_bucket(pack_bucket)
        if self.pack_bucket > model.max_context:
            raise ValueError("pack_bucket exceeds model max_context")
        self.check_finite = bool(check_finite)
        self._link = _register_link_metrics()

    @staticmethod
    def _greedy(picked) -> int:
        """The token served for one row, from what the device picked."""
        return int(picked)

    # ------------------------------------------------------------- validation
    def validate_prompt(self, prompt) -> np.ndarray:
        p = np.asarray(prompt)
        if p.ndim != 1 or p.size < 1:
            raise ValueError("prompt must be a non-empty 1-D token list")
        p = p.astype(np.int64)
        if p.min() < 0 or p.max() >= self.model.vocab:
            raise ValueError(
                f"prompt tokens outside [0, {self.model.vocab})")
        if p.size > self.pack_bucket:
            raise ValueError(
                f"prompt length {p.size} exceeds pack_bucket "
                f"{self.pack_bucket}")
        return p.astype(np.int32)

    def max_total_len(self, prompt_len: int, max_new: int) -> int:
        return prompt_len + max_new

    # ---------------------------------------------------------------- prefill
    def pack_groups(self, items: List[Tuple[int, np.ndarray]]
                    ) -> List[List[Tuple[int, np.ndarray]]]:
        """Greedy first-fit: split (rid, prompt) pairs into groups whose
        total token count rides one [1, pack_bucket] row."""
        groups: List[List[Tuple[int, np.ndarray]]] = []
        fill: List[int] = []
        for rid, p in items:
            for gi, g in enumerate(groups):
                if fill[gi] + p.size <= self.pack_bucket:
                    g.append((rid, p))
                    fill[gi] += p.size
                    break
            else:
                groups.append([(rid, p)])
                fill.append(p.size)
        return groups

    def prefill_group(self, group: List[Tuple[int, np.ndarray]]
                      ) -> Tuple[Dict[int, int], Dict[int, BaseException]]:
        """Prefill ONE packed group: reserve each rid's KV blocks,
        splice the prompts into a segment-masked row and run the packed
        forward, which writes their K/V into those blocks. Returns
        ``({rid: first token}, {rid: error})`` — a rid whose blocks
        cannot be granted lands in the failure dict (typed
        KVCacheExhaustedError) and stays out of the row, without
        costing groupmates theirs."""
        pb = self.pack_bucket
        row = np.zeros((1, pb), np.int32)
        seg = np.zeros((1, pb), np.int32)
        pos = np.zeros((1, pb), np.int32)
        blk = np.full((pb,), self.cache.scratch, np.int32)
        off = np.zeros((pb,), np.int32)
        last = np.zeros((pb,), np.int32)
        out: Dict[int, int] = {}
        fails: Dict[int, BaseException] = {}
        placed: List[Tuple[int, int]] = []  # (rid, prompt tokens)
        cur = 0
        for rid, p in group:
            lo, hi = cur, cur + p.size
            if hi > pb:
                raise ValueError("pack group overflows pack_bucket")
            try:
                blk[lo:hi], off[lo:hi] = self.cache.reserve(rid, p.size)
            except KVCacheExhaustedError as e:
                fails[rid] = e
                continue
            row[0, lo:hi] = p
            seg[0, lo:hi] = len(placed) + 1
            pos[0, lo:hi] = np.arange(p.size)
            last[len(placed)] = hi - 1
            placed.append((rid, p.size))
            cur = hi
        if not placed:
            return out, fails
        up = _nbytes(row, seg, pos, blk, off, last)
        with tracing.span("decode/launch", cat="serve", bytes=up):
            picked, finite = self.cache.update(
                lambda k, v: self.model.prefill(row, seg, pos, k, v, blk,
                                                off, last))
        self._link["h2d", "prefill"].inc(up)
        with tracing.span("decode/fetch", cat="serve"):
            picked, finite = jax.device_get((picked, finite))
            down = _nbytes(picked, finite)
            tracing.annotate(bytes=down)
        self._link["d2h", "prefill"].inc(down)
        with tracing.span("decode/commit", cat="serve"):
            for i, (rid, n_tok) in enumerate(placed):
                if self.check_finite and not finite[i]:
                    self.cache.free(rid)
                    fails[rid] = NonFiniteOutputError(
                        "prefill produced non-finite logits")
                    continue
                self.cache.advance(rid, n_tok)
                out[rid] = self._greedy(picked[i])
        return out, fails

    # ------------------------------------------------------------------- step
    def kv_bucket(self, rids: Sequence[int]) -> int:
        """View length for this step: room for every row's cache + the
        token being scattered, snapped to the shared pow2 rule and never
        below one block (the finite compiled grid)."""
        need = max(self.cache.length(r) for r in rids) + 1
        return max(self.cache.block_tokens, next_pow2_bucket(need))

    def step(self, rids: Sequence[int], last_items: Sequence[Any]
             ) -> Tuple[Dict[int, int], Dict[int, BaseException]]:
        """One iteration-level step for `rids`: rows are padded to the
        pow2 row bucket with rows of the scratch block at length 0 (a
        pad row reads and writes nothing a request owns). Returns
        ``({rid: next token}, {rid: error})``."""
        n = len(rids)
        bucket = next_pow2_bucket(n)
        kvb = self.kv_bucket(rids)
        tracing.annotate(row_bucket=bucket, kv_bucket=kvb)
        with tracing.span("decode/gather", cat="serve"):
            tables, lens, fails = self.cache.batch_view(rids, kvb, bucket)
            tracing.annotate(bytes=_nbytes(tables, lens))
        toks = np.zeros((bucket,), np.int32)
        toks[:n] = last_items
        # `lens` goes up twice: as the positions and as the lengths
        up = _nbytes(toks, lens, tables, lens)
        with tracing.span("decode/launch", cat="serve", bytes=up):
            picked, finite = self.cache.update(
                lambda k, v: self.model.step(toks, lens, k, v, tables, lens))
        self._link["h2d", "step"].inc(up)
        with tracing.span("decode/fetch", cat="serve"):
            picked, finite = jax.device_get((picked, finite))
            down = _nbytes(picked, finite)
            tracing.annotate(bytes=down)
        self._link["d2h", "step"].inc(down)
        out: Dict[int, int] = {}
        with tracing.span("decode/commit", cat="serve"):
            for i, rid in enumerate(rids):
                if rid in fails:
                    continue
                if self.check_finite and not finite[i]:
                    fails[rid] = NonFiniteOutputError(
                        "decode step produced non-finite logits")
                    continue
                self.cache.advance(rid)
                out[rid] = self._greedy(picked[i])
        return out, fails

    # ------------------------------------------------------------------ admin
    def free(self, rid: int) -> None:
        self.cache.free(rid)

    def kv_blocks(self, rid: int) -> int:
        return self.cache.blocks_of(rid)

    def result_of(self, generated: List[int]) -> List[int]:
        return list(generated)

    def warm_signatures(self, max_rows: int, max_context: int
                        ) -> Tuple[List[int], List[int]]:
        """The finite compiled grid: pow2 row buckets × pow2 KV view
        buckets (block-floored, each a table width; one bucket past
        max_context so a row AT max_context can still scatter its final
        token)."""
        rows, b = [], 1
        top = next_pow2_bucket(max_rows)
        while b <= top:
            rows.append(b)
            b <<= 1
        kvs, kv = [], self.cache.block_tokens
        top_kv = next_pow2_bucket(max_context + 1)
        while kv <= top_kv:
            kvs.append(kv)
            kv <<= 1
        return rows, kvs

    def warm_steps(self, rows: int, kvs: Sequence[int]) -> None:
        """Run the step executable of `rows` rows at each view bucket
        on the live arena (it is the only one): every row is a pad row,
        so only the scratch block is written."""
        for kv in kvs:
            tables, zeros, _ = self.cache.batch_view((), kv, rows)
            self.cache.update(lambda k, v: self.model.step(
                zeros, zeros, k, v, tables, zeros))

    def warmup(self, max_rows: int, max_context: int) -> List[int]:
        """Precompile the packed prefill signature and every step
        signature in the grid. Returns the row buckets warmed."""
        pb = self.pack_bucket
        row = np.zeros((1, pb), np.int32)
        at = np.zeros((pb,), np.int32)
        scratch = np.full((pb,), self.cache.scratch, np.int32)
        self.cache.update(lambda k, v: self.model.prefill(
            row, row, row, k, v, scratch, at, at))
        rows, kvs = self.warm_signatures(max_rows, max_context)
        for b in rows:
            self.warm_steps(b, kvs)
        return rows


# ---------------------------------------------------------------------------
# Recurrent arm: streaming LSTM through rnn_time_step
# ---------------------------------------------------------------------------
class RecurrentAdapter:
    """Stream-family adapter: a MultiLayerNetwork generates through the
    same iteration-level loop via ``rnn_time_step``.

    There is no KV cache — recurrent state IS the cache. Per-request
    carry rows (each layer's h/c at batch 1) live host-side in this
    adapter; every step gathers the active rows into one pow2-bucketed
    batch (pad rows repeat row 0), assigns it as the net's carry, runs
    ONE ``rnn_time_step`` at the bucketed signature, and scatters the
    advanced rows back. The model's own output feeds back as the next
    step's input, so the net must be built with ``n_out == n_in``.
    """

    kind = "stream"

    def __init__(self, net, *, feature_dim: int,
                 check_finite: bool = True):
        for layer in net.layers:
            if not layer.supports_streaming():
                raise ValueError(
                    f"{type(layer).__name__} cannot stream — the decode "
                    "loop needs rnn_time_step support")
        self.net = net
        self.feature_dim = int(feature_dim)
        self.check_finite = bool(check_finite)
        self._carries: Dict[int, tuple] = {}
        self.pack_bucket = 0  # no packed path on the stream arm
        self._link = _register_link_metrics()

    # ------------------------------------------------------------- validation
    def validate_prompt(self, prompt) -> np.ndarray:
        p = np.asarray(prompt, np.float32)
        if p.ndim != 2 or p.shape[0] < 1 or p.shape[1] != self.feature_dim:
            raise ValueError(
                f"stream prompt must be [time, {self.feature_dim}], got "
                f"{p.shape}")
        return p

    def max_total_len(self, prompt_len: int, max_new: int) -> int:
        return prompt_len + max_new

    # ---------------------------------------------------------------- prefill
    def pack_groups(self, items):
        # Stream prefill is inherently sequential per request: every
        # prompt is its own "group" (still admitted between steps).
        return [[it] for it in items]

    def prefill_group(self, group
                      ) -> Tuple[Dict[int, np.ndarray],
                                 Dict[int, BaseException]]:
        (rid, prompt), = group
        net = self.net
        net.rnn_clear_previous_state()
        out = None
        try:
            # One token at a time at the [1, features] signature:
            # exactly ONE compiled executable serves every prompt
            # length, and no pad step ever touches the carry.
            with tracing.span("decode/launch", cat="serve",
                              bytes=prompt.nbytes):
                for t in range(prompt.shape[0]):
                    out = net.rnn_time_step(prompt[t:t + 1])
            self._link["h2d", "prefill"].inc(prompt.nbytes)
            with tracing.span("decode/fetch", cat="serve"):
                carry = tuple(
                    {k: np.asarray(v).copy() for k, v in layer.items()}
                    for layer in net._rnn_carry)
                out = np.asarray(out)[0]
                down = _nbytes(out, carry)
                tracing.annotate(bytes=down)
            self._link["d2h", "prefill"].inc(down)
        finally:
            net.rnn_clear_previous_state()
        with tracing.span("decode/commit", cat="serve"):
            if self.check_finite and not np.isfinite(out).all():
                return {}, {rid: NonFiniteOutputError(
                    "stream prefill produced non-finite outputs")}
            self._carries[rid] = carry
        return {rid: out}, {}

    # ------------------------------------------------------------------- step
    def step(self, rids: Sequence[int], last_items: Sequence[np.ndarray]
             ) -> Tuple[Dict[int, np.ndarray],
                        Dict[int, BaseException]]:
        n = len(rids)
        bucket = next_pow2_bucket(n)
        pad_i = [min(i, n - 1) for i in range(bucket)]
        net = self.net
        layers = len(self._carries[rids[0]])
        tracing.annotate(row_bucket=bucket)
        with tracing.span("decode/launch", cat="serve"):
            merged = tuple(
                {k: jnp.asarray(np.concatenate(
                    [self._carries[rids[pad_i[j]]][li][k]
                     for j in range(bucket)], axis=0))
                 for k in self._carries[rids[0]][li]}
                for li in range(layers))
            x = np.stack([np.asarray(last_items[pad_i[j]], np.float32)
                          for j in range(bucket)], axis=0)
            up = _nbytes(x, merged)
            tracing.annotate(bytes=up)
            net._rnn_carry = merged
            try:
                out = net.rnn_time_step(x)
                new_carry = net._rnn_carry
            finally:
                net.rnn_clear_previous_state()
        self._link["h2d", "step"].inc(up)
        with tracing.span("decode/fetch", cat="serve"):
            out = np.asarray(out)
            # the carry comes back in the commit below, once a leaf (jax
            # keeps an array's host copy) however many riders slice it
            down = _nbytes(out, new_carry)
            tracing.annotate(bytes=down)
        self._link["d2h", "step"].inc(down)
        results: Dict[int, np.ndarray] = {}
        fails: Dict[int, BaseException] = {}
        with tracing.span("decode/commit", cat="serve"):
            for i, rid in enumerate(rids):
                if self.check_finite and not np.isfinite(out[i]).all():
                    fails[rid] = NonFiniteOutputError(
                        "decode step produced non-finite outputs")
                    continue
                self._carries[rid] = tuple(
                    {k: np.asarray(v)[i:i + 1].copy()
                     for k, v in layer.items()}
                    for layer in new_carry)
                results[rid] = out[i]
        return results, fails

    # ------------------------------------------------------------------ admin
    def free(self, rid: int) -> None:
        self._carries.pop(rid, None)

    def kv_blocks(self, rid: int) -> int:
        return 0

    def result_of(self, generated: List[np.ndarray]) -> np.ndarray:
        return np.stack([np.asarray(g) for g in generated], axis=0)

    def warm_signatures(self, max_rows: int, max_context: int):
        rows, b = [], 1
        top = next_pow2_bucket(max_rows)
        while b <= top:
            rows.append(b)
            b <<= 1
        return rows, []

    def warmup(self, max_rows: int, max_context: int) -> List[int]:
        """Warm the [1, features] prefill signature and each pow2 row
        bucket's step signature."""
        rows, _ = self.warm_signatures(max_rows, max_context)
        net = self.net
        for b in rows:
            net.rnn_clear_previous_state()
            net.rnn_time_step(np.zeros((b, self.feature_dim), np.float32))
        net.rnn_clear_previous_state()
        return rows


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
class _DecodeRequest:
    __slots__ = ("rid", "prompt", "max_new_tokens", "event", "result",
                 "error", "deadline", "trace", "generated", "last_item",
                 "t_last")

    def __init__(self, rid: int, prompt, max_new_tokens: int,
                 deadline: Optional[float], trace):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.deadline = deadline
        self.trace = trace
        self.generated: List[Any] = []
        self.last_item = None     # feeds the next step's input
        self.t_last = 0.0         # last token emission (inter-token gap)

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) > self.deadline


class _StepPause:
    """Context manager holding the engine's step lock: the in-flight
    step completes, then the loop stalls BETWEEN steps — active
    generations wait, they are not dropped (the hot-swap window)."""

    def __init__(self, lock):
        self._lock = lock

    def __enter__(self):
        self._lock.acquire()
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False


class DecodeEngine:
    """Iteration-level continuous-batching decode loop.

    Duck-types the :class:`~..parallel.inference.ParallelInference`
    surface ModelPool and the gateway consume (queue_depth /
    estimate_wait_s / warmup / paused / shutdown / scheduler hooks /
    telemetry counters), so a decode entry rides the existing breaker,
    WFQ, hot-swap, and describe() machinery unchanged. The entry point
    is :meth:`generate`, not ``output``.

    The loop, each iteration:

    1. **Admit** queued prompts up to ``max_decode_batch`` active
       requests — packed prefill groups for the token arm, per-prompt
       for the stream arm. A prompt whose KV blocks cannot be granted
       requeues at the FRONT while others are still generating (their
       completions free blocks), or fails typed when the cache could
       never fit it right now.
    2. **Step** every active request ONE token, under the step lock
       (the ``paused()`` swap gate) and one scheduler slot (WFQ
       preemption point: cost = live rows). Each attempt fires
       ``serve.decode_step``; a failed batch step is re-run solo per
       rider — only a request whose solo step ALSO fails gets
       :class:`DecodeStepError`, its KV freed, batchmates untouched.
    3. **Retire** finished requests immediately (event set between
       steps — the Orca property) with flight-recorder ctx
       ``tokens_generated`` / ``kv_blocks``.
    """

    def __init__(self, adapter, *, name: str = "decode",
                 max_decode_batch: int = 8, queue_limit: int = 64,
                 max_context: Optional[int] = None):
        self.adapter = adapter
        self.name = name
        self.max_decode_batch = int(max_decode_batch)
        self.queue_limit = int(queue_limit)
        if max_context is None:
            max_context = (adapter.model.max_context
                           if adapter.kind == "token" else 64)
        self.max_context = int(max_context)
        self.check_finite = bool(getattr(adapter, "check_finite", True))

        # ParallelInference duck-type surface (ModelPool/describe()).
        self.warmed_buckets: List[int] = []
        self.total_forwards = 0
        self.total_shed = 0
        self.total_batch_failures = 0
        self.batch_timeout_ms = 0.0
        self.on_shed: Optional[Callable] = None
        self.on_batch: Optional[Callable] = None
        self.on_batch_error: Optional[Callable] = None
        self.scheduler = None
        self.sched_name: Optional[str] = None

        self._rid = 0
        self._queue: collections.deque = collections.deque()
        self._active: List[_DecodeRequest] = []
        self._cv = threading.Condition()
        self._lock = threading.RLock()   # step/execution lock (paused())
        self._shutdown = False
        self._ewma_step_s = 0.0
        self._step_no = 0       # step attempts made; names `decode/step`

        reg = registry()
        self._tokens_c = reg.counter("serving_decode_tokens_total",
                                     _TOKENS_HELP).labels(model=name)
        self._steps_c = reg.counter("serving_decode_steps_total",
                                    _STEPS_HELP).labels(model=name)
        self._prefills_c = reg.counter("serving_decode_prefills_total",
                                       _PREFILL_HELP).labels(model=name)
        self._itl_h = reg.histogram(
            "serving_inter_token_ms", _ITL_HELP,
            buckets=INTER_TOKEN_BUCKETS_MS).labels(model=name)
        cache = getattr(adapter, "cache", None)
        if cache is not None:
            wr = weakref.ref(cache)

            def _collect(reg, _wr=wr, _name=name):
                c = _wr()
                if c is None:
                    return
                reg.gauge("serving_kv_blocks_in_use",
                          _KV_BLOCKS_HELP).labels(model=_name).set(
                              c.blocks_in_use())
                reg.gauge("serving_kv_utilization",
                          _KV_UTIL_HELP).labels(model=_name).set(
                              round(c.utilization(), 6))

            reg.register_collector(_collect)

        self._worker = threading.Thread(
            target=self._loop, name=f"DecodeEngine-{name}", daemon=True)
        self._worker.start()

    # ------------------------------------------------------ duck-type surface
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def active_count(self) -> int:
        with self._cv:
            return len(self._active)

    def estimate_wait_s(self) -> float:
        """Admission estimate: queued prompts ahead at the EWMA step
        time, batched at max_decode_batch. 0.0 while cold (admit
        optimistically, like ParallelInference)."""
        svc = self._ewma_step_s
        if svc <= 0.0:
            return 0.0
        ahead = self.queue_depth() // max(1, self.max_decode_batch)
        return (ahead + 1) * svc

    def paused(self) -> _StepPause:
        return _StepPause(self._lock)

    def warmup(self, max_bucket: Optional[int] = None, **_kw
               ) -> "DecodeEngine":
        """Precompile the full decode signature grid: packed prefill +
        every (row bucket × KV view bucket) step executable. After this
        a steady-state engine compiles NOTHING (the acceptance gate
        tests/smoke_decode.py asserts)."""
        with self._lock:    # the token arm's warm-up runs on the live arena
            rows = self.adapter.warmup(
                max_bucket or self.max_decode_batch, self.max_context)
        for b in rows:
            if b not in self.warmed_buckets:
                self.warmed_buckets.append(b)
        return self

    def swap_warm(self, bucket: int) -> None:
        """ModelPool.swap's per-bucket warm hook: new params, same
        static signatures — re-run the row bucket across the KV grid so
        a canaried swap admits zero cold compiles."""
        ad = self.adapter
        if ad.kind == "token":
            _, kvs = ad.warm_signatures(bucket, self.max_context)
            ad.warm_steps(next_pow2_bucket(bucket), kvs)
        else:
            net = ad.net
            net.rnn_clear_previous_state()
            net.rnn_time_step(np.zeros((next_pow2_bucket(bucket),
                                        ad.feature_dim), np.float32))
            net.rnn_clear_previous_state()

    def output(self, x, **_kw):
        raise NotImplementedError(
            f"{self.name!r} is a decode entry: use generate() / POST "
            "/generate (one-shot /predict does not apply)")

    # --------------------------------------------------------------- generate
    def generate(self, prompt, *, max_new_tokens: int = 16,
                 deadline: Optional[float] = None, trace=None):
        """Blocking decode of one prompt: admitted between steps, rides
        the continuous batch, returns the adapter's result (token-id
        list for the transformer arm, [steps, features] array for the
        stream arm). Raises the same typed taxonomy as
        ParallelInference.output plus DecodeStepError /
        KVCacheExhaustedError."""
        p = self.adapter.validate_prompt(prompt)
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        plen = p.shape[0]
        total = self.adapter.max_total_len(plen, int(max_new_tokens))
        if total > self.max_context:
            raise ValueError(
                f"prompt {plen} + max_new_tokens {max_new_tokens} "
                f"exceeds max_context {self.max_context}")
        with self._cv:
            if self._shutdown:
                raise ServerClosedError(
                    "DecodeEngine was shut down before this request ran")
            if len(self._queue) >= self.queue_limit:
                raise QueueFullError(
                    f"decode admission queue at capacity "
                    f"({self.queue_limit})")
            self._rid += 1
            req = _DecodeRequest(self._rid, p, int(max_new_tokens),
                                 deadline, trace)
            self._queue.append(req)
            self._cv.notify_all()
        while not req.event.wait(timeout=0.5):
            with self._cv:
                if self._shutdown and not req.event.is_set():
                    req.error = ServerClosedError(
                        "DecodeEngine shut down mid-request")
                    req.event.set()
        if req.error is not None:
            raise req.error
        return req.result

    # ------------------------------------------------------------------- loop
    def _loop(self):
        while True:
            with self._cv:
                while (not self._queue and not self._active
                       and not self._shutdown):
                    self._cv.wait(timeout=0.2)
                if self._shutdown and not self._active and not self._queue:
                    return
                admits = self._take_admits_locked()
            try:
                if admits:
                    self._admit(admits)
                if self._active:
                    self._step_once()
            except Exception as e:  # noqa: BLE001 — loop must survive
                # A bug past the per-step isolation would otherwise hang
                # every caller: fail the in-flight set typed and keep
                # the loop alive for the next admission.
                for req in admits + list(self._active):
                    if not req.event.is_set():
                        self._fail_step(req, e)
                # loop thread is the ONLY writer of _active; readers
                # snapshot the rebound list
                self._active = [r for r in self._active  # jaxlint: atomic
                                if not r.event.is_set()]

    def _take_admits_locked(self) -> List[_DecodeRequest]:
        room = self.max_decode_batch - len(self._active)
        admits: List[_DecodeRequest] = []
        while room > 0 and self._queue:
            admits.append(self._queue.popleft())
            room -= 1
        return admits

    def _fail(self, req: _DecodeRequest, err: BaseException) -> None:
        self._finalize_trace(req)
        self.adapter.free(req.rid)
        req.error = err
        req.event.set()

    def _finish(self, req: _DecodeRequest) -> None:
        req.result = self.adapter.result_of(req.generated)
        self._finalize_trace(req)
        self.adapter.free(req.rid)
        req.event.set()

    def _finalize_trace(self, req: _DecodeRequest) -> None:
        tr = req.trace
        if tr is None:
            return
        tr.ctx["tokens_generated"] = len(req.generated)
        tr.ctx["kv_blocks"] = self.adapter.kv_blocks(req.rid)
        tr.mark("unpack")

    # -------------------------------------------------------------- admission
    def _admit(self, admits: List[_DecodeRequest]) -> None:
        now = time.monotonic()
        live: List[_DecodeRequest] = []
        for req in admits:
            if req.expired(now):
                self.total_shed += 1  # jaxlint: atomic (loop-thread stat)
                if self.on_shed is not None:
                    self.on_shed(req, "expired")
                self._fail(req, DeadlineExceededError(
                    "deadline passed while queued for decode admission"))
                continue
            live.append(req)
        if not live:
            return
        with tracing.span("decode/admit", cat="serve"):
            tracing.annotate(admitted=self._prefill_groups(live))
        self._retire_done()

    def _prefill_groups(self, live: List[_DecodeRequest]) -> int:
        """Prefill `live` group by group; the number that joined the
        active set."""
        admitted = 0
        by_rid = {r.rid: r for r in live}
        for group in self.adapter.pack_groups(
                [(r.rid, r.prompt) for r in live]):
            reqs = [by_rid[rid] for rid, _ in group]
            for r in reqs:
                if r.trace is not None:
                    r.trace.mark("queue_wait")
            try:
                with self._lock, self._sched_slot(cost=float(len(group))):
                    t0 = time.perf_counter()
                    with self._span("decode/prefill", reqs):
                        if tracing.is_enabled():
                            tracing.annotate(tokens=sum(
                                int(p.shape[0]) for _, p in group))
                        first, fails = self.adapter.prefill_group(group)
                    dur = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 — typed wrapper below
                self.total_batch_failures += 1  # jaxlint: atomic
                if self.on_batch_error is not None:
                    self.on_batch_error(e, len(reqs))
                for r in reqs:
                    err = DecodeStepError(
                        f"packed prefill failed for request {r.rid}: "
                        f"{e!r}")
                    err.__cause__ = e
                    self._fail(r, err)
                continue
            self.total_forwards += 1  # jaxlint: atomic (loop-thread stat)
            self._prefills_c.inc()
            ok_reqs = [r for r in reqs if r.rid in first]
            if self.on_batch is not None and ok_reqs:
                self.on_batch(ok_reqs, len(ok_reqs),
                              getattr(self.adapter, "pack_bucket", 0),
                              dur)
            now2 = time.perf_counter()
            for r in ok_reqs:
                item = first[r.rid]
                r.generated.append(item)
                r.last_item = item
                r.t_last = now2
                self._tokens_c.inc()
                if r.trace is not None:
                    r.trace.mark("prefill")
                self._active.append(r)
            admitted += len(ok_reqs)
            kv_starved = [r for r in reqs if isinstance(
                fails.get(r.rid), KVCacheExhaustedError)]
            if kv_starved:
                self._requeue_or_fail(kv_starved, fails)
            for r in reqs:
                err = fails.get(r.rid)
                if err is not None and r not in kv_starved:
                    self.total_batch_failures += 1  # jaxlint: atomic
                    if self.on_batch_error is not None:
                        self.on_batch_error(err, 1)
                    self._fail(r, err)
        return admitted

    def _requeue_or_fail(self, reqs: List[_DecodeRequest],
                         fails: Dict[int, BaseException]) -> None:
        """KV admission backpressure: while anything is generating its
        completion will free blocks — park the starved prompts at the
        FRONT of the queue. With nothing active the cache cannot free
        up on its own: fail typed now (the gateway's 429)."""
        if self._active:
            with self._cv:
                for r in reversed(reqs):
                    self._queue.appendleft(r)
        else:
            self.total_shed += len(reqs)  # jaxlint: atomic (loop-thread stat)
            for r in reqs:
                if self.on_shed is not None:
                    self.on_shed(r, "kv_exhausted")
                self._fail(r, fails[r.rid])

    # ------------------------------------------------------------------- step
    def _sched_slot(self, cost: float = 1.0):
        if self.scheduler is None:
            return contextlib.nullcontext()
        return self.scheduler.slot(self.sched_name or self.name,
                                   cost=cost)

    def _span(self, name: str, reqs: List[_DecodeRequest], **args):
        """A `serve` span over work done for `reqs`: `rows`, and `rids`,
        each rider under the identifier its `serve/*` spans carry (the
        flight recorder's) or, with the recorder off, the engine's own.
        One branch when tracing is off."""
        if not tracing.is_enabled():
            return tracing.span(name)
        return tracing.span(
            name, cat="serve", rows=len(reqs),
            rids=[r.rid if r.trace is None else r.trace.rid for r in reqs],
            **args)

    def _step_attempt(self, reqs: List[_DecodeRequest]
                      ) -> Tuple[Dict[int, Any], Dict[int, BaseException]]:
        self._step_no += 1  # jaxlint: atomic (loop-thread stat)
        with self._span("decode/step", reqs, step=self._step_no):
            faults.fire("serve.decode_step")
            return self.adapter.step([r.rid for r in reqs],
                                     [r.last_item for r in reqs])

    def _step_once(self) -> None:
        now = time.monotonic()
        for req in list(self._active):
            if req.expired(now):
                self._active.remove(req)
                self.total_shed += 1  # jaxlint: atomic (loop-thread stat)
                if self.on_shed is not None:
                    self.on_shed(req, "expired")
                self._fail(req, DeadlineExceededError(
                    f"deadline passed after {len(req.generated)} "
                    "token(s)"))
        if not self._active:
            return
        reqs = list(self._active)
        t0 = time.perf_counter()
        with self._lock, self._sched_slot(cost=float(len(reqs))):
            try:
                out, fails = self._step_attempt(reqs)
            except Exception as e:  # noqa: BLE001 — isolated below
                self.total_batch_failures += 1  # jaxlint: atomic
                if self.on_batch_error is not None:
                    self.on_batch_error(e, len(reqs))
                out, fails = self._isolate(reqs, e)
        dur = time.perf_counter() - t0
        # loop thread is the only writer; estimate_wait_s reads a
        # torn-free float snapshot
        self._ewma_step_s = (dur if self._ewma_step_s == 0.0  # jaxlint: atomic
                             else 0.8 * self._ewma_step_s + 0.2 * dur)
        for rid, err in fails.items():
            req = next((r for r in self._active if r.rid == rid), None)
            if req is None:
                continue
            self.total_batch_failures += 1  # jaxlint: atomic
            if self.on_batch_error is not None:
                self.on_batch_error(err, 1)
            self._fail_step(req, err)
        if out:
            self.total_forwards += 1  # jaxlint: atomic (loop-thread stat)
            self._steps_c.inc()
            if self.on_batch is not None:
                survivors = [r for r in reqs if r.rid in out]
                self.on_batch(survivors, len(survivors),
                              next_pow2_bucket(len(reqs)), dur)
            self._apply(out)
        self._retire_done()

    def _isolate(self, reqs: List[_DecodeRequest],
                 batch_err: BaseException
                 ) -> Tuple[Dict[int, Any], Dict[int, BaseException]]:
        """Solo-retry isolation after a failed batch step: each rider
        re-steps ALONE (its own fault fire — mirrors ParallelInference's
        per-attempt semantics); a request whose solo step also fails is
        failed typed with its KV freed, survivors' results merge."""
        if len(reqs) == 1:
            return {}, {reqs[0].rid: batch_err}
        merged: Dict[int, Any] = {}
        fails: Dict[int, BaseException] = {}
        for r in reqs:
            if r not in self._active:
                continue
            try:
                out_r, fails_r = self._step_attempt([r])
            except Exception as e:  # noqa: BLE001
                fails[r.rid] = e
                continue
            merged.update(out_r)
            fails.update(fails_r)
        return merged, fails

    def _fail_step(self, req: _DecodeRequest, cause: BaseException) -> None:
        if req in self._active:
            self._active.remove(req)
        if isinstance(cause, (KVCacheExhaustedError,
                              NonFiniteOutputError)):
            err: BaseException = cause
        else:
            err = DecodeStepError(
                f"decode step failed after {len(req.generated)} "
                f"token(s): {cause!r}")
            err.__cause__ = cause
        self._fail(req, err)

    def _apply(self, out: Dict[int, Any]) -> None:
        now = time.perf_counter()
        for req in list(self._active):
            if req.rid not in out:
                continue
            item = out[req.rid]
            req.generated.append(item)
            req.last_item = item
            self._tokens_c.inc()
            if req.t_last:
                self._itl_h.observe((now - req.t_last) * 1000.0)
            req.t_last = now
            if req.trace is not None:
                req.trace.mark("decode_step")

    def _retire_done(self) -> None:
        for req in list(self._active):
            if len(req.generated) >= req.max_new_tokens:
                self._active.remove(req)
                self._finish(req)

    # --------------------------------------------------------------- shutdown
    def shutdown(self, join_timeout: float = 5.0) -> None:
        """Close the engine: the loop drains in-flight generations to
        completion; anything still unserved after the join window is
        failed with ServerClosedError instead of hanging its caller."""
        with self._cv:
            already = self._shutdown
            self._shutdown = True
            self._cv.notify_all()
        if not already:
            self._worker.join(timeout=join_timeout)
        err = ServerClosedError(
            "DecodeEngine was shut down before this request completed")
        with self._cv:
            stranded = list(self._queue) + list(self._active)
            self._queue.clear()
            self._active.clear()
        for req in stranded:
            if not req.event.is_set():
                self.adapter.free(req.rid)
                req.error = err
                req.event.set()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
