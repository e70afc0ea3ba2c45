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
  tokens-in-flight, not ``max_seq × batch``; a layer that attends over
  a sliding window gives back the blocks behind it, so the cache keeps
  one set of arenas and tables per KIND of layer (full, sliding). The
  arenas are operands of
  the step and prefill executables, donated and updated in place: a
  step sends a block table and the lengths, never K/V. Block size is
  forced through the shared ``next_pow2_bucket`` rule
  (data/padding.py) — the same rule buckets the decode-step row count
  and the KV view length, so the set of compiled step executables is
  the finite grid ``{row buckets} × {table widths}`` and a warmed
  engine compiles NOTHING at steady state.

Two model families decode through the one engine:

* :class:`TransformerAdapter` wraps :class:`TransformerDecoder` (a
  self-contained causal LM whose block is a configuration: norm,
  positions, grouped KV heads, dense or sparse-expert feed-forward, a
  pattern of full and sliding-window layers): prompts are admitted
  through the packed path in CHUNKS of ``pack_bucket`` positions —
  ragged prompts spliced into ONE segment-masked row (PR 12's segment
  semantics), a prompt longer than a chunk slice by slice, each slice
  reading the ones before it from the cache, decode steps running
  between chunks — and each step runs single-query-row attention
  through the block tables via
  ``ops.flash_attention.paged_decode_attention``.
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
from ..ops import moe
from ..ops.flash_attention import paged_decode_attention, prefill_attention
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
_CHUNKS_HELP = "Prefill chunks executed (one packed row of pack_bucket)"
_CHUNK_TOKENS_HELP = "Prompt positions the prefill chunks held (no padding)"
_KV_TOKENS_HELP = ("Keys the rows of the decode steps attended to in one "
                   "layer of the kind, each row's own token included")
_BLOCK_STEPS_HELP = ("KV blocks the cache held for one layer of the kind, "
                     "summed over decode steps")
_MOE_HELP = {
    "assignments": ("serving_moe_assignments_total",
                    "Token-to-expert assignments the decode steps' expert "
                    "layers computed, summed over layers"),
    "touched": ("serving_moe_experts_touched_total",
                "Experts with at least one token in a decode step, summed "
                "over layers and steps"),
    "peak": ("serving_moe_expert_load_peak_total",
             "Tokens of the fullest expert of a layer in a decode step, "
             "summed over layers and steps"),
}


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
    _register_model_metrics()


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


def _register_model_metrics() -> Dict[Any, Any]:
    """The counters the token arm increments about the model's own
    mechanisms: prefill chunks, keys attended and blocks held by kind of
    layer, the expert layers' routing sums. Labels are bounded (the two
    kinds)."""
    reg = registry()
    out: Dict[Any, Any] = {
        "chunks": reg.counter("serving_decode_prefill_chunks_total",
                              _CHUNKS_HELP),
        "chunk_tokens": reg.counter("serving_decode_prefill_tokens_total",
                                    _CHUNK_TOKENS_HELP)}
    for key, (name, text) in _MOE_HELP.items():
        out[key] = reg.counter(name, text)
    kv = reg.counter("serving_decode_kv_tokens_total", _KV_TOKENS_HELP)
    blocks = reg.counter("serving_kv_block_steps_total", _BLOCK_STEPS_HELP)
    for k in KINDS:
        out["kv_tokens", k] = kv.labels(kind=k)
        out["block_steps", k] = blocks.labels(kind=k)
    return out


def _nbytes(*arrays) -> int:
    """Bytes of the arrays, and of the leaves of any tree among them."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(arrays))


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------
KINDS = ("full", "sliding")     # a layer keeps every position, or a window


class PagedKVCache:
    """Bucket-allocated KV arenas on the device: fixed-size token blocks,
    per-request block tables, all-or-nothing grabs, one set of each per
    KIND of layer.

    A ``full`` layer keeps every position of a request; a ``sliding``
    layer needs only the last ``window`` and gives back the blocks
    behind them. So tables, free lists and arenas are per kind: for each
    kind present one preallocated pair of device arrays ``[layers of the
    kind, max_blocks + 1, block_tokens, heads × head_dim]`` holds K and
    V (``heads`` are KV heads), and all layers of a kind share a
    request's table. A full table covers positions from 0; a sliding
    table covers them from :meth:`held_from`, which moves up a block
    whenever every position of the first block is ``window`` or more
    behind the request's length (at :meth:`advance`). The arrays are
    operands of the decoder's step and prefill executables, which read
    a row's blocks through its table, scatter the new K/V in place (the
    operands are donated) and hand the arrays back: :meth:`update`
    lends them and takes the outputs. Layer-major with a token's heads
    side by side, so one layer's block is one contiguous ``block_tokens
    × (heads × head_dim)`` chunk and the last axis fills the device's
    lanes.

    Everything else is host integers and stays here: the free lists, the
    tables, the lengths. A length advances only at :meth:`advance`,
    after the host has seen the step's outcome, so a step that is run
    again (a failed row, a solo retry) writes the same slot again.

    Block ``max_blocks`` of a kind (``scratch_of[kind]``) belongs to no
    request: pad rows, pad positions and warm-up calls point at it, so
    they can write nowhere else. ``blocks_in_use`` never counts it.
    ``block_tokens`` is forced through ``next_pow2_bucket`` — the SAME
    rule that buckets the step's view length, so every table width a
    growing request can need is already in the compiled set.

    Exhaustion is typed and all-or-nothing ACROSS kinds: an allocation
    that any kind cannot satisfy raises :class:`KVCacheExhaustedError`
    WITHOUT taking a block of either (the admission path requeues or
    sheds on it; a growth failure fails only the growing request). The
    bookkeeping is thread-safe under the one lock; the arrays belong to
    whoever holds the engine's step lock.
    """

    def __init__(self, *, layers: int, heads: int, head_dim: int,
                 block_tokens: int = 16, max_blocks=256,
                 dtype=jnp.float32, layer_kinds: Optional[Sequence[str]]
                 = None, window: Optional[int] = None):
        self.block_tokens = next_pow2_bucket(block_tokens)
        of_layer = ("full",) * int(layers) if layer_kinds is None \
            else tuple(layer_kinds)
        if len(of_layer) != int(layers) or set(of_layer) - set(KINDS):
            raise ValueError(f"layer_kinds {of_layer!r} for {layers} layers")
        self.kinds = tuple(k for k in KINDS if k in of_layer)
        self.window = None if window is None else int(window)
        if "sliding" in self.kinds and not self.window:
            raise ValueError("sliding layers need window=")
        per = max_blocks if isinstance(max_blocks, dict) \
            else {k: max_blocks for k in self.kinds}
        self.max_blocks_of = {k: int(per[k]) for k in self.kinds}
        self.scratch_of = dict(self.max_blocks_of)
        self.max_blocks = sum(self.max_blocks_of.values())
        width = int(heads) * int(head_dim)
        self._arenas: Dict[str, Tuple[jax.Array, jax.Array]] = {}
        for k in self.kinds:
            shape = (of_layer.count(k), self.max_blocks_of[k] + 1,
                     self.block_tokens, width)
            self._arenas[k] = (jnp.zeros(shape, dtype),
                               jnp.zeros(shape, dtype))
        self._free: Dict[str, List[int]] = {
            k: list(range(self.max_blocks_of[k] - 1, -1, -1))
            for k in self.kinds}
        self._tables: Dict[str, Dict[int, List[int]]] = {
            k: {} for k in self.kinds}
        self._first: Dict[int, int] = {}    # sliding: first block held
        self._lens: Dict[int, int] = {}
        self._reserved: Dict[int, int] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ bookkeeping
    def blocks_needed(self, n_tokens: int) -> int:
        return max(1, -(-int(n_tokens) // self.block_tokens))

    def table_width(self, kind: str, bucket_t: int) -> int:
        """Entries of a `kind` table in a view of `bucket_t` positions: a
        sliding row never holds more than the window's blocks and one."""
        w = bucket_t // self.block_tokens
        if kind == "sliding":
            w = min(w, self.blocks_needed(self.window) + 1)
        return w

    def blocks_of(self, rid: int) -> int:
        with self._lock:
            return sum(len(t.get(rid, ())) for t in self._tables.values())

    def length(self, rid: int) -> int:
        with self._lock:
            return self._lens.get(rid, 0)

    def held_from(self, rid: int) -> int:
        """The first position a sliding layer still holds for `rid`."""
        with self._lock:
            return self._first.get(rid, 0) * self.block_tokens

    def blocks_in_use(self, kind: Optional[str] = None) -> int:
        with self._lock:
            return sum(self.max_blocks_of[k] - len(self._free[k])
                       for k in self.kinds if kind in (None, k))

    def free_blocks(self) -> int:
        with self._lock:
            return sum(len(f) for f in self._free.values())

    def utilization(self) -> float:
        """Stored tokens / allocated block capacity (block-tail waste)."""
        with self._lock:
            used = sum(self.max_blocks_of[k] - len(self._free[k])
                       for k in self.kinds)
            if used == 0:
                return 0.0
            bt = self.block_tokens
            tokens = sum(ln - (self._first.get(rid, 0) * bt
                               if k == "sliding" else 0)
                         for k in self.kinds
                         for rid, ln in self._lens.items())
            return tokens / float(used * bt)

    def _base(self, kind: str, rid: int) -> int:
        return self._first.get(rid, 0) if kind == "sliding" else 0

    def _grab_locked(self, rid: int, upto: int) -> None:
        """Blocks so that every kind's table of `rid` covers positions
        below `upto`; every kind's or none."""
        want = -(-int(upto) // self.block_tokens)
        need = {k: want - self._base(k, rid) - len(self._tables[k][rid])
                for k in self.kinds}
        for k, n in need.items():
            if n > len(self._free[k]):
                raise KVCacheExhaustedError(
                    f"KV cache needs {n} {k} block(s), "
                    f"{len(self._free[k])} free of {self.max_blocks_of[k]} "
                    f"(block_tokens={self.block_tokens})")
        for k, n in need.items():
            self._tables[k][rid].extend(
                self._free[k].pop() for _ in range(n))

    def extend(self, rid: int, upto: int
               ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
        """Give `rid` (made here if new) the blocks for positions below
        `upto` and return, per kind, where each position from the last
        one reserved goes: ``(block, offset)``, int32 each, the
        prefill's scatter operands. Lengths stay until :meth:`advance`.
        All-or-nothing: a raise leaves no block taken, and no entry of a
        rid that was new."""
        bt = self.block_tokens
        with self._lock:
            new = rid not in self._lens
            if new:
                for k in self.kinds:
                    self._tables[k][rid] = []
            try:
                self._grab_locked(rid, upto)
            except KVCacheExhaustedError:
                if new:
                    for k in self.kinds:
                        del self._tables[k][rid]
                raise
            if new:
                self._lens[rid] = self._reserved[rid] = 0
            at = np.arange(self._reserved[rid], int(upto))
            self._reserved[rid] = int(upto)
            return {k: (np.asarray(self._tables[k][rid], np.int32)[
                at // bt - self._base(k, rid)], (at % bt).astype(np.int32))
                for k in self.kinds}

    def reserve(self, rid: int, n_tokens: int
                ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
        """:meth:`extend` for a request that must be new."""
        with self._lock:
            if rid in self._lens:
                raise ValueError(f"rid {rid} already cached")
        return self.extend(rid, n_tokens)

    def advance(self, rid: int, n_tokens: int = 1) -> None:
        """`rid`'s next `n_tokens` slots now hold what the device wrote
        there. A sliding table gives back each leading block whose every
        position is now `window` or more behind the length: no later
        query can see it."""
        bt = self.block_tokens
        with self._lock:
            self._lens[rid] += int(n_tokens)
            if "sliding" not in self.kinds:
                return
            table = self._tables["sliding"][rid]
            first = self._first.get(rid, 0)
            while table and (first + 1) * bt - 1 <= \
                    self._lens[rid] - self.window:
                self._free["sliding"].append(table.pop(0))
                first += 1
            self._first[rid] = first

    def free(self, rid: int) -> None:
        """Return `rid`'s blocks to the free lists (idempotent). What
        they held stays in the arenas; a reader masks it by length."""
        with self._lock:
            for k in self.kinds:
                self._free[k].extend(self._tables[k].pop(rid, ()))
            for d in (self._lens, self._reserved, self._first):
                d.pop(rid, None)

    # ---------------------------------------------------------- the step's view
    def context(self, rid: int, widths: Dict[str, int]
                ) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
        """What a later chunk of `rid`'s prompt reads of the chunks
        before it: per kind the table, padded with the scratch block to
        `widths[kind]` entries, and the position its first entry starts
        at."""
        with self._lock:
            tables, starts = {}, {}
            for k in self.kinds:
                held = self._tables[k][rid]
                t = np.full((widths[k],), self.scratch_of[k], np.int32)
                t[:len(held)] = held
                tables[k] = t
                starts[k] = self._base(k, rid) * self.block_tokens
        return tables, starts

    def batch_view(self, rids: Sequence[int], bucket_t: int,
                   rows: Optional[int] = None
                   ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray],
                              np.ndarray, Dict[int, BaseException]]:
        """Describe one decode step's view: per kind the block table
        int32 ``[rows, table_width(kind, bucket_t)]``; for the sliding
        kind the position each row's table starts at, int32 ``[rows]``;
        the valid lengths int32 ``[rows]``; and the rids that could not
        be given room. A row whose tail block is full grows by one block
        of each kind that needs it here, before the launch; a failed
        grow takes nothing, leaves the row's tables intact and names the
        rid in the last result. Such a row, every row past ``len(rids)``
        and every table entry past a row's own blocks point at the
        scratch block, at length 0. ``bucket_t`` must be a block
        multiple >= every row's length + 1 (room for the token the step
        is about to scatter in)."""
        bt = self.block_tokens
        if bucket_t % bt:
            raise ValueError(
                f"view bucket {bucket_t} not a multiple of "
                f"block_tokens {bt}")
        n = len(rids) if rows is None else int(rows)
        tables = {k: np.full((n, self.table_width(k, bucket_t)),
                             self.scratch_of[k], np.int32)
                  for k in self.kinds}
        starts = {k: np.zeros((n,), np.int32)
                  for k in self.kinds if k == "sliding"}
        lens = np.zeros((n,), np.int32)
        starved: Dict[int, BaseException] = {}
        with self._lock:
            for i, rid in enumerate(rids):
                ln = self._lens[rid]
                try:
                    self._grab_locked(rid, ln + 1)
                except KVCacheExhaustedError as e:
                    starved[rid] = e
                    continue
                for k in self.kinds:
                    held = self._tables[k][rid]
                    tables[k][i, :len(held)] = held
                if starts:
                    starts["sliding"][i] = self._base("sliding", rid) * bt
                lens[i] = ln
        return tables, starts, lens, starved

    def arenas(self) -> Dict[str, Tuple[jax.Array, jax.Array]]:
        """Per kind the K and V arrays as they stand, to read."""
        return dict(self._arenas)

    def update(self, fn: Callable) -> tuple:
        """Lend the arenas to ``fn(arenas) -> (*results, arenas)``, an
        executable that donates them, rebind to what it hands back, and
        return the results."""
        *results, self._arenas = fn(self._arenas)
        return tuple(results)


# ---------------------------------------------------------------------------
# Transformer arm: a self-contained causal LM whose block is a configuration
# ---------------------------------------------------------------------------
def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _sinusoid(pos, d_model):
    """Sinusoidal position encoding from int positions [..., t] →
    [..., t, d_model] (d_model even)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rope_inv_freq(head_dim: int, rope: Dict[str, Any]
                  ) -> Tuple[np.ndarray, float]:
    """A rotary table's inverse frequencies float32 ``[head_dim / 2]``
    and the factor its cos and sin are multiplied by, from settings as
    a published config gives them: ``rope_theta``, and for ``rope_type``
    ``yarn`` also ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow`` and ``attention_factor`` (default ``0.1
    ln factor + 1``). YaRN (Peng et al., arXiv:2309.00071) leaves the
    fast dimensions as they are, divides the slow ones by ``factor`` and
    blends linearly between the two correction dimensions; the table is
    static, it does not depend on a sequence's length."""
    theta, half = float(rope["rope_theta"]), head_dim // 2
    i = np.arange(half, dtype=np.float64)
    inv = theta ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") == "default":
        return inv.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    s, l0 = float(rope["factor"]), rope["original_max_position_embeddings"]

    def dim(rotations):   # the dimension that turns `rotations` times in l0
        return head_dim * math.log(l0 / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim(rope.get("beta_slow", 1))), head_dim - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv * (1.0 - r) + inv / s * r
    return inv.astype(np.float32), float(
        rope.get("attention_factor") or 0.1 * math.log(s) + 1.0)


class TransformerDecoder:
    """A pre-norm causal transformer LM whose block is a configuration.

    One block definition serves every setting; each has two choices and
    the defaults are the repo's first decoder (pre-LayerNorm, ReLU,
    sinusoidal positions, float32, as many KV heads as query heads,
    every layer alike, tied head), which OPT's sizes are a setting of:

    * ``norm``: ``"layer"`` (scale and bias) | ``"rms"`` (scale, in
      float32, ``norm_eps``)
    * ``position``: ``"sinusoid"`` added to the embedding | ``"rotary"``
      on q and k (rotate-half), one table per kind of layer from
      ``rope[kind]`` (:func:`rope_inv_freq`: plain or YaRN)
    * ``mlp``: ``"relu"`` dense of width ``ff`` with biases | ``"moe"``:
      ``experts`` SwiGLU experts of width ``ff``, ``experts_per_token``
      a token, of which this model holds ``experts_held`` (default all)
      and computes their part (ops/moe.py)
    * ``kv_heads`` (query head n reads KV head ``n // (heads /
      kv_heads)``), ``d_model`` (default ``heads × head_dim``), ``tied``
      or an own ``head`` matrix, ``dtype`` of parameters, activations
      and cache (products accumulate in float32; norms, softmax and the
      router run in float32)
    * ``layer_types``: the pattern of kinds, repeated over the layers:
      ``"full"`` attention or ``"sliding"`` (position i sees j <= i with
      i - j < ``window``)
    * ``row_buckets``: ``"pow2"`` step signatures, or ``"full"``: every
      step padded to the engine's row limit (a step that costs its
      weights whatever its rows needs one signature a table width)

    Weights are drawn on the device, one jitted call a layer from
    ``fold_in(key, layer)``, normal(0, ``init_std``) in ``dtype``; with
    ``params=`` a ready tree is taken and nothing is drawn.

    Two jitted pure functions are the serving surface. Both take the
    paged cache's arenas (``{kind: (K, V)}``, layer-major ``[layers of
    the kind, blocks + 1, block_tokens, kv_heads × head_dim]``) as
    DONATED operands, write the new K/V into them in place, pick the
    greedy token on the device and hand the arenas back — the cache
    rebinds to them (``PagedKVCache.update``), and only int32 tokens,
    bool flags and three routing sums cross the link:

    * ``prefill(tokens[T], seg[T], pos[T], arenas, slots, ctx_tables,
      ctx_starts, ctx_len, last[W])`` — one CHUNK: a packed row of
      whole prompts and at most one later slice of a long prompt, which
      is segment 1 and sees its ``ctx_len`` cached positions through
      ``ctx_tables[kind]`` (first entry at position
      ``ctx_starts[kind]``). Padding is segment 0. Position ``i``'s K/V
      go to ``slots[kind] = (blk[T], off[T])`` (pads point at the
      scratch block); the pick and the finite flag are taken at the
      positions ``last``. → ``(token [W], finite [W], arenas)``.
    * ``step(tok[b], pos[b], arenas, tables, starts, lens[b])`` — one
      token per row through ``paged_decode_attention``, which reads
      each row's blocks through ``tables[kind][b, w]`` (a sliding
      table's first entry at position ``starts[kind][b]``); the new K/V
      are scattered to the slot of position ``lens``. → ``(token [b],
      finite [b], routing sums int32 [3] or None, arenas)``.

    ``logits(tokens, seg, pos)`` is the plain forward of the same
    packed row with no cache: what ``naive_generate``, the tests and
    chip_smoke.py compare the served path with.

    All close over static geometry only; params ride as an argument,
    so a hot-swap is a tree assignment, never a recompile.
    """

    def __init__(self, *, vocab: int = 128, layers: int = 2,
                 heads: int = 2, head_dim: int = 8, ff: int = 64,
                 max_context: int = 128, seed: int = 0,
                 d_model: Optional[int] = None,
                 kv_heads: Optional[int] = None, norm: str = "layer",
                 norm_eps: float = 1e-6, position: str = "sinusoid",
                 rope: Optional[Dict[str, Dict[str, Any]]] = None,
                 mlp: str = "relu", experts: int = 0,
                 experts_per_token: int = 0,
                 experts_held: Optional[Sequence[int]] = None,
                 tied: bool = True, dtype=jnp.float32,
                 layer_types: Sequence[str] = ("full",),
                 window: Optional[int] = None, init_std: float = 0.08,
                 row_buckets: str = "pow2", params=None):
        self.vocab, self.n_layers = int(vocab), int(layers)
        self.heads, self.head_dim = int(heads), int(head_dim)
        self.kv_heads = self.heads if kv_heads is None else int(kv_heads)
        self.d_model = self.heads * self.head_dim if d_model is None \
            else int(d_model)
        self.ff = int(ff)
        self.max_context = int(max_context)
        self.norm, self.norm_eps = norm, float(norm_eps)
        self.position, self.mlp = position, mlp
        self.experts, self.top_k = int(experts), int(experts_per_token)
        self.experts_held = tuple(range(self.experts)) \
            if experts_held is None else tuple(experts_held)
        self.tied, self.dtype = bool(tied), jnp.dtype(dtype)
        self.layer_types = tuple(layer_types)
        self.window = None if window is None else int(window)
        self.init_std, self.row_buckets = float(init_std), row_buckets
        if norm not in ("layer", "rms") or mlp not in ("relu", "moe") \
                or position not in ("sinusoid", "rotary") \
                or row_buckets not in ("pow2", "full") \
                or set(self.layer_types) - set(KINDS):
            raise ValueError("unknown decoder setting")
        if self.heads % self.kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        if position == "sinusoid" and self.d_model % 2:
            raise ValueError("d_model must be even for the sinusoidal "
                             "position encoding")
        if "sliding" in self.layer_types and not self.window:
            raise ValueError("sliding layers need window=")
        if mlp == "moe" and not 0 < self.top_k <= self.experts:
            raise ValueError("moe needs experts >= experts_per_token > 0")
        self._rope = {} if position != "rotary" else {
            k: rope_inv_freq(self.head_dim, rope[k])
            for k in set(self.layer_types)}
        # Pool duck-compat: swap/describe read these on every entry.
        self.iteration = 0
        self.epoch = 0
        self.state_tree: Dict[str, Any] = {}
        self.params_tree: Dict[str, Any] = \
            self._draw(seed) if params is None else params
        self._logits_fn = jax.jit(self._logits_pure)
        self._prefill_fn = jax.jit(self._prefill_pure, donate_argnums=(4,))
        self._step_fn = jax.jit(self._step_pure, donate_argnums=(3,))

    # ---------------------------------------------------------------- geometry
    def kind_of(self, layer: int) -> str:
        return self.layer_types[layer % len(self.layer_types)]

    def layer_kinds(self) -> List[str]:
        """Each layer's kind, as the cache wants them."""
        return [self.kind_of(li) for li in range(self.n_layers)]

    def _slot_in_kind(self, layer: int) -> int:
        """Which layer of its kind's arena `layer` is."""
        return self.layer_kinds()[:layer].count(self.kind_of(layer))

    # ----------------------------------------------------------------- weights
    def _leaf_shapes(self) -> Dict[str, tuple]:
        d, f = self.d_model, self.ff
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
        if self.mlp == "relu":
            shapes.update(w1=(d, f), w2=(f, d))
        else:
            n = len(self.experts_held)
            shapes.update(wr=(d, self.experts), wg=(n, d, f), wu=(n, d, f),
                          wd=(n, f, d))
        return shapes

    def _draw(self, seed: int) -> Dict[str, Any]:
        dt, std, d = self.dtype, self.init_std, self.d_model
        normal = lambda k, shape: (
            jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
        ones, zeros = jnp.ones((d,), dt), jnp.zeros((d,), dt)

        @jax.jit
        def layer(key):
            lp = {name: normal(jax.random.fold_in(key, j), shape)
                  for j, (name, shape) in
                  enumerate(self._leaf_shapes().items())}
            lp.update(ln1_s=ones, ln2_s=ones)
            if self.norm == "layer":
                lp.update(ln1_b=zeros, ln2_b=zeros)
            if self.mlp == "relu":
                lp.update(b1=jnp.zeros((self.ff,), dt), b2=zeros)
            return lp

        key = jax.random.PRNGKey(seed)
        tree = {"emb": jax.jit(lambda k: normal(k, (self.vocab, d)))(
                    jax.random.fold_in(key, 0)),
                "lnf_s": ones,
                "layers": [layer(jax.random.fold_in(key, li + 1))
                           for li in range(self.n_layers)]}
        if self.norm == "layer":
            tree["lnf_b"] = zeros
        if not self.tied:
            tree["head"] = jax.jit(lambda k: normal(k, (d, self.vocab)))(
                jax.random.fold_in(key, self.n_layers + 1))
        return tree

    # ----------------------------------------------------------- pure forward
    def _mm(self, a, w):
        return jnp.dot(a, w, preferred_element_type=jnp.float32
                       ).astype(self.dtype)

    def _norm(self, x, p, name):
        if self.norm == "rms":
            return _rms_norm(x, p[name + "_s"], self.norm_eps)
        return _layer_norm(x, p[name + "_s"], p[name + "_b"])

    def _embed(self, params, tok, pos):
        x = params["emb"][tok]
        if self.position == "sinusoid":
            x = x + _sinusoid(pos, self.d_model).astype(x.dtype)
        return x

    def _rotate(self, a, pos, kind):
        """Rotary position on a [t, heads, head_dim], rotate-half."""
        inv, factor = self._rope[kind]
        ang = pos[:, None].astype(jnp.float32) * inv
        cos = jnp.tile(jnp.cos(ang), 2)[:, None, :] * factor
        sin = jnp.tile(jnp.sin(ang), 2)[:, None, :] * factor
        a32 = a.astype(jnp.float32)
        lo, hi = jnp.split(a32, 2, axis=-1)
        return (a32 * cos + jnp.concatenate([-hi, lo], -1) * sin
                ).astype(a.dtype)

    def _block(self, x, lp, li, pos, attend, valid):
        """One layer on x [t, d_model]. ``attend(q [t, heads, head_dim],
        k, v [t, kv_heads × head_dim]) -> [t, heads, head_dim]`` is the
        caller's: a chunk over itself and its context, or a step over
        the paged cache. -> (x, the layer's routing sums or None)."""
        kind, t = self.kind_of(li), x.shape[0]
        with jax.named_scope(f"layer_{li}/attn_{kind}"):
            h = self._norm(x, lp, "ln1")
            q = self._mm(h, lp["wq"]).reshape(t, self.heads, self.head_dim)
            k, v = self._mm(h, lp["wk"]), self._mm(h, lp["wv"])
            if self.position == "rotary":
                q = self._rotate(q, pos, kind)
                k = self._rotate(k.reshape(t, self.kv_heads, self.head_dim),
                                 pos, kind).reshape(k.shape)
            a = attend(q, k, v)
            x = x + self._mm(a.reshape(t, -1), lp["wo"])
        if self.mlp == "relu":
            with jax.named_scope(f"layer_{li}/mlp"):
                h = self._norm(x, lp, "ln2")
                h = jnp.maximum(self._mm(h, lp["w1"]) + lp["b1"], 0.0)
                return x + self._mm(h, lp["w2"]) + lp["b2"], None
        with jax.named_scope(f"layer_{li}/moe/route"):
            h = self._norm(x, lp, "ln2")
            w, idx = moe.route(h, lp["wr"], self.top_k)
        with jax.named_scope(f"layer_{li}/moe/experts"):
            y, sums = moe.expert_ffn(
                h, w, idx, lp["wg"], lp["wu"], lp["wd"],
                n_experts=self.experts, experts_held=self.experts_held,
                valid=valid)
        return x + y, sums

    def _head(self, params, x):
        """Final norm and unembedding: [..., d_model] → float32 logits."""
        with jax.named_scope("head"):
            x = self._norm(x, params, "lnf")
            w = params["emb"].T if self.tied else params["head"]
            return jnp.dot(x, w, preferred_element_type=jnp.float32)

    def _pick(self, params, x):
        """The greedy pick of x [n, d_model] → (first index of the
        largest logit int32 [n], all logits finite bool [n])."""
        logits = self._head(params, x)
        with jax.named_scope("head"):
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    jnp.isfinite(logits).all(axis=-1))

    def _scatter(self, arenas, slots, new):
        """new[kind] = (K, V lists, a layer of the kind each, [n, kv
        width]) into slot (blk[i], off[i]) of each layer of the kind.
        The layer is an index like the other two, so the three indexed
        axes are the arena's leading ones and the update is a plain row
        write (with the layer as a window axis the compiler re-lays the
        whole arena out around the scatter)."""
        out = {}
        with jax.named_scope("kv_scatter"):
            for kind, (ak, av) in arenas.items():
                blk, off = slots[kind]
                ks, vs = (jnp.stack(a) for a in new[kind])
                layer = jnp.arange(ks.shape[0])[:, None]
                out[kind] = (ak.at[layer, blk, off].set(ks),
                             av.at[layer, blk, off].set(vs))
        return out

    def _chunk_forward(self, params, tokens, seg, pos, context=None):
        """tokens/seg/pos [T] → (hidden [T, d_model] before the final
        norm, {kind: (K list, V list)} a layer each [T, kv width]).
        `context` = (arenas, ctx_tables, ctx_starts, ctx_len): segment
        1 also sees its cached positions below ctx_len.

        Causality inside the packed row is exact under the row's own
        order and segment equality; the context's keys lie before the
        row on that line, at their true distance from segment 1, so the
        window test holds across the seam. A cached position at or past
        ctx_len is no key: segment -1, its value zeroed (a freed block
        keeps its last owner's data)."""
        t = tokens.shape[0]
        line = jnp.arange(t, dtype=jnp.int32)
        valid = seg > 0
        with jax.named_scope("embed"):
            x = self._embed(params, tokens, pos)
        new = {k: ([], []) for k in set(self.layer_kinds())}
        for li, lp in enumerate(params["layers"]):
            kind, at = self.kind_of(li), self._slot_in_kind(li)

            def attend(q, k, v, kind=kind, at=at):
                new[kind][0].append(k)
                new[kind][1].append(v)
                kv_pos, kv_seg = line, seg
                if context is not None:
                    arenas, tables, starts, ctx_len = context
                    ak, av = arenas[kind]
                    n = tables[kind].shape[0] * ak.shape[2]
                    true = starts.get(kind, 0) + jnp.arange(n,
                                                            dtype=jnp.int32)
                    real = true < ctx_len
                    with jax.named_scope("kv_context"):
                        ck = ak[at][tables[kind]].reshape(n, -1)
                        cv = jnp.where(real[:, None], av[at][
                            tables[kind]].reshape(n, -1), 0)
                    k, v = jnp.concatenate([ck, k]), jnp.concatenate([cv, v])
                    kv_pos = jnp.concatenate([jnp.where(
                        real, true - ctx_len, jnp.int32(1 << 30)), line])
                    kv_seg = jnp.concatenate([jnp.where(real, 1, -1), seg])
                heads = lambda a: a.reshape(a.shape[0], self.kv_heads,
                                            self.head_dim)
                return prefill_attention(
                    q, heads(k), heads(v), q_pos=line, kv_pos=kv_pos,
                    q_seg=seg, kv_seg=kv_seg,
                    window=self.window if kind == "sliding" else None,
                    name=f"prefill_attention_{kind}")

            x, _ = self._block(x, lp, li, pos, attend, valid)
        return x, new

    def _logits_pure(self, params, tokens, seg, pos):
        """tokens/seg/pos [1, T] → logits [1, T, vocab]."""
        return self._head(params, self._chunk_forward(
            params, tokens[0], seg[0], pos[0])[0])[None]

    def _prefill_pure(self, params, tokens, seg, pos, arenas, slots,
                      ctx_tables, ctx_starts, ctx_len, last):
        """One chunk into the cache. → (token int32 [W], finite bool
        [W], arenas)."""
        x, new = self._chunk_forward(
            params, tokens, seg, pos,
            (arenas, ctx_tables, ctx_starts, ctx_len))
        arenas = self._scatter(arenas, slots, new)
        tok, finite = self._pick(params, x[last])
        return tok, finite, arenas

    def _step_pure(self, params, tok, pos, arenas, tables, starts, lens):
        """One decode step. tok/pos/lens [b]; tables[kind] [b, w] →
        (token int32 [b], finite bool [b], routing sums int32 [3] or
        None, arenas)."""
        b = tok.shape[0]
        zeros = jnp.zeros((b,), jnp.int32)
        valid = lens > 0            # a pad row: no expert computes it
        with jax.named_scope("embed"):
            x = self._embed(params, tok, pos)               # [b, d]
        new = {k: ([], []) for k in arenas}
        sums = []
        for li, lp in enumerate(params["layers"]):
            kind, at = self.kind_of(li), self._slot_in_kind(li)

            def attend(q, k, v, kind=kind, at=at):
                new[kind][0].append(k)
                new[kind][1].append(v)
                return paged_decode_attention(
                    q, k, v, *arenas[kind], at, tables[kind],
                    starts.get(kind, zeros), lens,
                    window=self.window if kind == "sliding" else None,
                    name=f"decode_attention_{kind}")

            x, s = self._block(x, lp, li, pos, attend, valid)
            sums.append(s)
        # One scatter per arena, after every layer has read it: the
        # donated buffer's last use, so it is updated where it lies.
        slots = {}
        for kind, (ak, _) in arenas.items():
            at = lens - starts.get(kind, zeros)
            bt = ak.shape[2]
            slots[kind] = (tables[kind][jnp.arange(b), at // bt], at % bt)
        arenas = self._scatter(arenas, slots, new)
        picked, finite = self._pick(params, x)
        sums = None if sums[0] is None else sum(sums[1:], sums[0])
        return picked, finite, sums, arenas

    # ------------------------------------------------------------ conveniences
    def logits(self, tokens, seg, pos):
        return self._logits_fn(self.params_tree,
                               np.asarray(tokens, np.int32),
                               np.asarray(seg, np.int32),
                               np.asarray(pos, np.int32))

    def prefill(self, tokens, seg, pos, arenas, slots, ctx_tables,
                ctx_starts, ctx_len, last):
        return self._prefill_fn(self.params_tree, tokens, seg, pos, arenas,
                                slots, ctx_tables, ctx_starts,
                                np.int32(ctx_len), last)

    def step(self, tok, pos, arenas, tables, starts, lens):
        return self._step_fn(self.params_tree, tok, pos, arenas, tables,
                             starts, lens)


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
    """Token-family adapter: chunked packed prefill + paged-KV decode
    steps.

    The adapter owns nothing on the device: each call lends the cache's
    arenas to the model's executable and rebinds the cache to what comes
    back. What crosses the link is a step's tokens, positions, block
    tables and lengths going up and one picked token and one finite flag
    a row (and a sparse model's three routing sums) coming back
    (``serving_decode_h2d|d2h_bytes_total`` count exactly those). Every
    served token passes through :meth:`_greedy` on the host.

    A prompt is prefilled in CHUNKS of ``pack_bucket`` positions through
    one signature: a chunk is a packed row of whole prompts and at most
    one later slice of a prompt longer than a chunk, which reads the
    slices before it from the cache (:meth:`pack_groups`). Only a
    prompt's last slice yields its first token.

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
                 *, pack_bucket: int = 64, check_finite: bool = True,
                 max_rows: Optional[int] = None):
        self.model = model
        self.cache = cache
        self.pack_bucket = next_pow2_bucket(pack_bucket)
        if self.pack_bucket > model.max_context:
            raise ValueError("pack_bucket exceeds model max_context")
        self.check_finite = bool(check_finite)
        self.max_rows = None if max_rows is None else int(max_rows)
        # a chunk finishes at most as many prompts as the engine admits
        self.last_width = self.pack_bucket if max_rows is None \
            else min(self.pack_bucket, next_pow2_bucket(max_rows))
        bt = cache.block_tokens
        self._kv_cap = -(-(model.max_context + 1) // bt) * bt
        self._ctx_widths = {k: cache.table_width(k, self._kv_cap)
                            for k in cache.kinds}
        self._prefilling: Dict[int, int] = {}   # rid -> positions cached
        self._link = _register_link_metrics()
        self._count = _register_model_metrics()

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
        return p.astype(np.int32)

    def max_total_len(self, prompt_len: int, max_new: int) -> int:
        return prompt_len + max_new

    # ---------------------------------------------------------------- prefill
    def pack_groups(self, items: List[Tuple[int, np.ndarray]]
                    ) -> List[List[Tuple[int, np.ndarray, int, bool]]]:
        """Split (rid, prompt) pairs into chunks of at most
        ``pack_bucket`` positions, in the order they must run. A piece
        is ``(rid, tokens, position of its first token, is the prompt's
        last)``. A prompt longer than a chunk gives whole chunks of its
        own and a last piece; the pieces are packed greedy first-fit, a
        piece with slices before it first in its chunk (one a chunk: it
        alone reads the cache) and in no chunk before its own slices."""
        pb = self.pack_bucket
        groups: List[List[Tuple[int, np.ndarray, int, bool]]] = []
        fill: List[int] = []
        for rid, p in items:
            lo = 0
            while p.size - lo > pb:
                groups.append([(rid, p[lo:lo + pb], lo, False)])
                fill.append(pb)
                lo += pb
            piece = (rid, p[lo:], lo, True)
            after = len(groups) if lo else 0
            for gi in range(after, len(groups)):
                if fill[gi] + piece[1].size <= pb and not (
                        lo and groups[gi][0][2]):
                    groups[gi].insert(0, piece) if lo \
                        else groups[gi].append(piece)
                    fill[gi] += piece[1].size
                    break
            else:
                groups.append([piece])
                fill.append(piece[1].size)
        return groups

    def prefill_group(self, group) -> Tuple[Dict[int, int],
                                            Dict[int, BaseException]]:
        """Prefill ONE chunk: give each piece its KV blocks, splice the
        pieces into a segment-masked row and run the chunk's forward,
        which writes their K/V into those blocks. A ``(rid, prompt)``
        pair stands for a whole prompt. Returns ``({rid: first token} of
        the prompts this chunk finished, {rid: error})`` — a rid whose
        blocks cannot be granted lands in the failure dict (typed
        KVCacheExhaustedError), gives back what it held and stays out
        of the row, without costing groupmates theirs; a later piece of
        a prompt that has failed is passed over."""
        pb, cache = self.pack_bucket, self.cache
        row = np.zeros((pb,), np.int32)
        seg = np.zeros((pb,), np.int32)
        pos = np.zeros((pb,), np.int32)
        slots = {k: (np.full((pb,), cache.scratch_of[k], np.int32),
                     np.zeros((pb,), np.int32)) for k in cache.kinds}
        ctx_tables = {k: np.full((w,), cache.scratch_of[k], np.int32)
                      for k, w in self._ctx_widths.items()}
        ctx_starts = {k: np.int32(0) for k in cache.kinds if k == "sliding"}
        ctx_len = 0
        last = np.zeros((self.last_width,), np.int32)
        out: Dict[int, int] = {}
        fails: Dict[int, BaseException] = {}
        placed: List[Tuple[int, int, int]] = []  # rid, tokens, `last` index
        cur = finals = 0
        for piece in group:
            rid, p, lo, final = piece if len(piece) == 4 \
                else (*piece, 0, True)
            if lo and self._prefilling.get(rid) != lo:
                continue            # its earlier slice failed
            if cur + p.size > pb or (lo and cur):
                raise ValueError("chunk overflows pack_bucket, or a later "
                                 "slice is not first in it")
            try:
                if lo:
                    before = cache.context(rid, self._ctx_widths)
                where = cache.extend(rid, lo + p.size)
            except KVCacheExhaustedError as e:
                fails[rid] = e
                self.free(rid)
                continue
            if lo:
                ctx_tables, starts = before
                ctx_starts = {k: np.int32(starts[k]) for k in ctx_starts}
                ctx_len = lo
            hi = cur + p.size
            for k in cache.kinds:
                slots[k][0][cur:hi], slots[k][1][cur:hi] = where[k]
            row[cur:hi] = p
            seg[cur:hi] = len(placed) + 1
            pos[cur:hi] = lo + np.arange(p.size)
            if final:
                last[finals] = hi - 1
            placed.append((rid, p.size, finals if final else -1))
            finals += final
            cur = hi
        if not placed:
            return out, fails
        self._count["chunks"].inc()
        self._count["chunk_tokens"].inc(cur)
        up = _nbytes(row, seg, pos, slots, ctx_tables, ctx_starts, last) + 4
        with tracing.span("decode/launch", cat="serve", bytes=up):
            picked, finite = cache.update(lambda a: self.model.prefill(
                row, seg, pos, a, slots, ctx_tables, ctx_starts, ctx_len,
                last))
        self._link["h2d", "prefill"].inc(up)
        with tracing.span("decode/fetch", cat="serve"):
            picked, finite = jax.device_get((picked, finite))
            down = _nbytes(picked, finite)
            tracing.annotate(bytes=down)
        self._link["d2h", "prefill"].inc(down)
        with tracing.span("decode/commit", cat="serve"):
            for rid, n_tok, i in placed:
                if i >= 0 and self.check_finite and not finite[i]:
                    self.free(rid)
                    fails[rid] = NonFiniteOutputError(
                        "prefill produced non-finite logits")
                    continue
                cache.advance(rid, n_tok)
                if i >= 0:
                    self._prefilling.pop(rid, None)
                    out[rid] = self._greedy(picked[i])
                else:
                    self._prefilling[rid] = cache.length(rid)
        return out, fails

    # ------------------------------------------------------------------- step
    def row_bucket(self, n: int) -> int:
        """Rows of the step executable for `n` live rows."""
        if self.model.row_buckets == "full" and self.max_rows:
            return next_pow2_bucket(max(n, self.max_rows))
        return next_pow2_bucket(n)

    def kv_bucket(self, rids: Sequence[int]) -> int:
        """View length for this step: room for every row's cache + the
        token being scattered, snapped to the shared pow2 rule, never
        below one block and never past the blocks of the model's longest
        context (the finite compiled grid)."""
        need = max(self.cache.length(r) for r in rids) + 1
        return min(max(self.cache.block_tokens, next_pow2_bucket(need)),
                   self._kv_cap)

    def step(self, rids: Sequence[int], last_items: Sequence[Any]
             ) -> Tuple[Dict[int, int], Dict[int, BaseException]]:
        """One iteration-level step for `rids`: rows are padded to the
        row bucket with rows of the scratch block at length 0 (a pad row
        reads and writes nothing a request owns). Returns
        ``({rid: next token}, {rid: error})``."""
        n, cache = len(rids), self.cache
        bucket = self.row_bucket(n)
        kvb = self.kv_bucket(rids)
        tracing.annotate(row_bucket=bucket, kv_bucket=kvb)
        with tracing.span("decode/gather", cat="serve"):
            tables, starts, lens, fails = cache.batch_view(rids, kvb, bucket)
            tracing.annotate(bytes=_nbytes(tables, starts, lens))
        toks = np.zeros((bucket,), np.int32)
        toks[:n] = last_items
        # `lens` goes up twice: as the positions and as the lengths
        up = _nbytes(toks, lens, tables, starts, lens)
        with tracing.span("decode/launch", cat="serve", bytes=up):
            picked, finite, sums = cache.update(lambda a: self.model.step(
                toks, lens, a, tables, starts, lens))
        self._link["h2d", "step"].inc(up)
        with tracing.span("decode/fetch", cat="serve"):
            picked, finite, sums = jax.device_get((picked, finite, sums))
            down = _nbytes(picked, finite, sums)
            tracing.annotate(bytes=down)
        self._link["d2h", "step"].inc(down)
        seen = lens[lens > 0].astype(np.int64) + 1      # with its own
        for k in cache.kinds:
            self._count["kv_tokens", k].inc(int(
                (np.minimum(seen, cache.window) if k == "sliding"
                 else seen).sum()))
            self._count["block_steps", k].inc(cache.blocks_in_use(k))
        if sums is not None:    # host integers since the fetch above
            for name, v in zip(("assignments", "touched", "peak"),
                               sums.tolist()):  # jaxlint: disable=JL102
                self._count[name].inc(v)
        out: Dict[int, int] = {}
        with tracing.span("decode/commit", cat="serve"):
            for i, rid in enumerate(rids):
                if rid in fails:
                    continue
                if self.check_finite and not finite[i]:
                    fails[rid] = NonFiniteOutputError(
                        "decode step produced non-finite logits")
                    continue
                cache.advance(rid)
                out[rid] = self._greedy(picked[i])
        return out, fails

    # ------------------------------------------------------------------ admin
    def free(self, rid: int) -> None:
        self._prefilling.pop(rid, None)
        self.cache.free(rid)

    def kv_blocks(self, rid: int) -> int:
        return self.cache.blocks_of(rid)

    def result_of(self, generated: List[int]) -> List[int]:
        return list(generated)

    def warm_signatures(self, max_rows: int, max_context: int
                        ) -> Tuple[List[int], List[int]]:
        """The finite compiled grid: row buckets (pow2, or the one full
        bucket) × KV view buckets (pow2 from one block up, then the
        blocks of the model's longest context with room for its last
        token)."""
        top = next_pow2_bucket(max_rows)
        rows, b = [], 1
        while b <= top:
            rows.append(b)
            b <<= 1
        if self.model.row_buckets == "full":
            rows = [self.row_bucket(max_rows)]
        kvs, kv = [], self.cache.block_tokens
        while kv < self._kv_cap:
            kvs.append(kv)
            kv <<= 1
        return rows, kvs + [self._kv_cap]

    def warm_steps(self, rows: int, kvs: Sequence[int]) -> None:
        """Run the step executable of `rows` rows at each view bucket
        on the live arenas (they are the only ones): every row is a pad
        row, so only the scratch blocks are written."""
        for kv in kvs:
            tables, starts, zeros, _ = self.cache.batch_view((), kv, rows)
            self.cache.update(lambda a: self.model.step(
                zeros, zeros, a, tables, starts, zeros))

    def warmup(self, max_rows: int, max_context: int) -> List[int]:
        """Precompile the chunk's prefill signature and every step
        signature in the grid. Returns the row buckets warmed."""
        pb, cache = self.pack_bucket, self.cache
        at = np.zeros((pb,), np.int32)
        slots = {k: (np.full((pb,), cache.scratch_of[k], np.int32), at)
                 for k in cache.kinds}
        ctx = {k: np.full((w,), cache.scratch_of[k], np.int32)
               for k, w in self._ctx_widths.items()}
        starts = {k: np.int32(0) for k in cache.kinds if k == "sliding"}
        cache.update(lambda a: self.model.prefill(
            at, at, at, a, slots, ctx, starts, 0,
            np.zeros((self.last_width,), np.int32)))
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
        """Prefill `live` chunk by chunk, one decode step of the active
        set between chunks (a row's token gap is bounded by one chunk,
        not by a newcomer's whole prompt); the number that joined the
        active set."""
        admitted = 0
        by_rid = {r.rid: r for r in live}
        for gi, group in enumerate(self.adapter.pack_groups(
                [(r.rid, r.prompt) for r in live])):
            reqs = [by_rid[piece[0]] for piece in group
                    if not by_rid[piece[0]].event.is_set()]
            if not reqs:
                continue        # every prompt of the chunk has failed
            if gi and self._active:
                self._step_once()
            for piece in group:     # a prompt's first piece ends its wait
                r = by_rid[piece[0]]
                if r.trace is not None and r in reqs and not (
                        len(piece) == 4 and piece[2]):
                    r.trace.mark("queue_wait")
            try:
                with self._lock, self._sched_slot(cost=float(len(group))):
                    t0 = time.perf_counter()
                    with self._span("decode/prefill", reqs, chunk=gi):
                        if tracing.is_enabled():
                            tracing.annotate(tokens=sum(
                                int(piece[1].shape[0]) for piece in group))
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
