"""Compile-cost control plane: persistent XLA cache + AOT dispatch.

Every jitted function in the stack recompiles from scratch in every
process — minutes of wall clock for the conv zoo before the first batch
runs. This module attacks that cost on two fronts:

* **Persistent compilation cache** — wires `jax_compilation_cache_dir`
  (`JAX_COMPILATION_CACHE_DIR` where the environment sets it, else the
  fixed in-checkout `.jax_cache/`) with the persistence thresholds
  dropped to zero so every executable is cached, and mirrors jax's
  cache-hit/miss monitoring events into the MetricsRegistry
  (`compile_cache_hits_total` / `_misses_total`) so warm vs cold
  compiles are visible in `/metrics`. A warm cache
  turns a minutes-long cold compile into a sub-second deserialize.

* **AOT precompile dispatch** — `PrecompiledDispatch` wraps one
  `jax.jit` callable and routes calls whose argument signature matches
  an executable precompiled via `jit.lower(ShapeDtypeStruct...).compile()`
  straight to that executable: no re-trace, no cache lookup, zero XLA
  compilations on the critical path. `MultiLayerNetwork.precompile()` /
  `ComputationGraph.precompile()` build these ahead of the first batch.

Note the counting subtlety this design answers: jax's
`backend_compile_duration` event (what `xla_compilations_total` counts)
wraps `compile_or_get_cached`, so it fires even on a PERSISTENT-cache
hit. Only the AOT dispatch path makes a step truly compile-silent —
which is why `precompile()` stores executables instead of merely
warming the disk cache.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

log = logging.getLogger(__name__)

# The cache directory is placed from OUTSIDE the program: where
# JAX_COMPILATION_CACHE_DIR is set the cache is there and nothing here
# sets another; where it is not, it is this one fixed directory inside
# the checkout (git-ignored). The path is part of the cache key's
# neighbourhood — a directory that moves between runs (a temp dir, a
# pid, a timestamp) never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_enabled_dir: Optional[str] = None
_listening = False

_HIT_EVENT_SUFFIX = "compilation_cache/cache_hits"
_MISS_EVENT_SUFFIX = "compilation_cache/cache_misses"


def _registry():
    from .metrics import registry
    return registry()


def _hit_counter():
    return _registry().counter(
        "compile_cache_hits_total",
        "Persistent XLA compilation cache hits (jax monitoring)")


def _miss_counter():
    return _registry().counter(
        "compile_cache_misses_total",
        "Persistent XLA compilation cache misses (jax monitoring)")


def _on_event(event: str, **_kw) -> None:
    if event.endswith(_HIT_EVENT_SUFFIX):
        _hit_counter().inc()
    elif event.endswith(_MISS_EVENT_SUFFIX):
        _miss_counter().inc()


def _ensure_listener() -> None:
    global _listening
    if _listening:
        return
    with _lock:
        if _listening:
            return
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        # Touch both families so a scrape sees them at 0 before the
        # first compile, making "no hits yet" distinguishable from
        # "counters never wired".
        _hit_counter()
        _miss_counter()
        _listening = True


def _reset_jax_cache_latch() -> None:
    """jax decides cache-on/off ONCE per process, at the first
    compilation (`compilation_cache.is_cache_used` latches
    `_cache_checked`). Any compile before `enable()` therefore latches
    the cache OFF for the whole process — silently. reset_cache() clears
    the latch."""
    from jax._src import compilation_cache
    compilation_cache.reset_cache()


def resolve_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable() -> str:
    """Turn the persistent compilation cache on; returns the directory
    (see resolve_cache_dir for where it is).

    Drops jax's persistence thresholds (min compile time / min entry
    size) to zero so even the small jits this framework builds by the
    dozen are persisted: a serving warmup is dozens of sub-second
    compiles, and the CPU cache smoke needs those cached too."""
    global _enabled_dir
    import jax

    d = resolve_cache_dir()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache_latch()
    _ensure_listener()
    with _lock:
        _enabled_dir = d
    _registry().gauge(
        "compile_cache_enabled",
        "1 when the persistent XLA compilation cache is wired").set(1)
    log.info("persistent XLA compilation cache enabled at %s", d)
    return d


def disable() -> None:
    """Detach the persistent cache (the monitoring listener stays; it
    only counts)."""
    global _enabled_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache_latch()  # un-latch "cache in use" too
    with _lock:
        _enabled_dir = None
    _registry().gauge(
        "compile_cache_enabled",
        "1 when the persistent XLA compilation cache is wired").set(0)


def status() -> Dict[str, Any]:
    """{enabled, dir, entries, bytes, hits, misses} — entries/bytes from
    a directory scan (cheap: one readdir), hits/misses from the
    registry counters."""
    with _lock:
        d = _enabled_dir
    entries = 0
    size = 0
    if d and os.path.isdir(d):
        try:
            for name in os.listdir(d):
                if name.endswith("-cache"):
                    entries += 1
                    try:
                        size += os.path.getsize(os.path.join(d, name))
                    except OSError:
                        pass
        except OSError:
            pass
    return {
        "enabled": d is not None,
        "dir": d,
        "entries": entries,
        "bytes": size,
        "hits": int(_hit_counter().value()),
        "misses": int(_miss_counter().value()),
    }


# ---------------------------------------------------------------------------
# AOT precompile dispatch
# ---------------------------------------------------------------------------
def _is_tracer(x) -> bool:
    try:
        import jax
        return isinstance(x, jax.core.Tracer)
    except Exception:
        return False


def call_signature(args: Sequence[Any],
                   static_argnums: Tuple[int, ...] = ()) -> Optional[tuple]:
    """Hashable signature of a call: pytree structure + per-leaf
    (shape, dtype, weak_type) + static argument values. Shape metadata
    only — never touches device values. Returns None when any leaf is a
    tracer (a transform is tracing through us: AOT executables cannot
    run under trace) or carries no shape/dtype."""
    import jax
    dynamic = tuple(a for i, a in enumerate(args)
                    if i not in static_argnums)
    statics = tuple(args[i] for i in static_argnums if i < len(args))
    leaves, treedef = jax.tree_util.tree_flatten(dynamic)
    sig = []
    for leaf in leaves:
        if _is_tracer(leaf):
            return None
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return None
        sig.append((tuple(shape), str(dtype),
                    bool(getattr(leaf, "weak_type", False))))
    return (treedef, tuple(sig), statics)


class PrecompiledDispatch:
    """One `jax.jit` callable plus a table of AOT-precompiled
    executables keyed by call signature.

    Calls whose signature matches run the stored executable directly —
    no trace, no lowering, no compile-cache lookup, zero
    `backend_compile` events. Everything else falls through to the jit
    untouched (first call traces+compiles as usual). Donation semantics
    are identical on both paths (the executable was lowered from the
    same jit, donate_argnums included).

    Transform-safe: when a wrapper (ParallelWrapper's vmap,
    SequenceParallelWrapper's re-jit) traces through this object, the
    tracer leaves force the jit path, so an AOT executable can never be
    invoked under trace.
    """

    def __init__(self, jit_fn, label: str,
                 static_argnums: Tuple[int, ...] = ()):
        self._jit = jit_fn
        self.label = label
        self._static_argnums = tuple(static_argnums)
        self._execs: Dict[tuple, Any] = {}
        self._warned_fallback = False

    # -- jax.jit surface the rest of the stack relies on ------------------
    @property
    def jit(self):
        """The wrapped jit — callers that KNOW their inputs carry a
        placement the AOT executables were not lowered for (the
        mesh-sharded DP step) dispatch here directly."""
        return self._jit

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        """Executable-cache size of the underlying jit (the
        telemetry.jit_cache_size probe contract). AOT executables live
        beside the jit cache, not in it."""
        probe = getattr(self._jit, "_cache_size", None)
        return int(probe()) if probe is not None else -1

    @property
    def aot_signatures(self) -> int:
        return len(self._execs)

    # -- AOT --------------------------------------------------------------
    def precompile(self, *abstract_args):
        """`jit.lower(...).compile()` on abstract ShapeDtypeStructs (or
        concrete arrays; only shape/dtype are read) and remember the
        executable under the call signature. Idempotent per signature."""
        key = call_signature(abstract_args, self._static_argnums)
        if key is None:
            raise ValueError(
                f"precompile({self.label}): arguments carry no static "
                "shape signature")
        if key in self._execs:
            return self._execs[key]
        compiled = self._jit.lower(*abstract_args).compile()
        self._execs[key] = compiled
        _registry().counter(
            "precompiled_signatures_total",
            "AOT-precompiled (lower+compile) executables built"
            ).labels(fn=self.label).inc()
        return compiled

    # -- dispatch ---------------------------------------------------------
    def __call__(self, *args):
        if self._execs:
            key = call_signature(args, self._static_argnums)
            exe = None if key is None else self._execs.get(key)
            if exe is not None:
                dynamic = tuple(a for i, a in enumerate(args)
                                if i not in self._static_argnums)
                try:
                    out = exe(*dynamic)
                except (TypeError, ValueError) as e:
                    # Layout/sharding drift the signature cannot see
                    # (e.g. an explicitly resharded input). Loud once,
                    # drop the executable, fall back to the jit — which
                    # handles any placement.
                    if not self._warned_fallback:
                        self._warned_fallback = True
                        log.warning(
                            "AOT executable for %s rejected its inputs "
                            "(%s); falling back to jit dispatch for "
                            "this signature", self.label, e)
                    self._execs.pop(key, None)
                    return self._jit(*args)
                _registry().counter(
                    "precompiled_dispatch_hits_total",
                    "Calls served by an AOT-precompiled executable "
                    "(zero compile work)").labels(fn=self.label).inc()
                return out
        return self._jit(*args)


def abstract_like(tree):
    """Pytree of ShapeDtypeStructs mirroring `tree`'s arrays (the
    AOT-argument builder; None leaves pass through)."""
    import jax

    def one(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

    return jax.tree_util.tree_map(one, tree)
