"""The jaxlint rule registry.

Each rule is a :class:`Rule` with a stable id, a severity, a one-line
fix hint, and a ``check(ctx)`` generator yielding ``(node, message)``
pairs. The engine turns those into findings, applies ``# jaxlint:
disable=RULE`` suppressions, and matches them against the baseline.

Rule families
-------------
* JL0xx  trace purity — impure Python inside jit-reachable code bakes
  stale values into the compiled executable.
* JL1xx  hidden host syncs — implicit device->host transfers inside hot
  paths (``fit`` / step loops / listener callbacks) that stall JAX's
  async dispatch pipeline.
* JL2xx  recompile hazards — things that change the jit cache key (or
  crash hashing) every call.
* JL3xx  buffer donation misuse.
* JL4xx  lock discipline in threaded subsystems (RacerD-style
  consistent-guard checking): JL401 consistent guards over thread entry
  points, JL402 lock-acquisition-order cycles (potential deadlocks),
  JL403 blocking calls under a held lock, JL404 field-level atomicity
  (shared attributes written under a lock but read or read-modify-
  written outside it).
* JL5xx  serving discipline: JL501 typed-error taxonomy at HTTP route
  handlers, JL502 metrics-family discipline (hot-path construction,
  unbounded label cardinality, missing pre-registration), JL503 fault-point chaos coverage (every
  ``faults.fire`` literal must be exercised by a test and documented).

Hotness is lexical: a function is *hot* if its name looks like a
training/step/iterator path (or a listener callback), or if it is
nested inside one. Jit-reachability comes from :mod:`.boundaries`.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Set, Tuple

from .boundaries import dotted_name

# --------------------------------------------------------------------------
# shared vocabularies
# --------------------------------------------------------------------------

#: function names considered hot paths for the host-sync rules
HOT_NAME_RE = re.compile(
    r"(^|_)(fit|train|step|batch|epoch|iterate|forward|backward|update|"
    r"pump|producer|consumer|worker|prefetch)($|_)|"
    r"^(__next__|__iter__)$")

#: listener / callback entry points whose whole body is per-step hot
CALLBACK_NAMES = {
    "iteration_done", "on_epoch_start", "on_epoch_end",
    "on_forward_pass", "on_backward_pass", "on_gradient_calculation",
    "epoch_done",
}

#: loop-index-ish receivers that float()/int() legitimately touches
_INDEXY = {
    "iteration", "epoch", "i", "j", "k", "idx", "n", "step", "step_num",
    "num_examples", "count", "batch_size", "num_batches", "total",
    "iteration_count", "epoch_count", "seed", "size", "length",
}

_TIME_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic", "time.time_ns",
    "time.perf_counter_ns", "time.monotonic_ns", "time.process_time",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}

_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "critical",
                "exception", "log"}
_LOGGERISH = re.compile(r"(^|_)(log|logger)(ger)?s?$", re.IGNORECASE)

_ARRAY_CTORS = {"array", "asarray", "ones", "zeros", "arange", "linspace",
                "full", "eye", "identity"}

_LOCKISH = re.compile(r"lock|mutex|cond|(^|_)cv($|_)|sem", re.IGNORECASE)

_SYNC_PRIMITIVE_CTORS = {"Lock", "RLock", "Condition", "Event", "Semaphore",
                         "BoundedSemaphore", "Barrier", "Queue", "LifoQueue",
                         "PriorityQueue", "SimpleQueue", "deque"}


@dataclass(frozen=True)
class Rule:
    id: str
    severity: str          # error | warning | info
    title: str
    hint: str
    check: Callable[["object"], Iterator[Tuple[ast.AST, str]]]

    def describe(self) -> dict:
        return {"id": self.id, "severity": self.severity,
                "title": self.title, "hint": self.hint}


def _name_of(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _is_self_attr(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self")


def _walk_no_nested(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function body without descending into nested defs/classes
    (their hotness / reachability is judged separately)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


# --------------------------------------------------------------------------
# JL0xx — trace purity
# --------------------------------------------------------------------------

def _check_impure_random(ctx):
    for fn in ctx.jit.reachable:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = ctx.dotted(node.func)
            if not d:
                continue
            parts = d.split(".")
            if parts[:2] == ["numpy", "random"] or (
                    parts[0] == "random" and len(parts) > 1):
                yield node, (f"call to '{d}' inside jit-reachable "
                             f"code is evaluated once at trace time, not "
                             f"per step")


def _check_impure_time(ctx):
    for fn in ctx.jit.reachable:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = ctx.dotted(node.func)
                if d in _TIME_CALLS:
                    yield node, (f"'{d}()' inside jit-reachable code is "
                                 f"frozen at trace time")


def _check_impure_io(ctx):
    for fn in ctx.jit.reachable:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id == "print":
                yield node, ("'print' inside jit-reachable code runs once "
                             "at trace time (use jax.debug.print)")
            elif isinstance(f, ast.Attribute) and f.attr in _LOG_METHODS:
                base = _name_of(f.value)
                d = ctx.dotted(f) or ""
                if d.startswith("logging.") or _LOGGERISH.search(base or ""):
                    yield node, (f"logging call '{d or base + '.' + f.attr}' "
                                 f"inside jit-reachable code runs once at "
                                 f"trace time")


def _check_trace_mutation(ctx):
    for fn in ctx.jit.reachable:
        globals_declared: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                globals_declared.update(node.names)
        for node in ast.walk(fn):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for tgt in targets:
                if _is_self_attr(tgt):
                    yield tgt, (f"write to 'self.{tgt.attr}' inside "
                                f"jit-reachable code mutates host state at "
                                f"trace time only")
                elif isinstance(tgt, ast.Name) and tgt.id in globals_declared:
                    yield tgt, (f"write to global '{tgt.id}' inside "
                                f"jit-reachable code happens at trace time "
                                f"only")


def _static_param_names(ctx, fn) -> Set[str]:
    """Parameter names marked static for this traced function — from a
    recorded jit assignment whose fn_name matches, or from a
    ``@functools.partial(jax.jit, static_argnums/static_argnames=...)``
    decorator on the function itself."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return set()
    params = [a.arg for a in fn.args.args]
    out: Set[str] = set()

    def add_positions(positions):
        for pos in positions:
            if 0 <= pos < len(params):
                out.add(params[pos])

    for asg in ctx.jit.assignments:
        if asg.fn_name == fn.name:
            add_positions(asg.static_argnums)
            out.update(asg.static_argnames)
    from .boundaries import _int_tuple, _kw, _str_tuple
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        add_positions(_int_tuple(_kw(dec, "static_argnums")))
        out.update(_str_tuple(_kw(dec, "static_argnames")))
    return out


_STATICISH_PARAMS = {"self", "train", "training", "is_training",
                     "deterministic", "mode", "axis", "axis_name",
                     "reduction"}


def _is_none_check(test: ast.AST) -> bool:
    return (isinstance(test, ast.Compare)
            and any(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops))


def _metadata_access(ctx, name_node: ast.AST) -> bool:
    """Branching on ``x.ndim`` / ``x.shape`` is branching on trace-time
    host metadata, not tracer truthiness."""
    parent = ctx.parent(name_node)
    return (isinstance(parent, ast.Attribute)
            and parent.attr in ("ndim", "shape", "dtype", "size"))


def _inside_none_check(ctx, node: ast.AST, stop: ast.AST) -> bool:
    """Is this name used under an ``is None`` / ``is not None`` compare
    somewhere inside the test expression (e.g. ``a and rng is not None``)?"""
    cur = node
    while cur is not None:
        if _is_none_check(cur):
            return True
        if cur is stop:
            return False
        cur = ctx.parent(cur)
    return False


def _check_tracer_branch(ctx):
    # Direct roots only: transitive callees are too often host helpers.
    for fn in ctx.jit.roots:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = {a.arg for a in fn.args.args} - _STATICISH_PARAMS \
            - _static_param_names(ctx, fn)
        if not params:
            continue
        for node in _walk_no_nested(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            test = node.test
            if _is_none_check(test):
                continue
            if any(isinstance(sub, ast.Call) and
                   _name_of(sub.func) == "isinstance"
                   for sub in ast.walk(test)):
                continue
            hits = [sub.id for sub in ast.walk(test)
                    if isinstance(sub, ast.Name)
                    and isinstance(sub.ctx, ast.Load)
                    and sub.id in params
                    and not _inside_none_check(ctx, sub, test)
                    and not _metadata_access(ctx, sub)]
            if hits:
                yield test, (f"Python branch on traced argument "
                             f"'{hits[0]}' — use jax.lax.cond/select, or "
                             f"mark it static")


# --------------------------------------------------------------------------
# JL1xx — hidden host syncs (hot paths)
# --------------------------------------------------------------------------

def _indexy(node: ast.AST) -> bool:
    name = _name_of(node)
    return name in _INDEXY or name.endswith(("_count", "_idx", "_index"))


def _in_loop(ctx, node: ast.AST, fn: ast.AST) -> bool:
    cur = ctx.parent(node)
    while cur is not None and cur is not fn:
        if isinstance(cur, (ast.For, ast.While, ast.AsyncFor, ast.ListComp,
                            ast.SetComp, ast.DictComp, ast.GeneratorExp)):
            return True
        cur = ctx.parent(cur)
    return False


def _hot_sites(ctx, fn) -> Iterator[ast.AST]:
    """Per-step-hot nodes in a hot function: the whole body of a listener
    callback / ``__next__`` (called once per iteration from outside), or
    nodes under a loop for ordinary fit/step/train functions."""
    whole_body = getattr(fn, "name", "") in CALLBACK_NAMES or \
        getattr(fn, "name", "") in ("__next__",)
    for node in _walk_no_nested(fn):
        if whole_body or _in_loop(ctx, node, fn):
            yield node


#: value-producing calls that read host state, not device buffers
_HOST_VALUE_METHODS = {"get", "pop", "integers", "randint", "choice",
                       "random", "uniform", "normal"}
_HOST_VALUE_FUNCS = {"len", "round", "min", "max", "sum", "abs", "ord",
                     "time", "perf_counter", "monotonic", "getattr"}


def _shape_read(arg: ast.AST) -> bool:
    for sub in ast.walk(arg):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim"):
            return True
        if isinstance(sub, ast.Name) and sub.id == "shape":
            return True
    return False


def _check_host_scalar_sync(ctx):
    for fn in ctx.hot_functions():
        params = {a.arg for a in fn.args.args} if \
            isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) else set()
        for node in _hot_sites(ctx, fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and len(node.args) == 1 and not node.keywords):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) or _indexy(arg):
                continue
            if isinstance(arg, ast.Name) and arg.id in params:
                continue  # coercing a host-side argument, not a device read
            if isinstance(arg, ast.Call) and (
                    _name_of(arg.func) in _HOST_VALUE_FUNCS or
                    (isinstance(arg.func, ast.Attribute)
                     and arg.func.attr in _HOST_VALUE_METHODS)):
                continue
            if isinstance(arg, (ast.BinOp, ast.BoolOp)):
                continue  # arithmetic on host scalars, not a device read
            if _shape_read(arg):
                continue  # shapes are host metadata
            desc = ast.unparse(arg) if hasattr(ast, "unparse") else "value"
            yield node, (f"'{node.func.id}({desc})' in hot path may block "
                         f"on device->host transfer every step")


def _check_item_sync(ctx):
    for fn in ctx.hot_functions():
        for node in _hot_sites(ctx, fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("item", "tolist")
                    and not node.args and not node.keywords):
                yield node, (f"'.{node.func.attr}()' in hot path forces a "
                             f"device->host sync every step")


_ASARRAY_CALLS = {"numpy.asarray", "numpy.array", "jax.device_get"}


def _check_asarray_sync(ctx):
    for fn in ctx.hot_functions():
        for node in _hot_sites(ctx, fn):
            if isinstance(node, ast.Call):
                d = ctx.dotted(node.func)
                if d in _ASARRAY_CALLS:
                    yield node, (f"'{d}()' in hot path copies device memory "
                                 f"to host; batch or fence it once per step")


# --------------------------------------------------------------------------
# JL2xx — recompile hazards
# --------------------------------------------------------------------------

_UNHASHABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                        ast.DictComp, ast.SetComp, ast.GeneratorExp)


def _jit_target_map(ctx) -> Dict[str, object]:
    return {asg.target_name: asg for asg in ctx.jit.assignments
            if asg.static_argnums}


def _check_unhashable_static(ctx):
    targets = _jit_target_map(ctx)
    if not targets:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = None
        if isinstance(node.func, ast.Name):
            name = node.func.id
        elif _is_self_attr(node.func):
            name = node.func.attr
        asg = targets.get(name)
        if asg is None:
            continue
        for pos in asg.static_argnums:
            if pos < len(node.args) and \
                    isinstance(node.args[pos], _UNHASHABLE_LITERALS):
                yield node.args[pos], (
                    f"unhashable literal passed at static position {pos} "
                    f"of jitted '{name}' — raises TypeError or defeats the "
                    f"jit cache; pass a tuple / hashable")


def _module_array_constants(ctx) -> Set[str]:
    out: Set[str] = set()
    body = getattr(ctx.tree, "body", [])
    for stmt in body:
        if not (isinstance(stmt, ast.Assign)
                and isinstance(stmt.value, ast.Call)):
            continue
        d = ctx.dotted(stmt.value.func) or ""
        parts = d.split(".")
        if parts[-1] in _ARRAY_CTORS and (
                parts[0] in ("numpy", "jax") or len(parts) == 1):
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name):
                    out.add(tgt.id)
    return out


def _check_array_closure(ctx):
    consts = _module_array_constants(ctx)
    if not consts:
        return
    for fn in ctx.jit.reachable:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
            continue
        local: Set[str] = set()
        if not isinstance(fn, ast.Lambda):
            local = {a.arg for a in fn.args.args}
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
                local.add(node.id)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in consts and node.id not in local):
                yield node, (f"module-level array '{node.id}' closed over "
                             f"by jit-reachable code constant-folds into "
                             f"the executable; pass it as an argument")


def _check_shape_fstring(ctx):
    for fn in ctx.hot_functions():
        for node in _walk_no_nested(fn):
            shapey = None
            if isinstance(node, ast.JoinedStr):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Attribute) and \
                            sub.attr in ("shape", "dtype"):
                        shapey = sub
                        break
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id == "str" and node.args
                  and isinstance(node.args[0], ast.Attribute)
                  and node.args[0].attr in ("shape", "dtype")):
                shapey = node.args[0]
            if shapey is not None:
                yield node, (f"shape/dtype-derived string built in hot "
                             f"path (per-step formatting; a classic "
                             f"recompile-churn cache key)")


# --------------------------------------------------------------------------
# JL3xx — donation misuse
# --------------------------------------------------------------------------

def _check_donation_reuse(ctx):
    donate_map = {asg.target_name: asg for asg in ctx.jit.assignments
                  if asg.donate_argnums}
    if not donate_map:
        return
    for fn in ctx.functions():
        aliases: Dict[str, str] = {}   # local name -> jitted target name
        donated: Dict[str, int] = {}   # identifier -> donating-call lineno
        reassigned: Dict[str, int] = {}

        def ident(node) -> Optional[str]:
            if isinstance(node, ast.Name):
                return node.id
            if _is_self_attr(node):
                return f"self.{node.attr}"
            return None

        # same-line ordering matters: the donating call completes first,
        # then reads happen (``return self.params`` reads on the return's
        # own line), then stores clear, then the return severs tracking
        # between mutually exclusive branches
        _PRIO = {"donate": 0, "load": 1, "assign": 2, "return": 3}
        events: List[Tuple[int, int, str, ast.AST]] = []

        def emit(lineno: int, kind: str, node: ast.AST) -> None:
            events.append((lineno, _PRIO[kind.split(":")[0]], kind, node))

        for node in ast.walk(fn):
            if isinstance(node, ast.Return):
                emit(node.lineno, "return", node)
            if isinstance(node, ast.Assign):
                src = ident(node.value)
                for tgt in node.targets:
                    names = [tgt]
                    if isinstance(tgt, (ast.Tuple, ast.List)):
                        names = list(tgt.elts)
                    for t in names:
                        tid = ident(t)
                        if tid is None:
                            continue
                        emit(node.lineno, "assign", t)
                        if isinstance(t, ast.Name):
                            if src in donate_map:
                                aliases[t.id] = src
                            else:
                                aliases.pop(t.id, None)
            if isinstance(node, ast.Call):
                name = None
                if isinstance(node.func, ast.Name):
                    name = aliases.get(node.func.id, node.func.id)
                elif _is_self_attr(node.func):
                    name = node.func.attr
                asg = donate_map.get(name)
                if asg is not None:
                    # donation takes effect after the whole (possibly
                    # multi-line) call — its own argument loads are fine
                    effect_line = getattr(node, "end_lineno", None) or \
                        node.lineno
                    for pos in asg.donate_argnums:
                        if pos < len(node.args):
                            aid = ident(node.args[pos])
                            if aid:
                                emit(effect_line, f"donate:{aid}", node)
            if isinstance(node, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(node, "ctx", None), ast.Load):
                aid = ident(node)
                if aid:
                    emit(node.lineno, f"load:{aid}", node)

        events.sort(key=lambda e: (e[0], e[1]))
        for lineno, _prio, kind, node in events:
            if kind == "return":
                donated.clear()
            elif kind.startswith("donate:"):
                donated.setdefault(kind[7:], lineno)
            elif kind == "assign":
                aid = ident(node)
                if aid in donated and lineno > donated[aid]:
                    donated.pop(aid, None)
            elif kind.startswith("load:"):
                aid = kind[5:]
                if aid in donated and lineno > donated[aid]:
                    yield node, (f"'{aid}' read after being donated to a "
                                 f"jitted call (line {donated[aid]}); the "
                                 f"buffer is deleted on real hardware")
                    donated.pop(aid, None)


# --------------------------------------------------------------------------
# JL4xx — lock discipline
# --------------------------------------------------------------------------

def _thread_entry_points(cls: ast.ClassDef,
                         methods: Dict[str, ast.FunctionDef]) -> Set[str]:
    entries: Set[str] = set()
    for base in cls.bases:
        if _name_of(base) == "Thread" and "run" in methods:
            entries.add("run")
    for m in methods.values():
        for node in ast.walk(m):
            if not isinstance(node, ast.Call):
                continue
            fname = _name_of(node.func)
            if fname == "Thread":
                for kw in node.keywords:
                    if kw.arg == "target" and _is_self_attr(kw.value) and \
                            kw.value.attr in methods:
                        entries.add(kw.value.attr)
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "submit":
                if node.args and _is_self_attr(node.args[0]) and \
                        node.args[0].attr in methods:
                    entries.add(node.args[0].attr)
    return entries


def _guard_of(ctx, node) -> Optional[str]:
    """Name of the self.<lock-ish> attribute whose ``with`` block encloses
    this node, or None."""
    cur = ctx.parent(node)
    while cur is not None and not isinstance(
            cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
        if isinstance(cur, ast.With):
            for item in cur.items:
                expr = item.context_expr
                if isinstance(expr, ast.Call):
                    expr = expr.func
                if _is_self_attr(expr) and _LOCKISH.search(expr.attr):
                    return expr.attr
        cur = ctx.parent(cur)
    return None


def _sync_primitive_attrs(init: Optional[ast.FunctionDef], ctx) -> Set[str]:
    out: Set[str] = set()
    if init is None:
        return out
    for node in ast.walk(init):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            d = (ctx.dotted(node.value.func) or "").split(".")[-1]
            if d in _SYNC_PRIMITIVE_CTORS:
                for tgt in node.targets:
                    if _is_self_attr(tgt):
                        out.add(tgt.attr)
    return out


def _check_lock_discipline(ctx):
    for cls in ctx.classes():
        methods = {n.name: n for n in cls.body
                   if isinstance(n, ast.FunctionDef)}
        entries = _thread_entry_points(cls, methods)
        if not entries:
            continue
        # thread side = entry points + one level of same-class callees
        thread_side: Set[str] = set(entries)
        for name in list(entries):
            fn = methods.get(name)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and \
                        _is_self_attr(node.func) and \
                        node.func.attr in methods:
                    thread_side.add(node.func.attr)
        main_side = set(methods) - thread_side - {"__init__"}
        exempt = _sync_primitive_attrs(methods.get("__init__"), ctx)

        def attr_events(names: Set[str], want_store: bool):
            for mname in names:
                fn = methods.get(mname)
                if fn is None:
                    continue
                for node in ast.walk(fn):
                    tgts = []
                    if isinstance(node, ast.Assign):
                        tgts = node.targets
                    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                        tgts = [node.target]
                    if want_store:
                        for t in tgts:
                            sub = [t]
                            if isinstance(t, (ast.Tuple, ast.List)):
                                sub = list(t.elts)
                            for s in sub:
                                if _is_self_attr(s):
                                    yield mname, s.attr, s
                    elif isinstance(node, ast.Attribute) and \
                            _is_self_attr(node) and \
                            isinstance(node.ctx, ast.Load):
                        yield mname, node.attr, node

        thread_writes: Dict[str, List[Tuple[str, ast.AST]]] = {}
        for mname, attr, node in attr_events(thread_side, True):
            thread_writes.setdefault(attr, []).append((mname, node))
        main_touch: Set[str] = set()
        for _, attr, _n in attr_events(main_side, True):
            main_touch.add(attr)
        for _, attr, _n in attr_events(main_side, False):
            main_touch.add(attr)

        for attr, writes in sorted(thread_writes.items()):
            if attr in exempt or attr.startswith("__"):
                continue
            writer_methods = {m for m, _ in writes}
            shared = attr in main_touch or len(writer_methods) > 1
            if not shared:
                continue
            guards = {_guard_of(ctx, node) for _, node in writes}
            # main-side write sites must use the same guard too
            main_writes = [(m, n) for m, a, n in attr_events(main_side, True)
                           if a == attr]
            guards |= {_guard_of(ctx, node) for _, node in main_writes}
            if guards == {None}:
                for mname, node in writes:
                    yield node, (
                        f"'{cls.name}.{attr}' is written from thread entry "
                        f"'{mname}' and shared with other methods, with no "
                        f"lock held at any write site")
            elif None in guards or len(guards - {None}) > 1:
                named = sorted(g for g in guards if g)
                for mname, node in writes + main_writes:
                    if _guard_of(ctx, node) is None or len(named) > 1:
                        yield node, (
                            f"'{cls.name}.{attr}' write in '{mname}' is not "
                            f"consistently guarded (locks seen: "
                            f"{', '.join(named) or 'none'})")


# --------------------------------------------------------------------------
# JL402/JL403 — lock-acquisition graphs and blocking-under-lock
# --------------------------------------------------------------------------

#: primitives that are *acquired* (``with``/``.acquire()``), as opposed to
#: queues/events which only block
_ACQUIRABLE_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
                     "BoundedSemaphore"}


def _module_lock_names(ctx) -> Set[str]:
    out: Set[str] = set()
    for stmt in getattr(ctx.tree, "body", []):
        if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
            d = (ctx.dotted(stmt.value.func) or "").split(".")[-1]
            if d in _ACQUIRABLE_CTORS:
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
    return out


def _class_lock_attrs(ctx, methods: Dict[str, ast.FunctionDef]) -> Set[str]:
    """``self.<attr>`` names that hold sync primitives: assigned one in
    ``__init__``, or lock-ish by name anywhere in the class."""
    out = _sync_primitive_attrs(methods.get("__init__"), ctx)
    for fn in methods.values():
        for node in ast.walk(fn):
            if _is_self_attr(node) and _LOCKISH.search(node.attr):
                out.add(node.attr)
    return out


def _lock_identity(ctx, expr, cls_name: str, lock_attrs: Set[str],
                   module_locks: Set[str]) -> Optional[str]:
    """Stable name for a lock object resolved by attribute path:
    ``Cls.attr`` for ``self.<lock>``, a dotted path for other attribute
    chains whose last segment is lock-ish, the bare name for
    module-level locks."""
    if isinstance(expr, ast.Call):
        expr = expr.func
    if _is_self_attr(expr) and (expr.attr in lock_attrs
                                or _LOCKISH.search(expr.attr)):
        return f"{cls_name}.{expr.attr}" if cls_name else f"self.{expr.attr}"
    if isinstance(expr, ast.Name) and (expr.id in module_locks
                                       or _LOCKISH.search(expr.id)):
        return expr.id
    if isinstance(expr, ast.Attribute) and _LOCKISH.search(expr.attr):
        d = ctx.dotted(expr)
        if d:
            return d
    return None


#: functions whose call under a held lock blocks on device/model work
_FORWARDISH = {"output", "predict", "generate", "forward", "_forward"}
#: queue-shaped receiver names for .get()/.put() blocking checks
_QUEUEISH = re.compile(r"queue|(^|_)q($|_)", re.IGNORECASE)
_SOCKETISH_METHODS = {"urlopen", "recv", "recv_into", "sendall",
                      "getresponse", "accept", "makefile"}


class _LockGraph:
    """Held-lock statement walker over one class (or the module's
    top-level functions).

    Records (a) lock-order edges ``A -> B`` (B acquired while A held,
    including one transitive level of same-scope callees, like
    :mod:`.boundaries` does for jit roots) and (b) blocking calls made
    while at least one lock is held."""

    def __init__(self, ctx, cls_name: str,
                 methods: Dict[str, ast.FunctionDef],
                 lock_attrs: Set[str], module_locks: Set[str]):
        self.ctx = ctx
        self.cls_name = cls_name
        self.methods = methods
        self.lock_attrs = lock_attrs
        self.module_locks = module_locks
        self.edges: Dict[Tuple[str, str], ast.AST] = {}
        self.blocking: List[Tuple[ast.AST, str, Tuple[str, ...]]] = []
        self._summaries: Dict[str, Set[str]] = {}

    def lock_of(self, expr) -> Optional[str]:
        return _lock_identity(self.ctx, expr, self.cls_name,
                              self.lock_attrs, self.module_locks)

    def walk(self) -> "_LockGraph":
        for _name, fn in sorted(self.methods.items()):
            self._stmts(fn.body, [])
        return self

    # -- one-level callee summaries ---------------------------------------
    def summary(self, name: str) -> Set[str]:
        """Locks a callee acquires anywhere in its own body (memoised;
        the one transitive level of the inter-procedural graph)."""
        if name in self._summaries:
            return self._summaries[name]
        self._summaries[name] = set()          # recursion guard
        acquired: Set[str] = set()
        fn = self.methods.get(name)
        if fn is not None:
            for node in _walk_no_nested(fn):
                if isinstance(node, ast.With):
                    for item in node.items:
                        lk = self.lock_of(item.context_expr)
                        if lk:
                            acquired.add(lk)
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "acquire":
                    lk = self.lock_of(node.func.value)
                    if lk:
                        acquired.add(lk)
        self._summaries[name] = acquired
        return acquired

    # -- walking ----------------------------------------------------------
    def _record(self, held: List[str], lock: str, node: ast.AST) -> None:
        for h in held:
            if h != lock:
                self.edges.setdefault((h, lock), node)

    def _stmts(self, body: List[ast.stmt], held: List[str]) -> None:
        for stmt in body:
            self._scan_exprs(stmt, held)
            if isinstance(stmt, ast.With):
                acquired: List[str] = []
                for item in stmt.items:
                    lk = self.lock_of(item.context_expr)
                    if lk:
                        self._record(held, lk, item.context_expr)
                        acquired.append(lk)
                self._stmts(stmt.body, held + acquired)
            elif isinstance(stmt, ast.If):
                self._stmts(stmt.body, list(held))
                self._stmts(stmt.orelse, list(held))
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                self._stmts(stmt.body, list(held))
                self._stmts(stmt.orelse, list(held))
            elif isinstance(stmt, ast.Try):
                self._stmts(stmt.body, list(held))
                for handler in stmt.handlers:
                    self._stmts(handler.body, list(held))
                self._stmts(stmt.orelse, list(held))
                self._stmts(stmt.finalbody, list(held))
            elif isinstance(stmt, ast.Expr) and isinstance(stmt.value,
                                                           ast.Call):
                # sequential .acquire()/.release() at this nesting level
                call = stmt.value
                if isinstance(call.func, ast.Attribute):
                    lk = self.lock_of(call.func.value)
                    if lk and call.func.attr == "acquire":
                        self._record(held, lk, call)
                        held.append(lk)
                    elif lk and call.func.attr == "release" and lk in held:
                        held.remove(lk)

    def _scan_exprs(self, stmt: ast.stmt, held: List[str]) -> None:
        """Calls in this statement's own expressions (tests, values,
        arguments) — child statements are handled by :meth:`_stmts`."""
        stack = [c for c in ast.iter_child_nodes(stmt)
                 if not isinstance(c, ast.stmt)]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef, ast.stmt)):
                continue
            if isinstance(node, ast.Call):
                self._call(node, held)
            stack.extend(ast.iter_child_nodes(node))

    def _call(self, call: ast.Call, held: List[str]) -> None:
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "acquire":
            lk = self.lock_of(func.value)
            if lk:
                self._record(held, lk, call)
            return
        # one transitive callee level: locks the callee itself acquires
        callee = None
        if _is_self_attr(func) and func.attr in self.methods:
            callee = func.attr
        elif isinstance(func, ast.Name) and func.id in self.methods:
            callee = func.id
        if callee is not None and held:
            for lk in sorted(self.summary(callee)):
                self._record(held, lk, call)
        if held:
            reason = self._blocking_reason(call, held)
            if reason:
                self.blocking.append((call, reason, tuple(held)))

    def _blocking_reason(self, call: ast.Call,
                         held: List[str]) -> Optional[str]:
        func = call.func
        attr = func.attr if isinstance(func, ast.Attribute) else ""
        d = self.ctx.dotted(func) or ""
        kwnames = {kw.arg for kw in call.keywords}
        if d == "time.sleep":
            return "'time.sleep' call"
        if attr == "block_until_ready":
            return "host fence '.block_until_ready()'"
        if d.split(".")[0] == "subprocess":
            return f"subprocess call '{d}'"
        if d.startswith(("urllib.", "requests.", "socket.")) or \
                attr in _SOCKETISH_METHODS:
            return "socket/HTTP I/O"
        recv = func.value if isinstance(func, ast.Attribute) else None
        rname = _name_of(recv) if recv is not None else ""
        if _QUEUEISH.search(rname or ""):
            if attr == "get" and not call.args and "timeout" not in kwnames:
                return f"blocking '{rname}.get()' without timeout"
            if attr == "put" and "timeout" not in kwnames and \
                    "block" not in kwnames:
                return f"blocking '{rname}.put()' without timeout"
        if attr == "wait" and not call.args and "timeout" not in kwnames:
            rid = self.lock_of(recv) if recv is not None else None
            if [h for h in held if h != rid]:
                return "'.wait()' without timeout"
        if attr in _FORWARDISH:
            return f"model forward '.{attr}()'"
        return None


def _lock_graphs(ctx) -> List[_LockGraph]:
    module_locks = _module_lock_names(ctx)
    mod_fns = {n.name: n for n in getattr(ctx.tree, "body", [])
               if isinstance(n, ast.FunctionDef)}
    graphs = [_LockGraph(ctx, "", mod_fns, set(), module_locks)]
    for cls in ctx.classes():
        methods = {n.name: n for n in cls.body
                   if isinstance(n, ast.FunctionDef)}
        graphs.append(_LockGraph(ctx, cls.name, methods,
                                 _class_lock_attrs(ctx, methods),
                                 module_locks))
    return [g.walk() for g in graphs]


def find_cycles(edges) -> List[List[str]]:
    """Simple cycles in a lock-order graph, each reported once, rooted
    at its lexicographically smallest lock. ``edges`` is any iterable of
    ``(from, to)`` pairs (a dict of edge->site works directly)."""
    adj: Dict[str, Set[str]] = {}
    for a, b in edges:
        adj.setdefault(a, set()).add(b)
    out: List[List[str]] = []
    seen: Set[Tuple[str, ...]] = set()

    def dfs(start: str, node: str, path: List[str],
            onpath: Set[str]) -> None:
        for nxt in sorted(adj.get(node, ())):
            if nxt == start:
                canon = tuple(path)
                if canon not in seen:
                    seen.add(canon)
                    out.append(list(path))
            elif nxt not in onpath and nxt > start:
                path.append(nxt)
                onpath.add(nxt)
                dfs(start, nxt, path, onpath)
                path.pop()
                onpath.discard(nxt)

    for start in sorted(adj):
        dfs(start, start, [start], {start})
    return out


def lock_edges_from_source(source: str,
                           path: str = "<string>") -> Dict[Tuple[str, str],
                                                           ast.AST]:
    """The static lock-acquisition-order graph of one source file, as an
    edge ``(held, acquired) -> acquisition site`` map — the static half
    of the :mod:`.lockcheck` runtime cross-check."""
    from .engine import FileContext
    tree = ast.parse(source)
    ctx = FileContext(path, source, tree)
    edges: Dict[Tuple[str, str], ast.AST] = {}
    for g in _lock_graphs(ctx):
        edges.update(g.edges)
    return edges


def _check_lock_order(ctx):
    for g in _lock_graphs(ctx):
        for cycle in find_cycles(g.edges):
            if len(cycle) < 2:
                continue
            node = g.edges.get((cycle[0], cycle[1]))
            if node is None:
                continue
            ring = " -> ".join(cycle + [cycle[0]])
            yield node, (f"cyclic lock acquisition order {ring}: two "
                         f"threads taking these locks in opposite order "
                         f"can deadlock")


def _check_blocking_under_lock(ctx):
    for g in _lock_graphs(ctx):
        for node, reason, held in g.blocking:
            locks = ", ".join(sorted(set(held)))
            yield node, (f"{reason} while holding {locks} — blocking "
                         f"inside a critical section wedges every waiter")


# --------------------------------------------------------------------------
# JL404 — field-level atomicity
# --------------------------------------------------------------------------

def _check_field_atomicity(ctx):
    for cls in ctx.classes():
        methods = {n.name: n for n in cls.body
                   if isinstance(n, ast.FunctionDef)}
        if not methods:
            continue
        sync_attrs = _class_lock_attrs(ctx, methods)
        owns_locks = any(_LOCKISH.search(a) for a in sync_attrs) or \
            bool(_sync_primitive_attrs(methods.get("__init__"), ctx))

        # (attr, node, kind, method, guard)
        events: List[Tuple[str, ast.AST, str, str, Optional[str]]] = []
        for mname, fn in methods.items():
            if mname.endswith("_locked"):
                continue      # caller-holds-lock convention
            for node in _walk_no_nested(fn):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    tgts = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for tgt in tgts:
                        subs = list(tgt.elts) if isinstance(
                            tgt, (ast.Tuple, ast.List)) else [tgt]
                        for s in subs:
                            if _is_self_attr(s) and \
                                    not s.attr.startswith("__"):
                                kind = "rmw" if isinstance(
                                    node, ast.AugAssign) else "write"
                                events.append((s.attr, s, kind, mname,
                                               _guard_of(ctx, s)))
                elif isinstance(node, (ast.If, ast.While)):
                    for sub in ast.walk(node.test):
                        if _is_self_attr(sub) and \
                                isinstance(sub.ctx, ast.Load) and \
                                not sub.attr.startswith("__"):
                            events.append((sub.attr, sub, "test-read",
                                           mname, _guard_of(ctx, sub)))

        by_attr: Dict[str, List] = {}
        for attr, node, kind, mname, guard in events:
            by_attr.setdefault(attr, []).append((node, kind, mname, guard))

        for attr, evs in sorted(by_attr.items()):
            if attr in sync_attrs:
                continue
            guarded = sorted({g for n, k, m, g in evs
                              if g and m != "__init__"
                              and k in ("write", "rmw")})
            for node, kind, mname, guard in evs:
                if mname == "__init__" or guard is not None:
                    continue
                if kind == "rmw" and (owns_locks or guarded):
                    yield node, (
                        f"unguarded read-modify-write of 'self.{attr}' in "
                        f"'{mname}' of lock-owning class '{cls.name}' — "
                        f"lost-update race (the 'dropped += 1' shape)")
                elif kind == "write" and guarded:
                    yield node, (
                        f"'self.{attr}' is written under "
                        f"{'/'.join(guarded)} elsewhere in '{cls.name}' "
                        f"but written without it in '{mname}'")
                elif kind == "test-read" and guarded:
                    yield node, (
                        f"check-then-act read of 'self.{attr}' in "
                        f"'{mname}' without {'/'.join(guarded)} (it is "
                        f"written under that lock) — the value can change "
                        f"between the test and the action")


# --------------------------------------------------------------------------
# JL5xx — serving discipline
# --------------------------------------------------------------------------

#: the typed serving-error taxonomy allowed to escape an HTTP handler
ERROR_TAXONOMY = {
    "ServerClosedError", "BatchExecutionError", "NonFiniteOutputError",
    "QueueFullError", "DeadlineExceededError", "DecodeStepError",
    "KVCacheExhaustedError", "BreakerOpenError", "TierShedError",
    "SwapError", "ReplicaLostError", "FaultInjected",
}

#: self.* calls that raise typed serving errors (must sit inside a try)
_ROUTE_RAISING_CALLS = {"predict", "generate", "swap", "dispatch", "get",
                        "reconfigure", "reconfigure_scheduler",
                        "eject_member", "remove", "admit"}


def _try_protected(ctx, node, fn) -> bool:
    """Is this node inside the *body* of a try that has handlers (not in
    a handler/else/finally, which run unprotected)?"""
    child, cur = node, ctx.parent(node)
    while cur is not None:
        if isinstance(cur, ast.Try) and cur.handlers and child in cur.body:
            return True
        if cur is fn:
            return False
        child, cur = cur, ctx.parent(cur)
    return False


def _check_route_typed_errors(ctx):
    for fn in ctx.functions():
        name = getattr(fn, "name", "")
        if not name.endswith("_route"):
            continue
        for node in _walk_no_nested(fn):
            if isinstance(node, ast.Raise) and node.exc is not None:
                exc = node.exc
                if isinstance(exc, ast.Call):
                    exc = exc.func
                ename = _name_of(exc)
                if ename and ename not in ERROR_TAXONOMY and \
                        not _try_protected(ctx, node, fn):
                    yield node, (
                        f"raise of non-taxonomy '{ename}' escapes HTTP "
                        f"handler '{name}' untyped — clients see a bare "
                        f"500 instead of a typed serving error")
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                if attr not in _ROUTE_RAISING_CALLS:
                    continue
                d = ctx.dotted(node.func) or ""
                if not d.startswith("self."):
                    continue
                if attr == "get" and d != "self.pool.get":
                    continue
                if not _try_protected(ctx, node, fn):
                    yield node, (
                        f"call to '{d}' outside any try in HTTP handler "
                        f"'{name}' — a typed serving error raised here "
                        f"escapes as an untyped 500")


# --- JL502: metrics discipline --------------------------------------------

_METRIC_FACTORIES = {"counter", "gauge", "histogram"}
_UNBOUNDED_LABELS = {"request_id", "rid", "uuid", "guid", "trace_id",
                     "span_id", "correlation_id", "port", "pid", "tid"}
_UNBOUNDED_VALUE_CALLS = {"uuid4", "uuid1", "getpid", "get_ident"}
_REGISTER_FN_RE = re.compile(r"register.*metrics")


def _metric_family_call(ctx, node) -> Optional[str]:
    """Family name if this call constructs a metric family on a
    registry-ish receiver, else None."""
    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _METRIC_FACTORIES
            and node.args and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)):
        return None
    recv = node.func.value
    if isinstance(recv, ast.Call):
        recv = recv.func
    if re.search(r"reg", _name_of(recv) or "", re.IGNORECASE):
        return node.args[0].value
    return None


def _package_root(path: str) -> Optional[str]:
    """Ascend from a file path to the ``deeplearning4j_tpu`` package dir
    (None when analyzing sources outside a checkout)."""
    cur = os.path.abspath(path)
    while True:
        if os.path.basename(cur) == "deeplearning4j_tpu" and \
                os.path.isdir(cur):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return None
        cur = parent


def _tree_files(root: str) -> List[str]:
    out: List[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        out.extend(os.path.join(dirpath, f) for f in sorted(filenames))
    return out


_PREREG_CACHE: Dict[str, frozenset] = {}


def _preregistered_families(pkg_root: str) -> frozenset:
    """Every string constant inside a ``register*metrics`` function in
    the package — the families a scrape sees at 0 before any traffic."""
    cached = _PREREG_CACHE.get(pkg_root)
    if cached is not None:
        return cached
    names: Set[str] = set()
    files = [f for f in _tree_files(pkg_root) if f.endswith(".py")]
    for fname in files:
        try:
            with open(fname, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError, UnicodeDecodeError):
            continue
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and _REGISTER_FN_RE.search(node.name):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        names.add(sub.value)
    out = frozenset(names)
    _PREREG_CACHE[pkg_root] = out
    return out


def _check_metrics_discipline(ctx):
    # (a) family construction reachable from a hot path
    for fn in ctx.hot_functions():
        fname = getattr(fn, "name", "<lambda>")
        if _REGISTER_FN_RE.search(fname):
            continue
        for node in _walk_no_nested(fn):
            fam = _metric_family_call(ctx, node)
            if fam:
                yield node, (
                    f"metric family '{fam}' constructed in hot function "
                    f"'{fname}' — construct once in register_metrics() "
                    f"and only .labels().inc() on the hot path")
    # (b) unbounded-cardinality label sets
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "labels"):
            continue
        for kw in node.keywords:
            if kw.arg and kw.arg.lower() in _UNBOUNDED_LABELS:
                yield kw.value, (
                    f"metric label '{kw.arg}' is unbounded-cardinality "
                    f"(per-request identity) — every value mints a new "
                    f"series and the scrape grows without bound")
            elif isinstance(kw.value, ast.Call) and \
                    _name_of(kw.value.func) in _UNBOUNDED_VALUE_CALLS:
                yield kw.value, (
                    f"metric label '{kw.arg}' is fed from "
                    f"'{_name_of(kw.value.func)}()' — unbounded "
                    f"cardinality mints a new series per value")
    # (c) serving families absent from every pre-registration
    if "serving" not in os.path.normpath(ctx.path).split(os.sep):
        return
    pkg = _package_root(ctx.path)
    if pkg is None:
        return
    prereg = _preregistered_families(pkg)
    if not prereg:
        return
    for node in ast.walk(ctx.tree):
        fam = _metric_family_call(ctx, node)
        if fam is None or fam in prereg:
            continue
        encl = ctx.enclosing_function(node)
        if encl is not None and \
                _REGISTER_FN_RE.search(getattr(encl, "name", "")):
            continue
        yield node, (
            f"metric family '{fam}' used in serving/ but absent from "
            f"every register_metrics() pre-registration — a scrape "
            f"before the first request misses it")


# --- JL503: fault-point coverage ------------------------------------------

_CORPUS_CACHE: Dict[Tuple[str, str], str] = {}


def _corpus(repo_root: str, sub: str, exts: Tuple[str, ...]) -> str:
    key = (repo_root, sub)
    cached = _CORPUS_CACHE.get(key)
    if cached is not None:
        return cached
    chunks: List[str] = []
    root = os.path.join(repo_root, sub)
    if os.path.isdir(root):
        for fname in _tree_files(root):
            if fname.endswith(exts):
                try:
                    with open(fname, "r", encoding="utf-8") as fh:
                        chunks.append(fh.read())
                except (OSError, UnicodeDecodeError):
                    continue
    out = "\n".join(chunks)
    _CORPUS_CACHE[key] = out
    return out


def _fault_env_var(point: str) -> str:
    return "DL4JTPU_FAULT_" + point.upper().replace(".", "_").replace(
        "-", "_")


def _check_fault_coverage(ctx):
    pkg = _package_root(ctx.path)
    if pkg is None:
        return
    root = os.path.dirname(pkg)
    tests = _corpus(root, "tests", (".py",))
    docs = _corpus(root, "docs", (".md",))
    if not tests or not docs:
        return
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("fire", "check")
                and node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        point = node.args[0].value
        if "." not in point:
            continue
        if node.func.attr == "check" and not re.search(
                r"fault", _name_of(node.func.value) or "", re.IGNORECASE):
            continue          # '.check' is a common name; require faults.*
        if point not in tests and _fault_env_var(point) not in tests:
            yield node, (
                f"fault point '{point}' is not exercised by any test "
                f"under tests/ — the chaos hook can silently rot")
        if point not in docs:
            yield node, (
                f"fault point '{point}' is missing from the docs fault "
                f"tables (docs/*.md)")


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

RULES: Tuple[Rule, ...] = (
    Rule("JL001", "error", "impure-random",
         "Use jax.random with an explicitly threaded PRNG key.",
         _check_impure_random),
    Rule("JL002", "warning", "impure-time",
         "Read clocks outside the traced function and pass values in.",
         _check_impure_time),
    Rule("JL003", "warning", "impure-io",
         "Use jax.debug.print, or log outside the traced function.",
         _check_impure_io),
    Rule("JL004", "error", "trace-mutation",
         "Return new values from the traced function instead of mutating "
         "self/globals.",
         _check_trace_mutation),
    Rule("JL005", "warning", "tracer-branch",
         "Use jax.lax.cond/jnp.where, or declare the argument in "
         "static_argnums.",
         _check_tracer_branch),
    Rule("JL101", "warning", "host-scalar-sync",
         "Fence once per step (tracecheck.fenced_read / "
         "block_until_ready) or read asynchronously off the hot path.",
         _check_host_scalar_sync),
    Rule("JL102", "warning", "item-sync",
         "Batch .item()/.tolist() reads behind an explicit per-step fence.",
         _check_item_sync),
    Rule("JL103", "info", "host-copy",
         "np.asarray/device_get copies device memory; hoist out of the "
         "per-step loop or fence deliberately.",
         _check_asarray_sync),
    Rule("JL201", "error", "unhashable-static",
         "Static arguments key the jit cache; pass tuples or other "
         "hashables.",
         _check_unhashable_static),
    Rule("JL202", "warning", "array-closure",
         "Pass module-level arrays as arguments so XLA doesn't "
         "constant-fold them into the executable.",
         _check_array_closure),
    Rule("JL203", "warning", "shape-fstring",
         "Hoist shape/dtype formatting out of the hot path (guard behind "
         "a rate limiter or log level).",
         _check_shape_fstring),
    Rule("JL301", "error", "donation-reuse",
         "Reassign or re-fetch the buffer from the call's outputs before "
         "reading; donated inputs are deleted on device.",
         _check_donation_reuse),
    Rule("JL401", "warning", "lock-discipline",
         "Guard every write with the same self.<lock>, or annotate a "
         "documented atomic with '# jaxlint: atomic'.",
         _check_lock_discipline),
    Rule("JL402", "error", "lock-order-cycle",
         "Acquire locks in one global order everywhere; break the cycle, "
         "or baseline it with a justification if it cannot manifest.",
         _check_lock_order),
    Rule("JL403", "warning", "blocking-under-lock",
         "Move the blocking call outside the critical section, or give it "
         "a timeout so waiters cannot wedge behind it.",
         _check_blocking_under_lock),
    Rule("JL404", "warning", "field-atomicity",
         "Take the guarding lock for every read-modify-write and "
         "check-then-act on shared fields, or annotate a documented "
         "atomic with '# jaxlint: atomic'.",
         _check_field_atomicity),
    Rule("JL501", "error", "untyped-route-error",
         "Wrap handler work in try/except and map failures to the typed "
         "serving taxonomy (QueueFullError, ServerClosedError, ...).",
         _check_route_typed_errors),
    Rule("JL502", "warning", "metrics-discipline",
         "Construct metric families once in register_metrics(), keep "
         "label sets bounded, and pre-register serving families so a "
         "scrape before traffic sees them.",
         _check_metrics_discipline),
    Rule("JL503", "error", "fault-coverage",
         "Add a test that arms the point (faults.inject/injected) and a "
         "row to the docs fault table.",
         _check_fault_coverage),
)

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in RULES}


def rule_catalog() -> List[dict]:
    """Stable, docs-friendly listing of every rule."""
    return [r.describe() for r in RULES]
