"""Jit-boundary inference: which functions in a module get traced.

A function "reaches" the XLA trace if any of these hold:

* it is decorated with a trace wrapper (``@jax.jit``, ``@jit``,
  ``@functools.partial(jax.jit, ...)``, ``pmap``, ``shard_map``, ...);
* its name is passed as an argument to a trace-wrapper call
  (``jax.jit(train_step, donate_argnums=...)``,
  ``jax.value_and_grad(self._loss_pure)``, ``jax.lax.scan(body, ...)``,
  ``PrecompiledDispatch(jax.jit(f), ...)``);
* it is a lambda written directly inside such a call;
* it is called (one transitive level, resolved within the module: plain
  names and ``self.method``) from any of the above.

The lazy ``__getattr__`` jit builders (``_build_training_jits`` in
nn/multilayer.py and nn/graph/graph.py) need no special casing for
*purity* — the inner step functions are arguments to ``jax.jit`` and
are caught by the call-site rule — but the *attributes* they assign
(``self._train_step_fn = jax.jit(step, donate_argnums=(0, 1, 2))``)
matter for donation analysis: the attribute is built in one method and
called from another, reached only through ``__getattr__``. So this
module also records every jit assignment (name or ``self.attr`` →
static_argnums / donate_argnums), letting the donation and static-arg
rules follow calls through the lazy indirection.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

# Last dotted component of a callee that traces its function argument.
# Bare (undotted) names are accepted only for the unambiguous ones.
_WRAPPER_LAST = {
    "jit", "pjit", "pmap", "vmap", "shard_map", "xmap",
    "grad", "value_and_grad", "checkpoint", "remat",
    "scan", "while_loop", "fori_loop", "cond", "switch", "associative_scan",
    "custom_jvp", "custom_vjp",
    "PrecompiledDispatch",
}
_BARE_OK = {"jit", "pjit", "pmap", "shard_map", "PrecompiledDispatch"}


def build_alias_map(tree: ast.AST) -> Dict[str, str]:
    """Import-alias resolution (``import numpy as np`` → np: numpy;
    ``from jax import numpy as jnp`` → jnp: jax.numpy), collected from
    every import statement in the file (function-local ones
    included)."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def dotted_name(node: ast.AST,
                aliases: Optional[Dict[str, str]] = None) -> Optional[str]:
    """``jax.lax.scan`` for an Attribute/Name chain (None when the chain
    contains calls/subscripts), with the first segment canonicalized
    through the import-alias map."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    else:
        return None
    parts.reverse()
    if aliases and parts[0] in aliases:
        parts[0:1] = aliases[parts[0]].split(".")
    return ".".join(parts)


def is_trace_wrapper(call: ast.Call,
                     aliases: Optional[Dict[str, str]] = None) -> bool:
    """Does this call trace (stage out) a function passed to it?"""
    d = dotted_name(call.func, aliases)
    if d is None:
        return False
    parts = d.split(".")
    last = parts[-1]
    if last not in _WRAPPER_LAST:
        return False
    if len(parts) == 1:
        return last in _BARE_OK
    return True


@dataclass
class JitAssignment:
    """``target = <wrapper>(fn, static_argnums=..., donate_argnums=...)``
    where target is a plain name or ``self.attr``. Call sites found by
    `target_name` let the donation/static rules follow the lazy
    ``__getattr__`` indirection."""
    target_name: str            # "x" or "_train_step_fn" (attr name)
    is_self_attr: bool
    fn_name: Optional[str]      # traced function's name when resolvable
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    node: Optional[ast.AST] = None


@dataclass
class JitInfo:
    """Per-module jit-boundary inference result."""
    roots: Set[ast.AST] = field(default_factory=set)
    reachable: Set[ast.AST] = field(default_factory=set)  # roots + 1 level
    assignments: List[JitAssignment] = field(default_factory=list)
    #: function-name → node for every def/lambda seen (diagnostics/tests)
    functions: Dict[str, ast.AST] = field(default_factory=dict)


def _int_tuple(node: Optional[ast.AST]) -> Tuple[int, ...]:
    """Literal ints out of ``(0, 1)`` / ``[0, 1]`` / ``0`` argnum specs."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    return ()


def _str_tuple(node: Optional[ast.AST]) -> Tuple[str, ...]:
    """Literal strings out of ``("a", "b")`` / ``"a"`` argname specs."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _inner_jit_call(call: ast.Call, aliases) -> ast.Call:
    """``PrecompiledDispatch(jax.jit(f, donate_argnums=...), tag)`` —
    the argnum metadata lives on the INNER jit call."""
    if call.args and isinstance(call.args[0], ast.Call) and \
            is_trace_wrapper(call.args[0], aliases):
        return call.args[0]
    return call


def _called_names(fn: ast.AST) -> Set[str]:
    """Simple call targets inside a function body: bare names and
    ``self.method`` attribute names (the one-level transitive edge)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            out.add(f.id)
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self":
            out.add(f.attr)
    return out


def infer(tree: ast.AST, aliases: Optional[Dict[str, str]] = None) -> JitInfo:
    """Run jit-boundary inference over one module AST."""
    if aliases is None:
        aliases = build_alias_map(tree)
    info = JitInfo()

    # ---- index every function/lambda by simple name ---------------------
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            info.functions[node.name] = node

    # ---- pass 1: direct roots ------------------------------------------
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                d = dotted_name(target, aliases)
                if d and d.split(".")[-1] in _WRAPPER_LAST and (
                        "." in d or d in _BARE_OK):
                    info.roots.add(node)
                # @functools.partial(jax.jit, ...) — wrapper hides inside
                if isinstance(dec, ast.Call) and dec.args and \
                        isinstance(dec.args[0], (ast.Name, ast.Attribute)):
                    inner = dotted_name(dec.args[0], aliases)
                    if inner and inner.split(".")[-1] in _WRAPPER_LAST:
                        info.roots.add(node)
        if not (isinstance(node, ast.Call) and
                is_trace_wrapper(node, aliases)):
            continue
        for arg in list(node.args) + [k.value for k in node.keywords]:
            if isinstance(arg, ast.Lambda):
                info.roots.add(arg)
            elif isinstance(arg, ast.Name) and arg.id in info.functions:
                info.roots.add(info.functions[arg.id])
            elif isinstance(arg, ast.Attribute) and \
                    isinstance(arg.value, ast.Name) and \
                    arg.value.id == "self" and arg.attr in info.functions:
                # jax.vmap(self._train_step_raw) style
                info.roots.add(info.functions[arg.attr])
            elif isinstance(arg, ast.Call) and \
                    isinstance(arg.func, (ast.Name, ast.Attribute)):
                fd = dotted_name(arg.func, aliases)
                if fd and fd.split(".")[-1] == "partial" and arg.args and \
                        isinstance(arg.args[0], ast.Name) and \
                        arg.args[0].id in info.functions:
                    info.roots.add(info.functions[arg.args[0].id])

    # ---- pass 1b: declared trace surfaces -------------------------------
    # A module-level ``__traced__ = ("fn", ...)`` tuple names functions
    # that are traced from ANOTHER file (cross-file jit wrapping the
    # per-file passes above cannot see) — e.g. a kernel entry point
    # jitted by its caller. Listed names become roots.
    for stmt in getattr(tree, "body", []):
        if not isinstance(stmt, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "__traced__"
                   for t in stmt.targets):
            continue
        if isinstance(stmt.value, (ast.Tuple, ast.List)):
            for elt in stmt.value.elts:
                if isinstance(elt, ast.Constant) and \
                        isinstance(elt.value, str) and \
                        elt.value in info.functions:
                    info.roots.add(info.functions[elt.value])

    # ---- pass 2: jit assignments (the lazy __getattr__ attribute map) --
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or \
                not isinstance(node.value, ast.Call):
            continue
        call = node.value
        if not is_trace_wrapper(call, aliases):
            continue
        jit_call = _inner_jit_call(call, aliases)
        # static_argnums may also live on the OUTER PrecompiledDispatch
        static = _int_tuple(_kw(jit_call, "static_argnums")) or \
            _int_tuple(_kw(call, "static_argnums"))
        donate = _int_tuple(_kw(jit_call, "donate_argnums"))
        argnames = _str_tuple(_kw(jit_call, "static_argnames")) or \
            _str_tuple(_kw(call, "static_argnames"))
        fn_name = None
        if jit_call.args and isinstance(jit_call.args[0], ast.Name):
            fn_name = jit_call.args[0].id
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                info.assignments.append(JitAssignment(
                    tgt.id, False, fn_name, static, donate, argnames, node))
            elif isinstance(tgt, ast.Attribute) and \
                    isinstance(tgt.value, ast.Name) and tgt.value.id == "self":
                info.assignments.append(JitAssignment(
                    tgt.attr, True, fn_name, static, donate, argnames, node))

    # ---- pass 3: one level of transitive callees ------------------------
    info.reachable = set(info.roots)
    for root in info.roots:
        for name in _called_names(root):
            fn = info.functions.get(name)
            if fn is not None:
                info.reachable.add(fn)
    return info
