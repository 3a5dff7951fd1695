"""Accelerator hot-path analyzer (`ray_tpu devtools accel`,
rules RT301-RT306) — the static twin of `_private/compile_watch.py`.

Fourth devtools layer (after lint's per-file idioms, check's
cross-process contracts, and race's thread/lock model): the failure
modes that silently break "runs as fast as the hardware allows" are
XLA-layer — recompile storms, hidden device->host syncs, donation
mistakes — and PR 15's compile watch only convicts them *at runtime,
after the step time is already lost*.  This pass rejects the same
bugs at `devtools all` time.

Two phases over the whole tree:

**Phase 1 — inventory.**  Every wrapping site (`jax.jit(...)`,
``partial(jax.jit, ...)(impl)``, ``@jax.jit`` / ``@partial(jax.jit,
...)`` decorators, ``checked_shard_map``) with its resolved
``donate_argnums`` (including the ``accel_donate(...)`` gate),
``static_argnums``/``static_argnames``, how the wrapper is bound
(module global, ``self`` attribute, local, decorated def, immediately
invoked), whether it flows into ``compile_watch.instrument`` (and
under what program name — f-string names become ``fnmatch`` patterns,
``mpmd.s{i}.{k}`` -> ``mpmd.s*.*``), plus the *hot contexts*: functions
billed by ``step_telemetry.phase_timer``, ``@rt.remote`` actor
methods, and any function whose loop dispatches a known-jitted
callable.  Module-level forwarders (a function whose return is a
1:1 positional call of a jit binding — the ``paged_decode_step`` ->
``_paged_decode_jit`` idiom in models/generate.py) inherit the inner
wrapper's donate/static signature, so call sites in *other* modules
are judged too.

**Phase 2 — judgment.**

| id    | judgment                                                     |
|-------|--------------------------------------------------------------|
| RT301 | jit/donate wrapper constructed inside a loop, or in a       |
|       | per-call function body — re-traces and re-compiles every    |
|       | call, defeating the compile cache.  One-time contexts       |
|       | (init/build/make/setup/warm/test/main names, factories      |
|       | that return the wrapper, the lazy module-global cache       |
|       | idiom) are exempt.                                          |
| RT302 | recompile-hazard argument: ``len(...)`` (or an unhashable   |
|       | list/dict/set literal) reaching a static position — every   |
|       | distinct value compiles a new program — or a               |
|       | ``len()``-bounded slice reaching a traced position (shape   |
|       | drift per batch); also per-call-computed static_argnums at  |
|       | the wrap site.  The static cause behind `verdict.compile`   |
|       | shape-drift storms.                                         |
| RT303 | hidden host sync in a hot loop: ``float()``/``int()``/      |
|       | ``bool``-branch/``.item()``/``np.asarray``/``print`` applied|
|       | to a device value inside a loop of a hot context.  Each one |
|       | blocks dispatch for a device round-trip — the class whose   |
|       | removal bought PR 12 ~10% tokens/s.                         |
| RT304 | use-after-donate: a plain name passed at a donated argnum   |
|       | and read again before rebinding — XLA consumed the buffer.  |
| RT305 | timing code measures a dispatched-but-unblocked device      |
|       | computation: clock read, jitted call, clock subtraction     |
|       | with no ``block_until_ready`` (or host materialization)     |
|       | in between — the benchmark reports dispatch, not compute.   |
| RT306 | jitted program invisible to the compile watch: the wrapper  |
|       | never flows through ``compile_watch.instrument``, so a      |
|       | recompile storm attributes to ``(unregistered)`` and the    |
|       | doctor cannot name the program.                             |
| RT390 | stale or unknown ``# rt: noqa[RT3xx]`` suppression (the     |
|       | shared hygiene contract; see lint.noqa_hygiene).            |

Scoping: RT303/RT305/RT306 stay out of test files (``test_*.py``,
``tests/``, ``conftest.py``) — tests time, sync and jit deliberately;
RT301/RT302/RT304 apply everywhere.  Precision over recall
throughout: aliased wrappers, cross-variable taint through containers
and dynamically-chosen callees stay silent rather than guessing —
the runtime twin (`compile_watch`, `rt.diagnose()`'s
`verdict.compile`) supplies the dynamic evidence this pass cannot
see, and `build_inventory()` is the bridge back: the doctor resolves
a live storm's program name against this pass's inventory so the
runtime conviction points at the static fix.

Shares the lint/check/race contract: ``# rt: noqa[RT3xx]``
suppressions, ``--json``, exit 0 clean / 1 findings / 2 usage errors.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys
from dataclasses import asdict, dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .lint import (
    Finding,
    _dotted,
    _is_remote_decorator,
    _iter_py_files,
    _parse_noqa,
    noqa_hygiene,
)

__all__ = [
    "accel_sources",
    "accel_paths",
    "build_inventory",
    "build_inventory_sources",
    "main",
    "RULES",
]

#: id -> one-line title (the --list-rules table).
RULES: Dict[str, str] = {
    "RT301": "jit/donate wrapper constructed per call (loop or call-path body)",
    "RT302": "recompile-hazard argument reaches a static/traced position",
    "RT303": "hidden host sync on a device value in a hot loop",
    "RT304": "buffer read after being donated to a jitted call",
    "RT305": "timing measures a dispatched-but-unblocked device computation",
    "RT306": "jitted program not registered with compile_watch.instrument",
    "RT390": "stale or unknown '# rt: noqa' suppression (accel family)",
}

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}
_SHARD_NAMES = {"checked_shard_map", "shard_map"}
_TIME_CALLS = {
    "time.time", "time.monotonic", "time.perf_counter",
    "time.time_ns", "time.monotonic_ns", "time.perf_counter_ns",
    "monotonic", "perf_counter",
}
#: Host materializers: calling one *blocks* on the device value (so it
#: also discharges a pending RT305 dispatch).
_SYNC_CALLS = {"float", "int", "np.asarray", "numpy.asarray",
               "jax.device_get", "device_get"}
_ONETIME_PREFIXES = ("init", "build", "make", "setup", "warm", "test",
                     "main", "create", "bench")


def _is_test_path(path: str) -> bool:
    norm = path.replace(os.sep, "/")
    base = os.path.basename(norm)
    return (
        base.startswith("test_")
        or base == "conftest.py"
        or "/tests/" in norm
        or norm.startswith("tests/")
    )


def _const_ints(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """(1, 2) / [1] / 3 -> ints; accel_donate(1, 2) -> (1, 2); else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    if isinstance(node, ast.Call):
        dotted = _dotted(node.func)
        if dotted == "accel_donate" or dotted.endswith(".accel_donate"):
            out = []
            for arg in node.args:
                if isinstance(arg, ast.Constant) and isinstance(arg.value, int):
                    out.append(arg.value)
                else:
                    return None
            return tuple(out)
    return None


def _const_strs(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def _contains_len(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _dotted(sub.func) == "len":
            return True
    return False


def _program_name(node: ast.AST) -> Tuple[Optional[str], Optional[str]]:
    """instrument() first arg -> (name, "literal"|"pattern")."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, "literal"
    if isinstance(node, ast.JoinedStr):
        parts = []
        for piece in node.values:
            if isinstance(piece, ast.Constant):
                parts.append(str(piece.value))
            else:
                parts.append("*")
        return "".join(parts), "pattern"
    return None, None


@dataclass
class _Wrap:
    """One jit / shard_map wrapping site (phase-1 inventory row)."""

    path: str
    line: int
    col: int
    kind: str  # "jit" | "shard_map"
    target: str  # dotted name of the wrapped callable ("" if opaque)
    binding: Optional[Tuple[str, str]]  # ("global"|"self"|"local"|"def", name)
    donate: Tuple[int, ...] = ()
    static_nums: Tuple[int, ...] = ()
    static_names: Tuple[str, ...] = ()
    fresh_static: bool = False  # static argnums/names computed per call
    enclosing: Optional[str] = None  # qualname of enclosing function
    in_loop: bool = False
    immediately_called: bool = False
    returned: bool = False  # factory idiom: `return jax.jit(...)`
    registered: bool = False
    program: Optional[str] = None
    program_kind: Optional[str] = None  # "literal" | "pattern"
    hazards: List[dict] = field(default_factory=list)  # RT302, for doctor


@dataclass
class _FnRec:
    """One function body to judge in phase 2."""

    path: str
    qualname: str
    node: ast.AST
    class_name: Optional[str] = None
    is_remote_method: bool = False
    uses_phase_timer: bool = False


@dataclass
class _ModuleScan:
    path: str
    source: str
    tree: ast.Module
    wraps: List[_Wrap] = field(default_factory=list)
    #: binding key -> (program, kind) from instrument(name, <binding>).
    regs: Dict[Tuple[str, str], Tuple[Optional[str], Optional[str]]] = field(
        default_factory=dict
    )
    #: bindings assigned a compile_watch.instrument(...) result — a
    #: WatchedFunction IS a jitted-program handle, so calls through it
    #: participate in taint/dispatch tracking (donate/static unknown).
    watched: List[Tuple[str, str]] = field(default_factory=list)
    funcs: List[_FnRec] = field(default_factory=list)


@dataclass
class _Callee:
    """Resolved signature of a jitted callable, for call-site rules."""

    donate: Tuple[int, ...]
    static_nums: Tuple[int, ...]
    static_names: Tuple[str, ...]
    wrap: Optional[_Wrap]  # None once terminal-name resolution is ambiguous


def _merge_callee(into: Dict[str, _Callee], name: str, cal: _Callee) -> None:
    """Terminal-name registry: collisions keep jittedness but drop the
    donate/static signature — wrong donation info is worse than none."""
    prev = into.get(name)
    if prev is None:
        into[name] = cal
    elif prev.wrap is not cal.wrap:
        into[name] = _Callee((), (), (), None)


class _Scanner(ast.NodeVisitor):
    """Phase 1: one walk per module collecting wraps, registrations and
    judgeable function bodies."""

    def __init__(self, mod: _ModuleScan):
        self.mod = mod
        self.func_stack: List[Tuple[str, ast.AST, Set[str]]] = []
        self.class_stack: List[Tuple[str, bool]] = []
        self.loop_depth = 0
        self._consumed: Set[int] = set()

    # -- wrap recognition ---------------------------------------------
    def _jit_wrap_of(self, node: ast.AST) -> Optional[Tuple[ast.Call, List[ast.keyword], Optional[ast.expr]]]:
        """node is a jit wrapping call -> (call, keywords, target expr)."""
        if not isinstance(node, ast.Call):
            return None
        dotted = _dotted(node.func)
        if dotted in _JIT_NAMES:
            target = node.args[0] if node.args else None
            return node, list(node.keywords), target
        # partial(jax.jit, **kw)(impl): the outer application.
        if isinstance(node.func, ast.Call):
            inner = node.func
            if (
                _dotted(inner.func) in _PARTIAL_NAMES
                and inner.args
                and _dotted(inner.args[0]) in _JIT_NAMES
            ):
                target = node.args[0] if node.args else None
                return node, list(inner.keywords), target
        return None

    def _make_wrap(
        self,
        node: ast.Call,
        keywords: Sequence[ast.keyword],
        target: Optional[ast.expr],
        kind: str = "jit",
        binding: Optional[Tuple[str, str]] = None,
        immediately_called: bool = False,
        returned: bool = False,
    ) -> _Wrap:
        donate: Tuple[int, ...] = ()
        static_nums: Tuple[int, ...] = ()
        static_names: Tuple[str, ...] = ()
        fresh = False
        for kw in keywords:
            if kw.arg == "donate_argnums":
                donate = _const_ints(kw.value) or ()
            elif kw.arg == "static_argnums":
                vals = _const_ints(kw.value)
                if vals is None and isinstance(
                    kw.value, (ast.Call, ast.ListComp, ast.GeneratorExp)
                ):
                    fresh = True
                static_nums = vals or ()
            elif kw.arg == "static_argnames":
                vals = _const_strs(kw.value)
                if vals is None and isinstance(
                    kw.value, (ast.Call, ast.ListComp, ast.GeneratorExp)
                ):
                    fresh = True
                static_names = vals or ()
        wrap = _Wrap(
            path=self.mod.path,
            line=node.lineno,
            col=node.col_offset + 1,
            kind=kind,
            target=_dotted(target) if target is not None else "",
            binding=binding,
            donate=donate,
            static_nums=static_nums,
            static_names=static_names,
            fresh_static=fresh,
            enclosing=self.func_stack[-1][0] if self.func_stack else None,
            in_loop=self.loop_depth > 0,
            immediately_called=immediately_called,
            returned=returned,
        )
        self.mod.wraps.append(wrap)
        self._consumed.add(id(node))
        return wrap

    def _binding_for(self, tgt: ast.expr) -> Optional[Tuple[str, str]]:
        if isinstance(tgt, ast.Name):
            if not self.func_stack:
                return ("global", tgt.id)
            if tgt.id in self.func_stack[-1][2]:  # `global X` declared
                return ("global", tgt.id)
            return ("local", tgt.id)
        if (
            isinstance(tgt, ast.Attribute)
            and isinstance(tgt.value, ast.Name)
            and tgt.value.id == "self"
        ):
            return ("self", tgt.attr)
        return None

    # -- visits --------------------------------------------------------
    def visit_Global(self, node: ast.Global) -> None:
        if self.func_stack:
            self.func_stack[-1][2].update(node.names)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        is_actor = any(_is_remote_decorator(d) for d in node.decorator_list)
        self.class_stack.append((node.name, is_actor))
        self.generic_visit(node)
        self.class_stack.pop()

    def _visit_func(self, node) -> None:
        qual = ".".join(
            [c for c, _ in self.class_stack]
            + [f[0].rsplit(".", 1)[-1] for f in self.func_stack]
            + [node.name]
        )
        uses_timer = any(
            isinstance(sub, ast.Call)
            and _dotted(sub.func).endswith("phase_timer")
            for sub in ast.walk(node)
        )
        in_actor = bool(self.class_stack) and self.class_stack[-1][1]
        decorated_remote = any(
            _is_remote_decorator(d) for d in node.decorator_list
        )
        self.mod.funcs.append(
            _FnRec(
                path=self.mod.path,
                qualname=qual,
                node=node,
                class_name=self.class_stack[-1][0] if self.class_stack else None,
                is_remote_method=in_actor or decorated_remote,
                uses_phase_timer=uses_timer,
            )
        )
        # Decorator wraps: @jax.jit / @partial(jax.jit, ...).
        for dec in node.decorator_list:
            if _dotted(dec) in _JIT_NAMES:
                fake = ast.Call(func=dec, args=[], keywords=[])
                ast.copy_location(fake, dec)
                self._make_wrap(fake, [], None, binding=("def", node.name))
                self.mod.wraps[-1].target = qual
            elif isinstance(dec, ast.Call):
                got = self._jit_wrap_of_decorator(dec)
                if got is not None:
                    self._make_wrap(dec, got, None, binding=("def", node.name))
                    self.mod.wraps[-1].target = qual
        outer_loop, self.loop_depth = self.loop_depth, 0
        self.func_stack.append((qual, node, set()))
        self.generic_visit(node)
        self.func_stack.pop()
        self.loop_depth = outer_loop

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def _jit_wrap_of_decorator(self, dec: ast.Call) -> Optional[List[ast.keyword]]:
        if _dotted(dec.func) in _JIT_NAMES:
            return list(dec.keywords)
        if (
            _dotted(dec.func) in _PARTIAL_NAMES
            and dec.args
            and _dotted(dec.args[0]) in _JIT_NAMES
        ):
            return list(dec.keywords)
        return None

    def _visit_loop(self, node) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    def visit_Assign(self, node: ast.Assign) -> None:
        got = self._wrap_like(node.value)
        binding = (
            self._binding_for(node.targets[0])
            if len(node.targets) == 1
            else None
        )
        if got is not None:
            call, kws, target, kind, registered, prog, pk = got
            wrap = self._make_wrap(call, kws, target, kind=kind, binding=binding)
            if registered:
                wrap.registered, wrap.program, wrap.program_kind = True, prog, pk
        elif (
            binding is not None
            and isinstance(node.value, ast.Call)
            and self._is_instrument(node.value)
        ):
            self.mod.watched.append(binding)
        self.generic_visit(node)

    def visit_Return(self, node: ast.Return) -> None:
        if node.value is not None:
            got = self._wrap_like(node.value)
            if got is not None:
                call, kws, target, kind, registered, prog, pk = got
                wrap = self._make_wrap(call, kws, target, kind=kind, returned=True)
                if registered:
                    wrap.registered, wrap.program, wrap.program_kind = True, prog, pk
        self.generic_visit(node)

    def _wrap_like(self, value: ast.expr):
        """value is a wrap, an instrument(<wrap>), or instrument-applied
        wrap -> (call, keywords, target, kind, registered, prog, prog_kind)."""
        got = self._jit_wrap_of(value)
        if got is not None:
            call, kws, target = got
            return call, kws, target, "jit", False, None, None
        if isinstance(value, ast.Call):
            dotted = _dotted(value.func)
            if dotted.rsplit(".", 1)[-1] in _SHARD_NAMES:
                target = value.args[0] if value.args else None
                return value, list(value.keywords), target, "shard_map", False, None, None
            if self._is_instrument(value) and len(value.args) >= 2:
                inner = self._jit_wrap_of(value.args[1])
                if inner is not None:
                    prog, pk = _program_name(value.args[0])
                    call, kws, target = inner
                    return call, kws, target, "jit", True, prog, pk
        return None

    def _is_instrument(self, call: ast.Call) -> bool:
        dotted = _dotted(call.func)
        return dotted == "instrument" or dotted.endswith(".instrument")

    def visit_Call(self, node: ast.Call) -> None:
        # instrument(name, X): register X whether X is a wrap, a name,
        # or a self attribute.
        if self._is_instrument(node) and len(node.args) >= 2:
            prog, pk = _program_name(node.args[0])
            inner = self._jit_wrap_of(node.args[1])
            if inner is not None:
                if id(node.args[1]) not in self._consumed:
                    call, kws, target = inner
                    wrap = self._make_wrap(call, kws, target)
                    wrap.registered, wrap.program, wrap.program_kind = True, prog, pk
            else:
                key = self._binding_for(node.args[1])
                if key is not None:
                    # A local name registers its local binding; fall back
                    # to the module-global spelling too (lazy-init idiom).
                    self.mod.regs[key] = (prog, pk)
                    if key[0] == "local":
                        self.mod.regs[("global", key[1])] = (prog, pk)
        # Immediately-invoked wrap: jax.jit(f, ...)(args).
        got = self._jit_wrap_of(node.func) if isinstance(node.func, ast.Call) else None
        if got is not None and _dotted(node.func.func) not in _PARTIAL_NAMES:
            if id(node.func) not in self._consumed:
                call, kws, target = got
                self._make_wrap(call, kws, target, immediately_called=True)
        # Anonymous wrap used as a plain expression/argument.
        if id(node) not in self._consumed and self._jit_wrap_of(node) is not None:
            call, kws, target = self._jit_wrap_of(node)
            self._make_wrap(call, kws, target)
        self.generic_visit(node)


def _scan_module(path: str, source: str, tree: ast.Module) -> _ModuleScan:
    mod = _ModuleScan(path=path, source=source, tree=tree)
    _Scanner(mod).visit(tree)
    # Resolve name-flow registrations: instrument("name", binding).
    for wrap in mod.wraps:
        if wrap.registered or wrap.binding is None:
            continue
        reg = mod.regs.get(wrap.binding)
        if reg is None and wrap.binding[0] == "def":
            reg = mod.regs.get(("global", wrap.binding[1]))
        if reg is not None:
            wrap.registered = True
            wrap.program, wrap.program_kind = reg
    return mod


def _forwarders(mod: _ModuleScan) -> Dict[str, _Wrap]:
    """Module-level `def f(...): return <jit binding>(...)` forwarders.
    1:1 positional forwarding inherits the wrapper's donate/static."""
    by_global: Dict[str, _Wrap] = {}
    for wrap in mod.wraps:
        if wrap.kind == "jit" and wrap.binding and wrap.binding[0] == "global":
            by_global[wrap.binding[1]] = wrap
    out: Dict[str, _Wrap] = {}
    if not by_global:
        return out
    for node in mod.tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for stmt in ast.walk(node):
            if not (isinstance(stmt, ast.Return) and isinstance(stmt.value, ast.Call)):
                continue
            callee = stmt.value.func
            if isinstance(callee, ast.Name) and callee.id in by_global:
                inner = by_global[callee.id]
                params = [a.arg for a in node.args.args]
                call_args = [
                    a.id if isinstance(a, ast.Name) else None
                    for a in stmt.value.args
                ]
                if call_args and call_args == params[: len(call_args)]:
                    out[node.name] = inner  # positional 1:1 — inherit
                else:
                    out.setdefault(
                        node.name, _Wrap(inner.path, inner.line, inner.col,
                                         "jit", inner.target, None)
                    )
                break
    return out


# ---------------------------------------------------------------------------
# phase 2: per-function judgment
# ---------------------------------------------------------------------------


class _FuncJudge:
    """Linear, source-order walk of one function body tracking device
    taint, pending dispatch, live donations and clock reads."""

    def __init__(
        self,
        rec: _FnRec,
        callees: Dict[str, _Callee],
        self_callees: Dict[str, _Callee],
        local_callees: Dict[str, _Callee],
        findings: List[Finding],
        in_test_file: bool,
    ):
        self.rec = rec
        self.callees = callees
        self.self_callees = self_callees
        self.local_callees = local_callees
        self.findings = findings
        self.in_test_file = in_test_file
        self.tainted: Set[str] = set()
        self.time_vars: Set[str] = set()
        self.pending_dispatch = False
        self.pending_line = 0
        self.donated: Dict[str, Tuple[int, str]] = {}  # name -> (line, callee)
        self.loop_depth = 0
        self.hot_reason: Optional[str] = None
        if rec.uses_phase_timer:
            self.hot_reason = "billed by step_telemetry.phase_timer"
        elif rec.is_remote_method:
            self.hot_reason = "@rt.remote dispatch path"

    # -- entry ---------------------------------------------------------
    def run(self) -> None:
        body = getattr(self.rec.node, "body", [])
        if self.hot_reason is None and self._has_jit_loop(body):
            self.hot_reason = "loop dispatches a jitted program"
        for stmt in body:
            self._stmt(stmt)

    def _has_jit_loop(self, body) -> bool:
        for stmt in body:
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.For, ast.AsyncFor, ast.While)):
                    for inner in ast.walk(sub):
                        if isinstance(inner, ast.Call) and self._callee(inner) is not None:
                            return True
        return False

    def _callee(self, call: ast.Call) -> Optional[_Callee]:
        func = call.func
        if isinstance(func, ast.Name):
            if func.id in self.local_callees:
                return self.local_callees[func.id]
            return self.callees.get(func.id)
        if isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name) and func.value.id == "self":
                return self.self_callees.get(func.attr)
            return self.callees.get(func.attr)
        return None

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(
            Finding(
                path=self.rec.path,
                line=getattr(node, "lineno", 1),
                col=getattr(node, "col_offset", 0) + 1,
                rule=rule,
                message=message,
            )
        )

    # -- statements ----------------------------------------------------
    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return  # judged as their own records
        if isinstance(stmt, ast.Assign):
            taint = self._expr(stmt.value)
            is_time = (
                isinstance(stmt.value, ast.Call)
                and _dotted(stmt.value.func) in _TIME_CALLS
            )
            for tgt in stmt.targets:
                self._store(tgt, taint, is_time)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            taint = self._expr(stmt.value)
            is_time = (
                isinstance(stmt.value, ast.Call)
                and _dotted(stmt.value.func) in _TIME_CALLS
            )
            self._store(stmt.target, taint, is_time)
        elif isinstance(stmt, ast.AugAssign):
            self._expr(stmt.value)
            if isinstance(stmt.target, ast.Name):
                self._load_name(stmt.target)
        elif isinstance(stmt, ast.Expr):
            self._expr(stmt.value)
        elif isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self._expr(stmt.value)
        elif isinstance(stmt, (ast.If,)):
            self._branch_test(stmt.test)
            for s in stmt.body:
                self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
        elif isinstance(stmt, (ast.While,)):
            self.loop_depth += 1
            self._branch_test(stmt.test)
            for s in stmt.body:
                self._stmt(s)
            self.loop_depth -= 1
            for s in stmt.orelse:
                self._stmt(s)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr(stmt.iter)
            self.loop_depth += 1
            for s in stmt.body:
                self._stmt(s)
            self.loop_depth -= 1
            for s in stmt.orelse:
                self._stmt(s)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr)
            for s in stmt.body:
                self._stmt(s)
        elif isinstance(stmt, ast.Try):
            for s in stmt.body:
                self._stmt(s)
            for handler in stmt.handlers:
                for s in handler.body:
                    self._stmt(s)
            for s in stmt.orelse:
                self._stmt(s)
            for s in stmt.finalbody:
                self._stmt(s)
        else:
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self._expr(child)

    def _store(self, tgt: ast.expr, taint: bool, is_time: bool) -> None:
        if isinstance(tgt, (ast.Tuple, ast.List)):
            for elt in tgt.elts:
                self._store(elt, taint, False)
            return
        if isinstance(tgt, ast.Starred):
            self._store(tgt.value, taint, False)
            return
        if isinstance(tgt, ast.Name):
            self.donated.pop(tgt.id, None)  # rebound — donation consumed
            self.time_vars.discard(tgt.id)
            if is_time:
                self.time_vars.add(tgt.id)
            if taint:
                self.tainted.add(tgt.id)
            else:
                self.tainted.discard(tgt.id)
        elif isinstance(tgt, (ast.Attribute, ast.Subscript)):
            self._expr(tgt.value)

    def _branch_test(self, test: ast.expr) -> None:
        taint = self._expr(test)
        if taint and self._hot_now():
            self._emit(
                "RT303",
                test,
                f"{self.rec.qualname} branches on a device value inside a "
                f"hot loop ({self.hot_reason}) — the bool() forces a "
                f"device->host sync every iteration; compute the predicate "
                f"on host or hoist it out of the loop",
            )
            self.pending_dispatch = False

    def _hot_now(self) -> bool:
        return (
            self.loop_depth > 0
            and self.hot_reason is not None
            and not self.in_test_file
        )

    # -- expressions ---------------------------------------------------
    def _load_name(self, node: ast.Name) -> bool:
        if node.id in self.donated:
            line, callee = self.donated.pop(node.id)
            self._emit(
                "RT304",
                node,
                f"{self.rec.qualname} reads '{node.id}' after donating it "
                f"to {callee} (line {line}) — XLA consumed the buffer; "
                f"rebind the result or drop it from donate_argnums",
            )
        return node.id in self.tainted

    def _expr(self, node: Optional[ast.expr]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return self._load_name(node)
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, ast.BinOp):
            return self._binop(node)
        if isinstance(node, ast.BoolOp):
            return any(self._expr(v) for v in list(node.values))
        if isinstance(node, ast.UnaryOp):
            return self._expr(node.operand)
        if isinstance(node, ast.Compare):
            got = self._expr(node.left)
            for cmp in node.comparators:
                got = self._expr(cmp) or got
            return got
        if isinstance(node, ast.Subscript):
            got = self._expr(node.value)
            self._expr(node.slice) if isinstance(node.slice, ast.expr) else None
            return got
        if isinstance(node, ast.Attribute):
            return self._expr(node.value)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self._expr(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            got = False
            for k, v in zip(node.keys, node.values):
                got = self._expr(k) or got if k is not None else got
                got = self._expr(v) or got
            return got
        if isinstance(node, ast.IfExp):
            self._expr(node.test)
            return self._expr(node.body) or self._expr(node.orelse)
        if isinstance(node, ast.Starred):
            return self._expr(node.value)
        if isinstance(node, ast.JoinedStr):
            return any(
                self._expr(v.value)
                for v in node.values
                if isinstance(v, ast.FormattedValue)
            )
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)):
            # Separate scope — no taint judgments inside, but a sync
            # call in the element expression still blocks on the device
            # (the `{k: np.asarray(v) ...}` materialize idiom), so it
            # discharges a pending RT305 dispatch.
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    dotted = _dotted(sub.func)
                    if dotted in _SYNC_CALLS or dotted in ("float", "int") or (
                        dotted.rsplit(".", 1)[-1] in ("item", "block_until_ready")
                    ):
                        self.pending_dispatch = False
                        break
            return False
        if isinstance(node, ast.Lambda):
            return False
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child)
        return False

    def _binop(self, node: ast.BinOp) -> bool:
        left_is_clock = (
            isinstance(node.left, ast.Call)
            and _dotted(node.left.func) in _TIME_CALLS
        ) or (
            isinstance(node.left, ast.Name) and node.left.id in self.time_vars
        )
        right_is_timevar = (
            isinstance(node.right, ast.Name) and node.right.id in self.time_vars
        )
        if isinstance(node.op, ast.Sub) and right_is_timevar and left_is_clock:
            if self.pending_dispatch and not self.in_test_file:
                self._emit(
                    "RT305",
                    node,
                    f"{self.rec.qualname} reads the clock after a jitted "
                    f"call (line {self.pending_line}) with no "
                    f"block_until_ready in between — the elapsed time "
                    f"measures async dispatch, not device compute",
                )
            self.pending_dispatch = False
            return False
        got = self._expr(node.left)
        return self._expr(node.right) or got

    def _call(self, node: ast.Call) -> bool:
        dotted = _dotted(node.func)
        terminal = dotted.rsplit(".", 1)[-1] if dotted else ""
        # block_until_ready discharges a pending dispatch.
        if terminal == "block_until_ready":
            for arg in node.args:
                self._expr(arg)
            if isinstance(node.func, ast.Attribute):
                self._expr(node.func.value)
            self.pending_dispatch = False
            return True  # still a device value (jax returns it)
        callee = self._callee(node)
        if callee is not None:
            self._jitted_call(node, callee)
            return True
        # Host syncs.
        if dotted in ("float", "int") and len(node.args) == 1:
            taint = self._expr(node.args[0])
            if taint:
                if self._hot_now():
                    self._emit(
                        "RT303",
                        node,
                        f"{self.rec.qualname} calls {dotted}() on a device "
                        f"value inside a hot loop ({self.hot_reason}) — "
                        f"each call blocks for a device->host round-trip; "
                        f"batch the transfer outside the loop",
                    )
                self.pending_dispatch = False
            return False
        if dotted in _SYNC_CALLS:
            taint = any(self._expr(a) for a in list(node.args))
            if taint:
                if self._hot_now():
                    self._emit(
                        "RT303",
                        node,
                        f"{self.rec.qualname} materializes a device value "
                        f"via {dotted}() inside a hot loop "
                        f"({self.hot_reason}) — each call is a blocking "
                        f"device->host transfer",
                    )
                self.pending_dispatch = False
            return False
        if terminal == "item" and isinstance(node.func, ast.Attribute):
            taint = self._expr(node.func.value)
            if taint:
                if self._hot_now():
                    self._emit(
                        "RT303",
                        node,
                        f"{self.rec.qualname} calls .item() on a device "
                        f"value inside a hot loop ({self.hot_reason}) — "
                        f"blocking device->host sync per iteration",
                    )
                self.pending_dispatch = False
            return False
        if dotted == "print":
            taint = any(self._expr(a) for a in list(node.args))
            if taint:
                if self._hot_now():
                    self._emit(
                        "RT303",
                        node,
                        f"{self.rec.qualname} prints a device value inside "
                        f"a hot loop ({self.hot_reason}) — formatting "
                        f"forces a device->host sync; log a host copy "
                        f"outside the loop",
                    )
                self.pending_dispatch = False
            return False
        # Opaque call: evaluate operands, propagate taint through.
        got = False
        for arg in node.args:
            got = self._expr(arg) or got
        for kw in node.keywords:
            got = self._expr(kw.value) or got
        if isinstance(node.func, ast.Attribute):
            got = self._expr(node.func.value) or got
        return got

    def _jitted_call(self, node: ast.Call, callee: _Callee) -> None:
        # RT302: hazard arguments.
        for idx, arg in enumerate(node.args):
            is_static = idx in callee.static_nums
            if is_static:
                if _contains_len(arg):
                    self._hazard(
                        node, callee,
                        f"static argument {idx} derives from len(...) — "
                        f"every distinct length traces and compiles a new "
                        f"program (recompile storm under varying batch)",
                    )
                elif isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                    self._hazard(
                        node, callee,
                        f"static argument {idx} is an unhashable "
                        f"{type(arg).__name__.lower()} literal — jit "
                        f"cannot cache on it",
                    )
            else:
                self._traced_shape_hazard(node, callee, arg)
            self._expr(arg)
        for kw in node.keywords:
            if kw.arg is not None and kw.arg in callee.static_names:
                if _contains_len(kw.value):
                    self._hazard(
                        node, callee,
                        f"static argument '{kw.arg}' derives from len(...) "
                        f"— every distinct length compiles a new program",
                    )
                elif isinstance(kw.value, (ast.List, ast.Dict, ast.Set)):
                    self._hazard(
                        node, callee,
                        f"static argument '{kw.arg}' is an unhashable "
                        f"{type(kw.value).__name__.lower()} literal",
                    )
            else:
                self._traced_shape_hazard(node, callee, kw.value)
            self._expr(kw.value)
        # RT304: donations of plain names.
        for idx in callee.donate:
            if idx < len(node.args) and isinstance(node.args[idx], ast.Name):
                name = node.args[idx].id
                label = callee.wrap.target or "a jitted program" if callee.wrap else "a jitted program"
                self.donated[name] = (node.lineno, label)
        self.pending_dispatch = True
        self.pending_line = node.lineno

    def _traced_shape_hazard(self, node: ast.Call, callee: _Callee, arg: ast.expr) -> None:
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Subscript) and isinstance(sub.slice, ast.Slice):
                bounds = [sub.slice.lower, sub.slice.upper]
                if any(b is not None and _contains_len(b) for b in bounds):
                    self._hazard(
                        node, callee,
                        "a len()-bounded slice reaches a traced position — "
                        "the operand shape drifts per batch; pad to a "
                        "fixed bucket instead",
                    )
                    return

    def _hazard(self, node: ast.Call, callee: _Callee, detail: str) -> None:
        message = f"{self.rec.qualname}: {detail}"
        self._emit("RT302", node, message)
        if callee.wrap is not None:
            callee.wrap.hazards.append(
                {
                    "rule": "RT302",
                    "path": self.rec.path,
                    "line": node.lineno,
                    "message": message,
                }
            )


# ---------------------------------------------------------------------------
# whole-program drivers
# ---------------------------------------------------------------------------


def _scan_all(sources: Sequence[Tuple[str, str]]):
    mods: List[_ModuleScan] = []
    parse_errors: List[Finding] = []
    for path, source in sources:
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            parse_errors.append(
                Finding(
                    path=path,
                    line=e.lineno or 1,
                    col=(e.offset or 0) + 1,
                    rule="RT000",
                    message=f"file does not parse: {e.msg}",
                )
            )
            continue
        mods.append(_scan_module(path, source, tree))
    return mods, parse_errors


def _callee_registry(mods: Sequence[_ModuleScan]):
    """Cross-module terminal-name registry of jitted callables."""
    callees: Dict[str, _Callee] = {}
    self_callees: Dict[str, _Callee] = {}
    for mod in mods:
        for wrap in mod.wraps:
            if wrap.kind != "jit":
                continue
            cal = _Callee(wrap.donate, wrap.static_nums, wrap.static_names, wrap)
            if wrap.binding is None:
                continue
            scope, name = wrap.binding
            if scope in ("global", "def"):
                _merge_callee(callees, name, cal)
            elif scope == "self":
                _merge_callee(self_callees, name, cal)
        for fname, wrap in _forwarders(mod).items():
            _merge_callee(
                callees, fname,
                _Callee(wrap.donate, wrap.static_nums, wrap.static_names, wrap),
            )
        # Local watched bindings stay out of the cross-module registry —
        # a test's `fn = instrument(...)` must not make every `fn()` in
        # the tree look jitted (precision over recall).
        for scope, name in mod.watched:
            blank = _Callee((), (), (), None)
            if scope in ("global", "def"):
                callees.setdefault(name, blank)
            elif scope == "self":
                self_callees.setdefault(name, blank)
    return callees, self_callees


def _judge(mods: Sequence[_ModuleScan]) -> List[Finding]:
    findings: List[Finding] = []
    callees, self_callees = _callee_registry(mods)
    for mod in mods:
        in_test = _is_test_path(mod.path)
        local_by_fn: Dict[str, Dict[str, _Callee]] = {}
        for wrap in mod.wraps:
            if (
                wrap.kind == "jit"
                and wrap.binding
                and wrap.binding[0] == "local"
                and wrap.enclosing
            ):
                local_by_fn.setdefault(wrap.enclosing, {})[wrap.binding[1]] = (
                    _Callee(wrap.donate, wrap.static_nums, wrap.static_names, wrap)
                )
        # Wrap-site rules.
        for wrap in mod.wraps:
            if wrap.kind == "jit" and wrap.fresh_static:
                findings.append(
                    Finding(
                        path=wrap.path, line=wrap.line, col=wrap.col,
                        rule="RT302",
                        message=(
                            "static_argnums/static_argnames computed per "
                            "call — the jit cache keys on a fresh value "
                            "every invocation"
                        ),
                    )
                )
            findings.extend(_judge_rt301(wrap))
            if (
                wrap.kind == "jit"
                and not wrap.registered
                and not in_test
            ):
                findings.append(
                    Finding(
                        path=wrap.path, line=wrap.line, col=wrap.col,
                        rule="RT306",
                        message=(
                            f"jitted program "
                            f"{wrap.target or wrap.binding[1] if wrap.binding else wrap.target or '<anonymous>'} "
                            f"is invisible to the compile watch — wrap it in "
                            f"compile_watch.instrument('<name>', ...) so "
                            f"recompile storms attribute to a named program "
                            f"instead of (unregistered)"
                        ),
                    )
                )
        # Call/use-site rules.
        for rec in mod.funcs:
            judge = _FuncJudge(
                rec,
                callees,
                self_callees,
                local_by_fn.get(rec.qualname, {}),
                findings,
                in_test,
            )
            judge.run()
    return findings


def _judge_rt301(wrap: _Wrap) -> List[Finding]:
    if wrap.kind != "jit":
        return []
    if wrap.in_loop:
        return [
            Finding(
                path=wrap.path, line=wrap.line, col=wrap.col, rule="RT301",
                message=(
                    "jit wrapper constructed inside a loop — it re-traces "
                    "and re-compiles every iteration; hoist the wrapper "
                    "out of the loop"
                ),
            )
        ]
    if wrap.enclosing is None or wrap.returned:
        return []  # module level, or a factory returning the wrapper
    if wrap.binding is not None and wrap.binding[0] in ("global", "def"):
        return []  # lazy module-global cache idiom / decorated def
    fn_name = wrap.enclosing.rsplit(".", 1)[-1].lstrip("_").lower()
    if fn_name.startswith(_ONETIME_PREFIXES) or (
        fn_name.startswith("__") and fn_name.endswith("__")
    ) or fn_name.strip("_") in ("init",):
        return []
    if wrap.binding is not None and wrap.binding[0] == "self":
        enclosed = wrap.enclosing.rsplit(".", 1)[-1]
        if enclosed == "__init__" or enclosed.lstrip("_").startswith(_ONETIME_PREFIXES):
            return []
    return [
        Finding(
            path=wrap.path, line=wrap.line, col=wrap.col, rule="RT301",
            message=(
                f"jit wrapper constructed in the body of "
                f"{wrap.enclosing}() — a fresh wrapper (and compile-cache "
                f"entry) per call; hoist it to module scope or cache it"
            ),
        )
    ]


def _rule_filter(rules: Optional[Iterable[str]]) -> Optional[Set[str]]:
    if rules is None:
        return None
    wanted = {r.upper() for r in rules}
    unknown = wanted - set(RULES)
    if unknown:
        raise ValueError(f"unknown rule id(s): {', '.join(sorted(unknown))}")
    return wanted


def accel_sources(
    sources: Sequence[Tuple[str, str]],
    rules: Optional[Iterable[str]] = None,
) -> List[Finding]:
    """Analyze a set of (path, source) blobs as one program."""
    only = _rule_filter(rules)
    mods, findings = _scan_all(sources)
    findings = findings + _judge(mods)
    noqa_by_path = {
        mod.path: _parse_noqa(mod.source) for mod in mods
    }
    kept: List[Finding] = []
    for finding in findings:
        if only is not None and finding.rule in RULES and finding.rule not in only:
            continue
        noqa = noqa_by_path.get(finding.path, {})
        suppressed = noqa.get(finding.line)
        if finding.line in noqa and (
            suppressed is None or finding.rule in suppressed
        ):
            continue
        kept.append(finding)
    # Noqa hygiene (RT390) judges the RAW findings, and is itself
    # exempt from suppression — stale suppressions must not be able to
    # suppress their own report.
    if only is None or "RT390" in only:
        for mod in mods:
            kept.extend(
                noqa_hygiene(
                    mod.path,
                    mod.source,
                    findings,
                    family_digit="3",
                    known_ids=set(RULES),
                    hygiene_id="RT390",
                )
            )
    uniq: Dict[Tuple[str, int, str], Finding] = {}
    for f in kept:
        uniq.setdefault((f.path, f.line, f.rule), f)
    out = list(uniq.values())
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out


def accel_paths(
    paths: Sequence[str], rules: Optional[Iterable[str]] = None
) -> List[Finding]:
    sources, findings = _read_sources(paths)
    findings.extend(accel_sources(sources, rules))
    return findings


def _read_sources(paths: Sequence[str]):
    sources: List[Tuple[str, str]] = []
    findings: List[Finding] = []
    for file_path in _iter_py_files(paths):
        try:
            with open(file_path, "r", encoding="utf-8") as f:
                sources.append((file_path, f.read()))
        except (OSError, UnicodeDecodeError) as e:
            findings.append(
                Finding(
                    path=file_path,
                    line=1,
                    col=1,
                    rule="RT000",
                    message=f"unreadable: {e}",
                )
            )
    return sources, findings


# ---------------------------------------------------------------------------
# inventory (the doctor bridge)
# ---------------------------------------------------------------------------


def build_inventory_sources(sources: Sequence[Tuple[str, str]]) -> dict:
    """Machine-readable program inventory: every wrap site, its program
    name (if registered), and its RT302 hazards.  `compile_watch.
    static_hint()` resolves a live storm's program name against this so
    `rt.diagnose()`'s `verdict.compile` names the static fix site."""
    mods, _ = _scan_all(sources)
    _judge(mods)  # populates wrap.hazards
    programs = []
    for mod in mods:
        for wrap in mod.wraps:
            programs.append(
                {
                    "program": wrap.program,
                    "name_kind": wrap.program_kind,
                    "path": wrap.path,
                    "line": wrap.line,
                    "wrap": wrap.kind,
                    "target": wrap.target or None,
                    "registered": wrap.registered,
                    "donate_argnums": list(wrap.donate),
                    "static_argnums": list(wrap.static_nums),
                    "static_argnames": list(wrap.static_names),
                    "hazards": list(wrap.hazards),
                }
            )
    return {
        "version": 1,
        "programs": programs,
        "unregistered": [
            {"path": p["path"], "line": p["line"], "target": p["target"]}
            for p in programs
            if p["wrap"] == "jit" and not p["registered"]
        ],
    }


def build_inventory(paths: Sequence[str]) -> dict:
    sources, _ = _read_sources(paths)
    return build_inventory_sources(sources)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None, out=None) -> int:
    """CLI body shared by `ray_tpu devtools accel` and `python -m
    ray_tpu.devtools.accel`. Exit codes mirror lint/check/race: 0
    clean, 1 findings, 2 usage/IO errors."""
    out = out if out is not None else sys.stdout
    parser = argparse.ArgumentParser(
        prog="ray_tpu devtools accel",
        description=(
            "accelerator hot-path analyzer (rules RT301-RT306; "
            "suppress with '# rt: noqa[RT3xx]')"
        ),
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help=(
            "files or directories to analyze as ONE program (default: "
            "the installed ray_tpu package)"
        ),
    )
    parser.add_argument(
        "--json",
        action="store_true",
        dest="as_json",
        help="emit findings as a JSON list (CI mode)",
    )
    parser.add_argument(
        "--rules",
        help="comma-separated rule ids to run (default: all)",
    )
    parser.add_argument(
        "--list-rules",
        action="store_true",
        help="print the rule table and exit",
    )
    parser.add_argument(
        "--inventory",
        action="store_true",
        help=(
            "emit the program inventory JSON (wrap sites, registration, "
            "RT302 hazards) instead of findings — the doctor bridge"
        ),
    )
    try:
        args = parser.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code else 0
    if args.list_rules:
        for rule_id, title in RULES.items():
            print(f"{rule_id}  {title}", file=out)
        return 0
    if not args.paths:
        args.paths = [
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ]
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(
            f"accel: no such path(s): {', '.join(missing)}",
            file=sys.stderr,
        )
        return 2
    if args.inventory:
        print(json.dumps(build_inventory(args.paths), indent=2), file=out)
        return 0
    only = (
        [r.strip() for r in args.rules.split(",") if r.strip()]
        if args.rules
        else None
    )
    try:
        findings = accel_paths(args.paths, only)
    except ValueError as e:
        print(f"accel: {e}", file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps([asdict(f) for f in findings], indent=2), file=out)
    else:
        for finding in findings:
            print(finding.render(), file=out)
        if findings:
            print(f"{len(findings)} finding(s)", file=out)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
