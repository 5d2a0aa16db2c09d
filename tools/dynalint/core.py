"""dynalint core: findings, suppressions, file walking, the scan driver.

Pure stdlib + pure AST: dynalint never imports the code under analysis, so
it runs in <5s on CPU with no JAX initialisation and cannot be broken by an
import-time crash in the package it is checking.
"""

from __future__ import annotations

import ast
import hashlib
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable

SUPPRESS_RE = re.compile(
    r"#\s*dynalint:\s*(disable|disable-file)\s*=\s*"
    r"(?P<rules>[A-Z0-9,\s]+?)\s*(?:--\s*(?P<reason>.*))?$"
)
ALL = "ALL"

# Calls that put bytes on (or take bytes off) a wire. The seed set for the
# project-wide wire-taint closure (ProjectIndex): any project function that
# transitively reaches one of these is "wire-tagged" — DL009 refuses to let
# an async lock span await it, and DL007 anchors frame extraction on the
# write_frame sites.
WIRE_PRIMITIVES = frozenset({
    "write_frame", "read_frame", "open_connection", "open_unix_connection",
    "create_connection", "drain",
})

# JAX tracing wrappers the jit registry indexes. The code calls
# ``jax.shard_map`` directly; ``compat_shard_map`` is the alias the rule
# fixtures import it under.
JIT_WRAPPERS = frozenset({"jit", "pjit"})
SHARD_MAP_WRAPPERS = frozenset({"shard_map", "compat_shard_map"})


@dataclass
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str  # repo-relative posix path
    line: int
    col: int
    message: str
    hint: str = ""
    context: str = ""  # enclosing def/class qualname ("Engine.generate")
    detail: str = ""  # stable token for the fingerprint (not line-based)

    @property
    def fingerprint(self) -> str:
        """Line-number-independent identity: survives unrelated edits to
        the same file, so the committed baseline doesn't churn."""
        raw = f"{self.rule}|{self.path}|{self.context}|{self.detail}"
        return hashlib.sha1(raw.encode()).hexdigest()[:12]

    def render(self) -> str:
        out = f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"
        if self.hint:
            out += f"  [fix: {self.hint}]"
        return out


@dataclass
class _Suppression:
    """One ``disable=`` directive and the source lines it covers."""

    rules: frozenset[str]
    lines: frozenset[int]
    declared_line: int
    used: set[str] = field(default_factory=set)  # rules that matched


@dataclass
class Suppressions:
    """Per-file suppression map parsed from the ``disable=`` directives
    (SUPPRESS_RE above; spelled indirectly here so this docstring isn't
    itself parsed as one)."""

    entries: list[_Suppression] = field(default_factory=list)
    file_wide: dict[str, int] = field(default_factory=dict)  # rule -> line
    _file_wide_used: set[str] = field(default_factory=set)

    def covers(self, finding: Finding) -> bool:
        if finding.rule in self.file_wide:
            self._file_wide_used.add(finding.rule)
            return True
        if ALL in self.file_wide:
            self._file_wide_used.add(ALL)
            return True
        hit = False
        for e in self.entries:
            if finding.line in e.lines and (
                finding.rule in e.rules or ALL in e.rules
            ):
                e.used.add(finding.rule)
                hit = True
        return hit

    def unused(self) -> list[tuple[int, str]]:
        """(line, rule) pairs that silenced nothing — a stale disable
        (per-line OR file-wide) would otherwise mask the NEXT real
        finding forever."""
        out = [
            (e.declared_line, r)
            for e in self.entries
            for r in sorted(e.rules)
            if r != ALL and r not in e.used
        ]
        out.extend(
            (line, rule)
            for rule, line in sorted(self.file_wide.items())
            if rule != ALL and rule not in self._file_wide_used
        )
        return sorted(out)


def parse_suppressions(source: str) -> Suppressions:
    sup = Suppressions()
    lines = source.splitlines()
    for i, raw in enumerate(lines, start=1):
        m = SUPPRESS_RE.search(raw)
        if m is None:
            continue
        rules = frozenset(
            r.strip() for r in m.group("rules").split(",") if r.strip()
        )
        if m.group(1) == "disable-file":
            for r in rules:
                sup.file_wide.setdefault(r, i)
            continue
        covered = {i}
        if raw.strip().startswith("#"):
            # comment-only line: the suppression names the next *code*
            # line (reason text may continue over further comment lines)
            j = i + 1
            while j <= len(lines) and (
                not lines[j - 1].strip()
                or lines[j - 1].strip().startswith("#")
            ):
                j += 1
            covered.add(j)
        sup.entries.append(_Suppression(
            rules=rules, lines=frozenset(covered), declared_line=i,
        ))
    return sup


def annotate_parents(tree: ast.AST) -> list[ast.AST]:
    """Attach ``_dl_parent`` to every node (rules walk ancestry for
    try/finally placement, with-blocks, and enclosing scopes) and return
    the flat node list — computed once per file so the six rules don't
    each re-walk the tree (the <5s tier-1 budget is real)."""
    nodes: list[ast.AST] = [tree]
    i = 0
    while i < len(nodes):
        node = nodes[i]
        i += 1
        for child in ast.iter_child_nodes(node):
            child._dl_parent = node  # type: ignore[attr-defined]
            nodes.append(child)
    return nodes


def parents(node: ast.AST) -> Iterable[ast.AST]:
    cur = getattr(node, "_dl_parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "_dl_parent", None)


def enclosing_function(node: ast.AST):
    """Nearest enclosing function scope (lambda counts: code inside a
    lambda passed to ``asyncio.to_thread`` is NOT on the event loop)."""
    for p in parents(node):
        if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return p
    return None


def qualname(node: ast.AST) -> str:
    """Dotted path of enclosing class/function defs ("Engine.generate")."""
    names: list[str] = []
    cur: ast.AST | None = node
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            names.append(cur.name)
        cur = getattr(cur, "_dl_parent", None)
    return ".".join(reversed(names)) or "<module>"


def dotted(node: ast.AST) -> str | None:
    """Resolve an attribute/name chain to a dotted string, or None when a
    segment is dynamic. ``a.b().c`` resolves through calls as ``a.b.c`` so
    ``asyncio.get_running_loop().create_task`` is matchable."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    if isinstance(node, ast.Call):
        return dotted(node.func)
    return None


class FunctionInfo:
    """One function/method definition in the project symbol table."""

    __slots__ = (
        "path", "qualname", "node", "is_async", "params", "cls",
        "calls", "has_request_context", "return_call_names",
    )

    def __init__(self, path: str, qual: str, node, cls: str | None):
        self.path = path
        self.qualname = qual
        self.node = node
        self.is_async = isinstance(node, ast.AsyncFunctionDef)
        args = node.args
        self.params = tuple(
            a.arg for a in (*args.posonlyargs, *args.args, *args.kwonlyargs)
        )
        self.cls = cls
        # calls made DIRECTLY by this function (nested defs excluded: their
        # bodies only run when the nested function itself is called)
        self.calls: list[tuple[str, ast.Call]] = []
        # dotted names of calls appearing inside a ``return`` expression —
        # the seed observations for the device-returning closure (DL010)
        self.return_call_names: set[str] = set()
        self.has_request_context = any(
            _is_request_context_param(a)
            for a in (*args.posonlyargs, *args.args, *args.kwonlyargs)
        )

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]


def _is_request_context_param(arg: ast.arg) -> bool:
    """A parameter that carries the per-request Context (and therefore the
    request deadline). Matched by the repo convention: named ``context``,
    or annotated ``Context`` (``ctx: Context``) — a bare ``ctx`` without
    annotation is NOT assumed (dynalint's own ScanContext convention)."""
    if arg.arg == "context":
        return True
    ann = arg.annotation
    if ann is None:
        return False
    name = dotted(ann) or (
        ann.value if isinstance(ann, ast.Constant)
        and isinstance(ann.value, str) else ""
    )
    return (name or "").rsplit(".", 1)[-1] == "Context"


def _const_int_tuple(node: ast.AST | None) -> tuple[int, ...] | None:
    """``donate_argnums=(5, 6)`` / ``static_argnums=0`` -> (5, 6) / (0,).
    None when absent or not a literal (dynamic specs can't be indexed)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out: list[int] = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _const_str_tuple(node: ast.AST | None) -> tuple[str, ...] | None:
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out: list[str] = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _kw(call: ast.Call, name: str) -> ast.AST | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


class JitInfo:
    """One ``jax.jit``/``pjit``-wrapped callable in the jit registry.

    Two shapes, both indexed: module-level assignment
    (``decode_steps = jax.jit(decode_steps_impl, donate_argnums=(5, 6))``)
    and decorator (``@jax.jit`` / ``@(functools.)partial(jax.jit, ...)``).
    ``donate_argnums``/``static_argnums``/``static_argnames`` are the
    literal values when literal, else None (unknown)."""

    __slots__ = (
        "path", "name", "context", "line", "col", "kind", "wrapped",
        "donate_argnums", "static_argnums", "static_argnames",
        "wrapped_fn",
    )

    def __init__(self, path: str, name: str, context: str, line: int,
                 col: int, kind: str, wrapped: str | None,
                 donate_argnums, static_argnums, static_argnames):
        self.path = path
        self.name = name  # the callable's public (call-site) name
        self.context = context  # enclosing qualname of the definition
        self.line = line
        self.col = col
        self.kind = kind  # "assign" | "decorator"
        self.wrapped = wrapped  # dotted name of the wrapped impl (assign)
        self.donate_argnums = donate_argnums
        self.static_argnums = static_argnums
        self.static_argnames = static_argnames
        # resolved at finalize(): the wrapped FunctionInfo when findable
        self.wrapped_fn: FunctionInfo | None = None


class ShardMapSite:
    """One ``shard_map``/``compat_shard_map`` call site with its declared
    specs, for DL013."""

    __slots__ = (
        "path", "context", "line", "col", "node",
        "in_specs", "out_specs", "wrapped",
    )

    def __init__(self, path: str, context: str, node: ast.Call):
        self.path = path
        self.context = context
        self.node = node
        self.line = node.lineno
        self.col = node.col_offset
        self.in_specs = _kw(node, "in_specs")
        self.out_specs = _kw(node, "out_specs")
        self.wrapped = node.args[0] if node.args else _kw(node, "f")


def _extract_jit_assign(node: ast.Assign, path: str) -> JitInfo | None:
    """``name = jax.jit(impl, static_argnums=..., donate_argnums=...)``."""
    if len(node.targets) != 1 or not isinstance(node.targets[0], ast.Name):
        return None
    call = node.value
    if not isinstance(call, ast.Call):
        return None
    last = (dotted(call.func) or "").rsplit(".", 1)[-1]
    if last not in JIT_WRAPPERS:
        return None
    wrapped = dotted(call.args[0]) if call.args else None
    return JitInfo(
        path=path, name=node.targets[0].id, context=qualname(node),
        line=node.lineno, col=node.col_offset, kind="assign",
        wrapped=wrapped,
        donate_argnums=_const_int_tuple(_kw(call, "donate_argnums")),
        static_argnums=_const_int_tuple(_kw(call, "static_argnums")),
        static_argnames=_const_str_tuple(_kw(call, "static_argnames")),
    )


def _extract_jit_decorator(node, path: str) -> JitInfo | None:
    """``@jax.jit`` / ``@partial(jax.jit, static_argnames=(...))`` on a
    def: the decorated function IS the jitted callable."""
    for dec in node.decorator_list:
        last = (dotted(dec) or "").rsplit(".", 1)[-1]
        kw_src: ast.Call | None = None
        if isinstance(dec, ast.Call):
            if last in JIT_WRAPPERS:
                kw_src = dec  # @jax.jit(static_argnums=...)
            elif last == "partial" and dec.args:
                inner = (dotted(dec.args[0]) or "").rsplit(".", 1)[-1]
                if inner in JIT_WRAPPERS:
                    kw_src = dec  # @partial(jax.jit, ...): kwargs on partial
                else:
                    continue
            else:
                continue
        elif last not in JIT_WRAPPERS:
            continue
        return JitInfo(
            path=path, name=node.name, context=qualname(node),
            # anchor at the DECORATOR: that is where donation/static
            # declarations live, and where a suppression comment lands
            line=dec.lineno, col=dec.col_offset, kind="decorator",
            wrapped=node.name,
            donate_argnums=_const_int_tuple(
                _kw(kw_src, "donate_argnums") if kw_src else None),
            static_argnums=_const_int_tuple(
                _kw(kw_src, "static_argnums") if kw_src else None),
            static_argnames=_const_str_tuple(
                _kw(kw_src, "static_argnames") if kw_src else None),
        )
    return None


class ProjectIndex:
    """Project-wide symbol table + call graph, built once per scan.

    The interprocedural substrate under DL007/DL008/DL009: which functions
    exist, what each one calls, which ones transitively reach a wire
    primitive, and which ones accept a per-request Context. Pure AST —
    method resolution is name-based with a precision bias (self-calls
    resolve within the class; free calls resolve only when every project
    definition of that name agrees)."""

    def __init__(self) -> None:
        self.functions: dict[tuple[str, str], FunctionInfo] = {}
        self.by_name: dict[str, list[FunctionInfo]] = {}
        self.contexts: list["ScanContext"] = []
        self._wire_tainted: set[tuple[str, str]] = set()
        self.context_callee_names: set[str] = set()
        # -- the jit registry (DL010-DL015 substrate) ----------------------
        self.jits: dict[tuple[str, str], JitInfo] = {}  # (path, name)
        self.jit_names: dict[str, list[JitInfo]] = {}
        self.shard_maps: list[ShardMapSite] = []
        # hot closure: functions transitively reachable from a step-thread
        # root (threading.Thread targets + catalog.HOT_PATH_ROOTS)
        self.hot: set[tuple[str, str]] = set()
        self._thread_root_specs: list[tuple] = []
        self._device_returning: set[tuple[str, str]] = set()

    def add_file(self, ctx: "ScanContext") -> None:
        self.contexts.append(ctx)
        # one pass over the pre-built flat node list (NOT a walk per
        # function — the <5s tier-1 budget is real): defs register, calls
        # attach to their nearest enclosing def
        by_node: dict[ast.AST, FunctionInfo] = {}
        for node in ctx.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = qualname(node)
                cls = None
                for p in parents(node):
                    if isinstance(p, ast.ClassDef):
                        cls = p.name
                        break
                    if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        break
                info = FunctionInfo(ctx.path, qual, node, cls)
                by_node[node] = info
                self.functions[(ctx.path, qual)] = info
                if isinstance(node, ast.FunctionDef):
                    jit = _extract_jit_decorator(node, ctx.path)
                    if jit is not None:
                        self.jits[(ctx.path, jit.name)] = jit
            elif isinstance(node, ast.Assign):
                jit = _extract_jit_assign(node, ctx.path)
                if jit is not None:
                    self.jits[(ctx.path, jit.name)] = jit
            elif isinstance(node, ast.Call):
                fn = enclosing_function(node)
                while isinstance(fn, ast.Lambda):
                    fn = enclosing_function(fn)
                info = by_node.get(fn)
                name = dotted(node.func)
                if info is not None and name:
                    info.calls.append((name, node))
                    for p in parents(node):
                        if p is fn:
                            break
                        if isinstance(p, ast.Return):
                            info.return_call_names.add(name)
                            break
                last = (name or "").rsplit(".", 1)[-1]
                if last in SHARD_MAP_WRAPPERS:
                    self.shard_maps.append(
                        ShardMapSite(ctx.path, qualname(node), node)
                    )
                elif last == "Thread":
                    self._note_thread_target(ctx.path, node)

    def _note_thread_target(self, path: str, node: ast.Call) -> None:
        """``threading.Thread(target=self.X / target=fn)``: X/fn is a hot
        root — a dedicated worker thread's entry point (the engine's step
        thread is ``Thread(target=self._thread_loop)``)."""
        for kw in node.keywords:
            if kw.arg != "target":
                continue
            v = kw.value
            if (
                isinstance(v, ast.Attribute)
                and isinstance(v.value, ast.Name)
                and v.value.id == "self"
            ):
                cls = None
                for p in parents(node):
                    if isinstance(p, ast.ClassDef):
                        cls = p.name
                        break
                if cls:
                    self._thread_root_specs.append((path, f"{cls}.{v.attr}"))
            elif isinstance(v, ast.Name):
                self._thread_root_specs.append((path, v.id))

    def finalize(self) -> None:
        self.by_name.clear()
        for info in self.functions.values():
            self.by_name.setdefault(info.name, []).append(info)
        self.context_callee_names = {
            info.name
            for info in self.functions.values()
            if info.has_request_context and not info.name.startswith("__")
        }
        self._compute_wire_taint()
        self.jit_names.clear()
        for (path, _name), jit in self.jits.items():
            self.jit_names.setdefault(jit.name, []).append(jit)
            if jit.wrapped:
                last = jit.wrapped.rsplit(".", 1)[-1]
                jit.wrapped_fn = self.functions.get((path, last))
                if jit.wrapped_fn is None:
                    cands = self.by_name.get(last, [])
                    if len(cands) == 1:
                        jit.wrapped_fn = cands[0]
        self._compute_hot()
        self._compute_device_returning()

    # -- hot closure (step-thread reachability) -----------------------------

    # a bare name with more candidate definitions than this is too generic
    # to propagate hotness through (put/get/run smear the whole project)
    _HOT_FANOUT_CAP = 6

    # method names every stdlib type answers: ``payload.encode()`` must
    # not make VitEncoder.encode hot just because both spell "encode"
    _HOT_GENERIC_METHODS = frozenset({
        "encode", "decode", "items", "keys", "values", "join", "read",
        "write", "close", "copy", "update", "strip", "split", "append",
        "pop", "clear", "add", "remove", "result", "set",
    })

    def _hot_roots(self) -> set[tuple[str, str]]:
        roots = {
            key for key in self._thread_root_specs if key in self.functions
        }
        catalog = None
        if self.contexts:
            catalog = self.contexts[0].catalog
        for spec in getattr(catalog, "HOT_PATH_ROOTS", {}) or {}:
            # "path/suffix.py::Qual.name" — suffix-matched so the catalog
            # entry survives a directory move
            suffix, _, qual = spec.partition("::")
            for (path, q) in self.functions:
                if q == qual and path.endswith(suffix):
                    roots.add((path, q))
        return roots

    def _compute_hot(self) -> None:
        hot = self.hot
        hot.clear()
        frontier = list(self._hot_roots())
        while frontier:
            key = frontier.pop()
            if key in hot:
                continue
            hot.add(key)
            info = self.functions[key]
            for name, _ in info.calls:
                last = name.rsplit(".", 1)[-1]
                if (
                    "." in name
                    and name != f"self.{last}"
                    and last in self._HOT_GENERIC_METHODS
                ):
                    continue
                cands = self._resolve(info, name)
                if not cands or len(cands) > self._HOT_FANOUT_CAP:
                    continue
                for c in cands:
                    # async callees don't run on the step thread (calling
                    # one from it would be its own bug)
                    if c.is_async:
                        continue
                    # a closure can only be called from inside the scope
                    # that defines it — by-name resolution from anywhere
                    # else is always a false edge
                    if enclosing_function(c.node) is not None and not (
                        c.path == info.path
                        and c.qualname.startswith(info.qualname + ".")
                    ):
                        continue
                    k2 = (c.path, c.qualname)
                    if k2 not in hot:
                        frontier.append(k2)

    def is_hot(self, info: FunctionInfo | None) -> bool:
        """Is this function transitively reachable from a step-thread
        root (Thread target or catalogued hot-loop entry)?"""
        return info is not None and (info.path, info.qualname) in self.hot

    # -- device-returning closure (DL010 taint) -----------------------------

    def _compute_device_returning(self) -> None:
        dr = self._device_returning
        dr.clear()
        for key, info in self.functions.items():
            if any(
                n.rsplit(".", 1)[-1] in self.jit_names
                for n in info.return_call_names
            ):
                dr.add(key)
        changed = True
        while changed:
            changed = False
            for key, info in self.functions.items():
                if key in dr:
                    continue
                for name in info.return_call_names:
                    cands = self._resolve(info, name)
                    # same unanimity rule as the wire taint
                    if cands and all(
                        (c.path, c.qualname) in dr for c in cands
                    ):
                        dr.add(key)
                        changed = True
                        break

    def is_device_call(
        self, caller: FunctionInfo | None, name: str
    ) -> bool:
        """Does calling ``name`` from ``caller`` return device values (a
        jit-registry callable, or a function that transitively returns
        one — e.g. the model-family adapter methods)?"""
        if name.rsplit(".", 1)[-1] in self.jit_names:
            return True
        if caller is None:
            return False
        cands = self._resolve(caller, name)
        return bool(cands) and all(
            (c.path, c.qualname) in self._device_returning for c in cands
        )

    # -- wire taint ---------------------------------------------------------

    def _resolve(self, caller: FunctionInfo, name: str) -> list[FunctionInfo]:
        """Best-effort callee resolution for ``name`` as called from
        ``caller``. Exactly ``self.X`` resolves within the caller's class
        (``self.other.X`` is some OTHER object's method — falling through
        to the bare-name candidates); otherwise all project definitions
        of the bare name are returned."""
        last = name.rsplit(".", 1)[-1]
        if name == f"self.{last}" and caller.cls:
            hit = self.functions.get((caller.path, f"{caller.cls}.{last}"))
            if hit is not None:
                return [hit]
        return self.by_name.get(last, [])

    def context_accepting(
        self, caller: FunctionInfo, name: str
    ) -> bool:
        """Does calling ``name`` from ``caller`` reach a context-accepting
        callee? Same unanimity rule as the wire taint: a bare name only
        counts when EVERY project definition of it takes a request
        context — ``cache.put`` must not smear just because some other
        ``put`` somewhere accepts one."""
        cands = self._resolve(caller, name)
        return bool(cands) and all(c.has_request_context for c in cands)

    def _compute_wire_taint(self) -> None:
        tainted = self._wire_tainted
        tainted.clear()
        for key, info in self.functions.items():
            if any(
                n.rsplit(".", 1)[-1] in WIRE_PRIMITIVES
                for n, _ in info.calls
            ):
                tainted.add(key)
        changed = True
        while changed:
            changed = False
            for key, info in self.functions.items():
                if key in tainted:
                    continue
                for name, _ in info.calls:
                    cands = self._resolve(info, name)
                    # unanimity rule (precision over recall): only taint
                    # through a bare name when EVERY definition of it is
                    # tainted — InMemoryHub.put must not smear RemoteHub
                    # taint onto queue.put
                    if cands and all(
                        (c.path, c.qualname) in tainted for c in cands
                    ):
                        tainted.add(key)
                        changed = True
                        break

    def is_wire_call(
        self, caller: FunctionInfo | None, name: str
    ) -> bool:
        """Does calling ``name`` (dotted) from ``caller`` reach the wire?"""
        if name.rsplit(".", 1)[-1] in WIRE_PRIMITIVES:
            return True
        if caller is None:
            return False
        cands = self._resolve(caller, name)
        return bool(cands) and all(
            (c.path, c.qualname) in self._wire_tainted for c in cands
        )

    def function_at(self, path: str, node: ast.AST) -> FunctionInfo | None:
        fn = node if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)
        ) else enclosing_function(node)
        while isinstance(fn, ast.Lambda):
            fn = enclosing_function(fn)
        if fn is None:
            return None
        return self.functions.get((path, qualname(fn)))


class ScanContext:
    """Everything one rule invocation gets to look at for one file."""

    def __init__(
        self,
        tree: ast.Module,
        source: str,
        path: str,
        catalog=None,
        nodes: list[ast.AST] | None = None,
    ):
        self.tree = tree
        self.source = source
        self.path = path
        self.lines = source.splitlines()
        # flat pre-order node list; every rule iterates this instead of
        # re-walking the tree
        self.nodes = annotate_parents(tree) if nodes is None else nodes
        # modules that participate in the async/threaded runtime: a sync
        # time.sleep in one of these is loop-reachable until proven
        # otherwise (DL001 tier 2)
        self.imports_async_runtime = any(
            isinstance(n, (ast.Import, ast.ImportFrom))
            and any(
                (a.name if isinstance(n, ast.Import) else n.module or "")
                .split(".")[0] in ("asyncio", "threading")
                for a in n.names
            )
            for n in self.nodes
        )
        if catalog is None:
            from tools.dynalint import catalog as catalog_mod

            catalog = catalog_mod
        self.catalog = catalog
        # cross-file accumulators (runner-owned; rules append)
        self.used_fault_sites: set[str] = set()
        self.used_metric_names: set[str] = set()
        self.used_span_names: set[str] = set()
        # per-file notices the runner surfaces (unused suppressions)
        self.warnings: list[str] = []
        # the project-wide symbol table / call graph; set by the runner
        # before any rule runs (single-file scans get a one-file index)
        self.project: ProjectIndex | None = None


def _parse_file(
    path: Path, root: Path, catalog=None
) -> tuple[ScanContext | None, Suppressions | None, Finding | None]:
    """Parse one file into a ScanContext (+its suppressions), or a DL000
    syntax-error finding."""
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    source = path.read_text(encoding="utf-8", errors="replace")
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        f = Finding(
            rule="DL000",
            path=rel,
            line=e.lineno or 1,
            col=e.offset or 0,
            message=f"syntax error: {e.msg}",
            detail="syntax-error",
        )
        return None, None, f
    ctx = ScanContext(tree, source, rel, catalog=catalog)
    return ctx, parse_suppressions(source), None


def _run_rules(
    ctxs: list[tuple[ScanContext, Suppressions]],
    project: ProjectIndex,
    rules=None,
) -> tuple[list[Finding], list[Finding]]:
    """Run per-file rules over every ctx, then the project-level rules
    over the whole index; route each finding through its own file's
    suppressions."""
    from tools.dynalint.rules import PROJECT_RULES, RULES

    sups = {ctx.path: sup for ctx, sup in ctxs}
    active: list[Finding] = []
    suppressed: list[Finding] = []

    def route(finding: Finding) -> None:
        sup = sups.get(finding.path)
        if sup is not None and sup.covers(finding):
            suppressed.append(finding)
        else:
            active.append(finding)

    for ctx, _sup in ctxs:
        ctx.project = project
        for rule_id, rule in RULES.items():
            if rules is not None and rule_id not in rules:
                continue
            if rule_id in PROJECT_RULES:
                continue  # runs once over the index, below
            for finding in rule.check(ctx):
                route(finding)
    for rule_id in PROJECT_RULES:
        if rules is not None and rule_id not in rules:
            continue
        rule = RULES[rule_id]
        for finding in rule.check_project(project):
            route(finding)
    if rules is None:
        # only meaningful under the full rule set: a DL004 disable looks
        # "unused" when DL004 wasn't run
        for ctx, sup in ctxs:
            for line, rule_id in sup.unused():
                ctx.warnings.append(
                    f"{ctx.path}:{line}: unused suppression for {rule_id} "
                    "— the finding is gone; remove the disable before it "
                    "masks a new one"
                )
    return active, suppressed


def scan_file(
    path: Path,
    root: Path,
    rules=None,
    catalog=None,
) -> tuple[list[Finding], list[Finding], ScanContext | None]:
    """Scan one file standalone (fixtures, ad-hoc checks). Project-level
    rules run over a one-file index, so a self-contained fixture can pin
    DL007 behavior. Returns (active, suppressed, ctx); ctx is None when
    the file failed to parse (which is itself a finding)."""
    ctx, sup, err = _parse_file(path, root, catalog=catalog)
    if err is not None:
        return [err], [], None
    project = ProjectIndex()
    project.add_file(ctx)
    project.finalize()
    active, suppressed = _run_rules([(ctx, sup)], project, rules=rules)
    return active, suppressed, ctx


def iter_python_files(paths: list[Path]) -> Iterable[Path]:
    for p in paths:
        if p.is_file() and p.suffix == ".py":
            yield p
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" in f.parts:
                    continue
                if "dynalint" in f.parts and "fixtures" in f.parts:
                    # the golden fixtures are findings BY DESIGN; scanning
                    # tools/ must not turn them into gate failures
                    continue
                yield f


def build_index(paths: list[Path], root: Path, catalog=None) -> ProjectIndex:
    """Parse ``paths`` into a finalized ProjectIndex without running any
    rules (wire-schema extraction / --emit-protocol)."""
    project = ProjectIndex()
    for path in iter_python_files(paths):
        ctx, _sup, err = _parse_file(path, root, catalog=catalog)
        if err is None:
            project.add_file(ctx)
    project.finalize()
    return project


def run_paths(
    paths: list[Path],
    root: Path,
    rules=None,
    catalog=None,
    wire_schema_path: Path | None = None,
) -> tuple[list[Finding], list[Finding], list[str]]:
    """Scan all files under ``paths``. Returns (findings, suppressed,
    cross-file warnings). Warnings cover catalog drift in the *stale*
    direction — a catalogued fault site, metric name, or wire op that no
    code uses — which can't be attributed to any single file.

    ``wire_schema_path``: when set (the CLI passes it for default-scope
    scans), the extracted wire schema is additionally diffed against this
    committed catalog in both directions (DL007)."""
    ctxs: list[tuple[ScanContext, Suppressions]] = []
    findings: list[Finding] = []
    project = ProjectIndex()
    for path in iter_python_files(paths):
        ctx, sup, err = _parse_file(path, root, catalog=catalog)
        if err is not None:
            findings.append(err)
            continue
        ctxs.append((ctx, sup))
        project.add_file(ctx)
    project.finalize()
    active, suppressed = _run_rules(ctxs, project, rules=rules)
    findings.extend(active)
    warnings: list[str] = []
    for ctx, _sup in ctxs:
        warnings.extend(ctx.warnings)
    if catalog is None:
        from tools.dynalint import catalog as catalog_mod

        catalog = catalog_mod
    # stale-catalog detection only makes sense over a whole tree: a
    # single-file scan trivially "doesn't use" almost every entry
    if any(p.is_dir() for p in paths):
        used_sites: set[str] = set()
        used_metrics: set[str] = set()
        used_spans: set[str] = set()
        for ctx, _sup in ctxs:
            used_sites |= ctx.used_fault_sites
            used_metrics |= ctx.used_metric_names
            used_spans |= ctx.used_span_names
        if rules is None or "DL006" in rules:
            for site in sorted(set(catalog.FAULT_SITES) - used_sites):
                warnings.append(
                    f"catalog: fault site {site!r} is documented but no "
                    f"faults fire()/fire_sync()/corrupt_bytes() call uses "
                    f"it (stale catalog entry?)"
                )
            for name in sorted(set(catalog.METRIC_NAMES) - used_metrics):
                warnings.append(
                    f"catalog: metric {name!r} is documented but never "
                    f"registered (stale catalog entry?)"
                )
            span_catalog = set(getattr(catalog, "SPAN_NAMES", ()))
            for name in sorted(span_catalog - used_spans):
                warnings.append(
                    f"catalog: span {name!r} is documented but never "
                    f"emitted (stale catalog entry?)"
                )
        if rules is None or "DL007" in rules:
            from tools.dynalint import wire

            warnings.extend(wire.unsent_op_warnings(project))
            if wire_schema_path is not None:
                findings.extend(
                    wire.schema_drift_findings(project, wire_schema_path)
                )
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, suppressed, warnings
