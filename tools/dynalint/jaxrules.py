"""The JAX hot-path analysis layer (DL010–DL015).

Built on the jit registry in core.ProjectIndex: every ``jax.jit``/``pjit``
wrapped callable with its ``donate_argnums``/``static_argnums``/
``static_argnames``, every ``shard_map`` site with its declared specs, the
step-thread hot closure (``threading.Thread`` targets plus
catalog.HOT_PATH_ROOTS), and the device-returning closure (functions whose
return value transitively comes from a jit call).

The bug classes these encode are the ones that silently eat serving
efficiency without failing a single test on CPU:

  * DL010 — a host↔device sync on the step thread serializes the device
    pipeline;
  * DL011 — a retrace per request turns microseconds into seconds;
  * DL012 — reading a donated buffer is undefined behavior; NOT donating a
    pool doubles its HBM footprint per step;
  * DL013 — a pytree leaf without a PartitionSpec (the QuantPool scale
    leaves) forces whole code paths off the fused kernels;
  * DL014 — a capability gate that downgrades fused→XLA or quantized→bf16
    without accounting for itself is invisible until a benchmark regresses
    (ROADMAP #7's "fp8 + tp>1 silently takes the XLA path");
  * DL015 — a threading.Lock held across ``await``, or two locks taken in
    opposite orders on the step-thread/asyncio boundary, is a deadlock
    waiting for kill-9 churn.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.dynalint.core import (
    Finding,
    FunctionInfo,
    JitInfo,
    ProjectIndex,
    ScanContext,
    ShardMapSite,
    dotted,
    enclosing_function,
    parents,
    qualname,
)

_LOG_METHODS = {"debug", "info", "warning", "error", "exception", "critical"}


def _last(name: str | None) -> str:
    return (name or "").rsplit(".", 1)[-1]


def _loaded_names(node: ast.AST) -> set[str]:
    return {
        n.id for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


def _own_call(info: FunctionInfo, node: ast.Call) -> bool:
    """Is this call made DIRECTLY by ``info`` (not by a nested def, whose
    body has its own FunctionInfo and gets checked on its own)?"""
    fn = enclosing_function(node)
    while isinstance(fn, ast.Lambda):
        fn = enclosing_function(fn)
    return fn is info.node


# --------------------------------------------------------------------------
# DL010 host-sync-in-hot-path
# --------------------------------------------------------------------------

# calls that force the host to wait for the device regardless of operand
_ALWAYS_SYNC = frozenset({"device_get", "block_until_ready"})
# conversions that force a sync only when fed a device value
_TAINT_SYNC_METHODS = frozenset({"item", "tolist"})
_TAINT_SYNC_NAMES = frozenset({"float", "int", "bool"})
_TAINT_SYNC_NP = frozenset({"asarray", "array"})


class HostSyncInHotPath:
    """DL010: host↔device sync reachable from the engine step loop.

    The step thread owns the device: every ``jax.device_get``/
    ``block_until_ready``/``.item()``/``float(...)``/``np.asarray(...)``
    on a device value it executes is serial time added to EVERY decode
    step — the device sits idle behind the host for the full transfer.
    Deliberate, *accounted* syncs are the discipline this repo already
    has: wrap them in ``with self._phase("...d2h...")`` so the profiler
    attributes the wait (dispatch.d2h_wait / readmit.d2h_wait /
    process.d2h_sync), and DL010 treats the block as exempt. Anything
    else is either hoisted off the step thread or suppressed with the
    reason it must block.

    Hot functions = the transitive closure from ``threading.Thread``
    targets and catalog.HOT_PATH_ROOTS; device values = results of
    jit-registry callables (and of functions that transitively return
    one, e.g. the model-family adapters), tracked through assignments.
    """

    id = "DL010"
    name = "host-sync-in-hot-path"

    def check(self, ctx: ScanContext) -> Iterable[Finding]:
        project = ctx.project
        if project is None or not project.hot:
            return
        for (path, _qual), info in project.functions.items():
            if path != ctx.path or not project.is_hot(info):
                continue
            yield from self._check_fn(ctx, project, info)

    def _check_fn(self, ctx, project, info) -> Iterable[Finding]:
        tainted = self._device_tainted(project, info)
        for node in ast.walk(info.node):
            if not isinstance(node, ast.Call) or not _own_call(info, node):
                continue
            name = dotted(node.func) or ""
            last = _last(name)
            hit: str | None = None
            if last in _ALWAYS_SYNC:
                hit = last
            elif last in _TAINT_SYNC_METHODS and isinstance(
                node.func, ast.Attribute
            ):
                if _loaded_names(node.func.value) & tainted:
                    hit = f".{last}()"
            elif (
                isinstance(node.func, ast.Name)
                and node.func.id in _TAINT_SYNC_NAMES
                and node.args
                and _loaded_names(node.args[0]) & tainted
            ):
                hit = f"{node.func.id}()"
            elif (
                last in _TAINT_SYNC_NP
                and name.split(".", 1)[0] in ("np", "numpy")
                and node.args
                and _loaded_names(node.args[0]) & tainted
            ):
                hit = f"{name}()"
            if hit is None or self._accounted(node):
                continue
            yield Finding(
                rule=self.id, path=ctx.path,
                line=node.lineno, col=node.col_offset,
                message=f"{hit} on the step-thread hot path "
                        f"({info.qualname}) blocks the device pipeline "
                        "for the full device->host transfer",
                hint="hoist the sync off the step thread, or account for "
                     "it: wrap in `with self._phase(\"...d2h...\")` so "
                     "the dispatch-overhead profile attributes the wait",
                context=info.qualname,
                detail=f"sync:{info.qualname}:{hit}",
            )

    @staticmethod
    def _device_tainted(project, info) -> set[str]:
        """Local names bound (incl. tuple-unpack) from device-returning
        calls inside this function."""
        tainted: set[str] = set()
        for node in ast.walk(info.node):
            if not (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
            ):
                continue
            name = dotted(node.value.func)
            if not name or not project.is_device_call(info, name):
                continue
            for t in node.targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for el in elts:
                    if isinstance(el, ast.Name):
                        tainted.add(el.id)
        return tainted

    @staticmethod
    def _accounted(node: ast.AST) -> bool:
        """Inside a ``with self._phase("...d2h...")`` block: the sync is
        deliberate and profiler-attributed — the repo's accounted-sync
        discipline (dispatch.d2h_wait / readmit.d2h_wait /
        process.d2h_sync)."""
        for p in parents(node):
            if not isinstance(p, ast.With):
                continue
            for item in p.items:
                ce = item.context_expr
                if not (
                    isinstance(ce, ast.Call)
                    and _last(dotted(ce.func)) == "_phase"
                    and ce.args
                ):
                    continue
                arg = ce.args[0]
                if (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and "d2h" in arg.value
                ):
                    return True
        return False


# --------------------------------------------------------------------------
# DL011 retrace-hazard
# --------------------------------------------------------------------------

# trace-time-structural attribute reads on a traced value (shape/dtype are
# Python objects under tracing — branching on them specializes, it does
# not fail; any OTHER use of the value in a Python branch does)
_STRUCTURAL_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding"})
# calls that probe the PYTREE STRUCTURE of their argument (Python type /
# arity), which is static under tracing — `if is_quant(cache):` picks the
# QuantPool vs array form of the program, it never reads traced data
_STRUCTURAL_CALLS = frozenset({"len", "isinstance", "type", "is_quant"})


class RetraceHazard:
    """DL011: per-call-varying values where jit expects trace constants.

    Two shapes:

      * data-dependent Python branching inside a jit-wrapped body — an
        ``if``/``while`` on a traced parameter's *value* raises
        TracerBoolConversionError at best; at worst the branch happens to
        work at trace time and silently bakes one side in;
      * a call site feeding a per-call-varying expression (``len(...)``,
        ``.shape[...]``, arithmetic) to a ``static_argnames`` parameter —
        every distinct value is a full retrace + XLA compile on the hot
        path (the repo buckets these: cfg.bucket_for / padded shapes).
    """

    id = "DL011"
    name = "retrace-hazard"

    def check(self, ctx: ScanContext) -> Iterable[Finding]:
        project = ctx.project
        if project is None:
            return
        yield from self._check_traced_branches(ctx, project)
        yield from self._check_static_callsites(ctx, project)

    def _check_traced_branches(self, ctx, project) -> Iterable[Finding]:
        for (path, _name), jit in sorted(project.jits.items()):
            fn = jit.wrapped_fn
            if path != ctx.path or fn is None or fn.path != ctx.path:
                continue
            static = set(jit.static_argnames or ())
            for i in jit.static_argnums or ():
                if i < len(fn.params):
                    static.add(fn.params[i])
            traced = {
                p for p in fn.params if p not in static and p != "self"
            }
            for node in ast.walk(fn.node):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                hit = self._traced_value_use(node.test, traced)
                if hit is None:
                    continue
                yield Finding(
                    rule=self.id, path=ctx.path,
                    line=node.lineno, col=node.col_offset,
                    message=f"Python branch on traced parameter {hit!r} "
                            f"inside jit-wrapped {fn.name!r} — traced "
                            "values have no Python truth value; this "
                            "either crashes at trace time or silently "
                            "bakes one side into the compiled program",
                    hint="use jnp.where/lax.cond on the traced value, or "
                         f"declare {hit!r} in static_argnames (then bucket "
                         "its values to bound retraces)",
                    context=fn.qualname,
                    detail=f"branch:{fn.qualname}:{hit}",
                )

    @staticmethod
    def _traced_value_use(test: ast.AST, traced: set[str]) -> str | None:
        for n in ast.walk(test):
            if not (isinstance(n, ast.Name) and n.id in traced
                    and isinstance(n.ctx, ast.Load)):
                continue
            parent = getattr(n, "_dl_parent", None)
            if (
                isinstance(parent, ast.Attribute)
                and parent.attr in _STRUCTURAL_ATTRS
            ):
                continue  # x.shape / x.dtype: static under tracing
            if isinstance(parent, ast.Call) and _last(
                dotted(parent.func)
            ) in _STRUCTURAL_CALLS:
                continue  # len(x) / is_quant(x) / isinstance: structural
            if isinstance(parent, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot))
                for op in parent.ops
            ):
                continue  # `x is None`: pytree-structure check, static
            return n.id
        return None

    def _check_static_callsites(self, ctx, project) -> Iterable[Finding]:
        for info in project.functions.values():
            if info.path != ctx.path:
                continue
            for name, call in info.calls:
                jits = project.jit_names.get(_last(name))
                if not jits:
                    continue
                statics = {j.static_argnames for j in jits}
                if len(statics) != 1:
                    continue  # same name, different signatures: stay quiet
                static_names = statics.pop() or ()
                for kw in call.keywords:
                    if kw.arg not in static_names:
                        continue
                    how = self._varying(kw.value)
                    if how is None:
                        continue
                    yield Finding(
                        rule=self.id, path=ctx.path,
                        line=call.lineno, col=call.col_offset,
                        message=f"static arg {kw.arg!r} of jitted "
                                f"{_last(name)!r} fed a per-call-varying "
                                f"expression ({how}) — every distinct "
                                "value is a full retrace + XLA compile",
                        hint="bucket the value (cfg.bucket_for / pad to a "
                             "fixed set) or make the parameter traced",
                        context=info.qualname,
                        detail=f"static:{info.qualname}:{kw.arg}",
                    )

    @staticmethod
    def _varying(expr: ast.AST) -> str | None:
        for n in ast.walk(expr):
            if isinstance(n, ast.Call) and _last(dotted(n.func)) == "len":
                return "len(...)"
            if isinstance(n, ast.Attribute) and n.attr == "shape":
                return ".shape"
            if isinstance(n, ast.BinOp):
                return "arithmetic"
        return None


# --------------------------------------------------------------------------
# DL012 donation-audit
# --------------------------------------------------------------------------

# parameter names that carry a KV pool / latent cache — the multi-GiB
# buffers where donation is the difference between in-place updates and a
# second full copy in HBM every step
_POOL_PARAMS = frozenset({
    "k_pages", "v_pages", "kv_pages", "latent_pages", "kv_latent",
})


class DonationAudit:
    """DL012: donated buffers read after the call; pool buffers undonated.

    ``donate_argnums`` hands the buffer's memory to XLA: the caller's
    reference is invalid the moment the call is issued — reading it
    afterwards returns garbage (or crashes with buffer-deleted, backend
    depending). The repo idiom rebinds in the same statement
    (``self.k_pages, self.v_pages = fam.decode_steps(..., self.k_pages,
    self.v_pages, ...)``), which is safe and what the rule checks for.

    The registry-level check is the flip side: a jit whose signature
    takes a pool-sized buffer (k_pages/v_pages/latent) WITHOUT donating
    it forces XLA to keep input and output alive simultaneously — the
    pool's HBM footprint doubles for the step. Read-only gathers
    (extract_kv_pages) are legitimate and get a reasoned suppression:
    the contract is written down at the jit definition.
    """

    id = "DL012"
    name = "donation-audit"

    def check(self, ctx: ScanContext) -> Iterable[Finding]:
        project = ctx.project
        if project is None:
            return
        yield from self._check_undonated_pools(ctx, project)
        for info in project.functions.values():
            if info.path != ctx.path:
                continue
            yield from self._check_read_after_donate(ctx, project, info)

    def _check_undonated_pools(self, ctx, project) -> Iterable[Finding]:
        for (path, _name), jit in sorted(project.jits.items()):
            if path != ctx.path or jit.wrapped_fn is None:
                continue
            donated = set(jit.donate_argnums or ())
            undonated = [
                p for i, p in enumerate(jit.wrapped_fn.params)
                if p in _POOL_PARAMS and i not in donated
            ]
            if not undonated:
                continue
            yield Finding(
                rule=self.id, path=ctx.path,
                line=jit.line, col=jit.col,
                message=f"jit {jit.name!r} takes pool buffer(s) "
                        f"{', '.join(undonated)} without donate_argnums — "
                        "XLA keeps input AND output alive, doubling the "
                        "pool's HBM footprint for the call",
                hint="donate the pool positions (and rebind from the "
                     "result), or suppress with the read-only contract "
                     "as the reason",
                context=jit.context,
                detail=f"undonated:{jit.name}:{','.join(undonated)}",
            )

    def _check_read_after_donate(self, ctx, project, info) -> Iterable[Finding]:
        for name, call in info.calls:
            jits = project.jit_names.get(_last(name))
            if not jits:
                continue
            donates = {j.donate_argnums for j in jits}
            if len(donates) != 1:
                continue
            donate = donates.pop()
            if not donate:
                continue
            rebound = self._stmt_targets(call)
            for pos in donate:
                if pos >= len(call.args):
                    continue
                d = dotted(call.args[pos])
                if d is None or d in rebound:
                    continue
                line = self._first_read_after(info, call, d)
                if line is None:
                    continue
                yield Finding(
                    rule=self.id, path=ctx.path,
                    line=call.lineno, col=call.col_offset,
                    message=f"{d} is donated to {_last(name)}() (arg "
                            f"{pos}) but read again at line {line} — the "
                            "buffer is invalid the moment the call is "
                            "issued",
                    hint="rebind the name from the call's result in the "
                         "same statement, or stop donating the position",
                    context=info.qualname,
                    detail=f"donated-read:{info.qualname}:{d}:{pos}",
                )

    @staticmethod
    def _stmt_targets(call: ast.Call) -> set[str]:
        """Dotted names the call's enclosing assignment rebinds —
        donated-and-rebound in one statement is the safe idiom."""
        out: set[str] = set()
        for p in parents(call):
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                break
            if isinstance(p, ast.Assign):
                for t in p.targets:
                    elts = (
                        t.elts if isinstance(t, (ast.Tuple, ast.List))
                        else [t]
                    )
                    for el in elts:
                        d = dotted(el)
                        if d:
                            out.add(d)
                break
        return out

    @staticmethod
    def _first_read_after(info, call, name: str) -> int | None:
        """Line of the first use of ``name`` after the call, when that
        use is a read (a rebind first makes later reads fine)."""
        after = getattr(call, "end_lineno", call.lineno)
        first: tuple[int, int, bool] | None = None  # (line, col, is_load)
        for n in ast.walk(info.node):
            if isinstance(n, (ast.Name, ast.Attribute)):
                if dotted(n) != name or n.lineno <= after:
                    continue
                key = (n.lineno, n.col_offset, isinstance(n.ctx, ast.Load))
                if first is None or key[:2] < first[:2]:
                    first = key
        if first is not None and first[2]:
            return first[0]
        return None


# --------------------------------------------------------------------------
# DL013 spec-coverage
# --------------------------------------------------------------------------


class SpecCoverage:
    """DL013: shard_map/pjit specs that don't cover the declared params.

    Two checks:

      * arity — ``in_specs`` entries vs the wrapped callable's positional
        params (and ``out_specs`` vs its visible return arity): a missing
        entry fails at the first real mesh, which on a CPU-tested repo
        means production;
      * pytree-leaf coverage — a quant-capable value (one the enclosing
        function tests with ``is_quant(...)``) passed into a shard_map
        whose spec for that position is a bare ``P(...)``: a QuantPool's
        scale leaves have no spec, so the mapped kernel can't accept the
        quantized form at all — the generalized ROADMAP #7 scale-leaf
        bug. Either plumb per-leaf specs or guard the path AND account
        for the fallback (DL014).
    """

    id = "DL013"
    name = "spec-coverage"

    def check(self, ctx: ScanContext) -> Iterable[Finding]:
        project = ctx.project
        if project is None:
            return
        for sm in project.shard_maps:
            if sm.path != ctx.path:
                continue
            yield from self._check_arity(ctx, project, sm)
            yield from self._check_quant_leaves(ctx, project, sm)

    # -- arity --------------------------------------------------------------

    def _check_arity(self, ctx, project, sm) -> Iterable[Finding]:
        n_params = self._wrapped_param_count(project, sm)
        specs = self._spec_elements(sm)
        if n_params is not None and specs is not None:
            n_specs, exact = specs
            if (exact and n_specs != n_params) or (
                not exact and n_specs > n_params
            ):
                yield Finding(
                    rule=self.id, path=ctx.path,
                    line=sm.line, col=sm.col,
                    message=f"shard_map declares {n_specs} in_specs "
                            f"{'=' if exact else '>'}"
                            f" for a callable taking {n_params} params — "
                            "every positional arg needs exactly one spec "
                            "entry",
                    hint="add/remove the spec entry; None (replicated) "
                         "is an explicit choice, not a default",
                    context=sm.context,
                    detail=f"arity:{sm.context}:{n_specs}:{n_params}",
                )
        n_out = self._out_spec_count(sm)
        n_ret = self._wrapped_return_arity(project, sm)
        if n_out is not None and n_ret is not None and n_out != n_ret:
            yield Finding(
                rule=self.id, path=ctx.path,
                line=sm.line, col=sm.col,
                message=f"shard_map declares {n_out} out_specs for a "
                        f"callable returning {n_ret} values",
                hint="one out_spec per returned leaf",
                context=sm.context,
                detail=f"out-arity:{sm.context}:{n_out}:{n_ret}",
            )

    @staticmethod
    def _wrapped_param_count(project, sm) -> int | None:
        w = sm.wrapped
        if isinstance(w, ast.Lambda):
            a = w.args
            return len(a.posonlyargs) + len(a.args)
        if isinstance(w, ast.Name):
            cands = [
                f for f in project.by_name.get(w.id, ())
                if f.path == sm.path
            ] or project.by_name.get(w.id, [])
            if len(cands) == 1:
                return len([p for p in cands[0].params if p != "self"])
        return None

    @staticmethod
    def _wrapped_return_arity(project, sm) -> int | None:
        w = sm.wrapped
        node = None
        if isinstance(w, ast.Lambda):
            node = w.body
            return len(node.elts) if isinstance(node, ast.Tuple) else None
        if isinstance(w, ast.Name):
            cands = [
                f for f in project.by_name.get(w.id, ())
                if f.path == sm.path
            ] or project.by_name.get(w.id, [])
            if len(cands) != 1:
                return None
            arities = set()
            for n in ast.walk(cands[0].node):
                if isinstance(n, ast.Return) and n.value is not None:
                    arities.add(
                        len(n.value.elts)
                        if isinstance(n.value, ast.Tuple) else 1
                    )
            if len(arities) == 1:
                a = arities.pop()
                return a if a > 1 else None  # single value: can't misdeclare
        return None

    def _spec_elements(self, sm) -> tuple[int, bool] | None:
        """(entry count, exact?) of in_specs. Handles the repo idiom of a
        locally-built list (``in_specs = [...]; ... in_specs.append(...);
        shard_map(..., in_specs=tuple(in_specs))``): the literal base
        count is a lower bound (exact=False) once an append is seen."""
        return self._count_spec_expr(sm, sm.in_specs)

    def _out_spec_count(self, sm) -> int | None:
        counted = self._count_spec_expr(sm, sm.out_specs)
        if counted is None or not counted[1]:
            return None
        n, _ = counted
        return n

    @staticmethod
    def _count_spec_expr(sm, expr) -> tuple[int, bool] | None:
        if expr is None:
            return None
        if isinstance(expr, (ast.Tuple, ast.List)):
            return len(expr.elts), True
        if isinstance(expr, ast.Call) and _last(dotted(expr.func)) in (
            "P", "PartitionSpec"
        ):
            return 1, True
        if (
            isinstance(expr, ast.Call)
            and _last(dotted(expr.func)) == "tuple"
            and expr.args
            and isinstance(expr.args[0], ast.Name)
        ):
            # tuple(name): find the local list literal + appends
            var = expr.args[0].id
            fn = enclosing_function(sm.node)
            if fn is None:
                return None
            base: int | None = None
            appended = False
            for n in ast.walk(fn):
                if (
                    isinstance(n, ast.Assign)
                    and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                    and n.targets[0].id == var
                    and isinstance(n.value, (ast.List, ast.Tuple))
                ):
                    base = len(n.value.elts)
                elif (
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in ("append", "extend")
                    and dotted(n.func.value) == var
                ):
                    appended = True
            if base is not None:
                return base, not appended
        return None

    # -- quant pytree leaves ------------------------------------------------

    def _check_quant_leaves(self, ctx, project, sm) -> Iterable[Finding]:
        fn = enclosing_function(sm.node)
        if fn is None:
            return
        quant_names = {
            dotted(n.args[0])
            for n in ast.walk(fn)
            if isinstance(n, ast.Call) and n.args
            and _last(dotted(n.func)) == "is_quant"
        } - {None}
        if not quant_names:
            return
        arg_names = self._kernel_args(fn, sm)
        if arg_names is None:
            return
        spec_elts = self._spec_expr_elts(fn, sm)
        for i, arg in enumerate(arg_names):
            if arg not in quant_names:
                continue
            if spec_elts is not None and i < len(spec_elts):
                el = spec_elts[i]
                if not (
                    isinstance(el, ast.Call)
                    and _last(dotted(el.func)) in ("P", "PartitionSpec")
                ):
                    continue  # nested/helper spec: leaves are covered
            yield Finding(
                rule=self.id, path=ctx.path,
                line=sm.line, col=sm.col,
                message=f"quant-capable {arg!r} (this function tests "
                        f"is_quant({arg})) enters shard_map under an "
                        "array-only P(...) spec — a QuantPool's scale "
                        "leaves have no PartitionSpec, so the mapped "
                        "kernel cannot take the quantized form",
                hint="plumb per-leaf specs for the pool pytree, or guard "
                     "the quantized case out AND account for the "
                     "fallback (ops.fallback.note_fallback — DL014)",
                context=sm.context,
                detail=f"quant-leaf:{sm.context}:{arg}",
            )

    @staticmethod
    def _kernel_args(fn, sm) -> list[str | None] | None:
        """Positional arg names at the mapped kernel's invocation:
        ``kernel = shard_map(kernel, ...); ... kernel(*args)`` with
        ``args = (...)``, or a direct ``kernel(a, b, c)``."""
        target: str | None = None
        for p in parents(sm.node):
            if isinstance(p, ast.Assign) and len(p.targets) == 1 and (
                isinstance(p.targets[0], ast.Name)
            ):
                target = p.targets[0].id
                break
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
        if target is None:
            return None
        tuples: dict[str, list[str | None]] = {}
        for n in ast.walk(fn):
            if (
                isinstance(n, ast.Assign)
                and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)
                and isinstance(n.value, ast.Tuple)
            ):
                tuples[n.targets[0].id] = [
                    dotted(e) for e in n.value.elts
                ]
        for n in ast.walk(fn):
            if not (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id == target
                and n is not sm.node
            ):
                continue
            if (
                len(n.args) == 1
                and isinstance(n.args[0], ast.Starred)
                and isinstance(n.args[0].value, ast.Name)
            ):
                return tuples.get(n.args[0].value.id)
            if n.args and not any(
                isinstance(a, ast.Starred) for a in n.args
            ):
                return [dotted(a) for a in n.args]
        return None

    def _spec_expr_elts(self, fn, sm) -> list[ast.AST] | None:
        expr = sm.in_specs
        if isinstance(expr, (ast.Tuple, ast.List)):
            return list(expr.elts)
        if (
            isinstance(expr, ast.Call)
            and _last(dotted(expr.func)) == "tuple"
            and expr.args
            and isinstance(expr.args[0], ast.Name)
        ):
            var = expr.args[0].id
            for n in ast.walk(fn):
                if (
                    isinstance(n, ast.Assign)
                    and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                    and n.targets[0].id == var
                    and isinstance(n.value, (ast.List, ast.Tuple))
                ):
                    return list(n.value.elts)
        return None


# --------------------------------------------------------------------------
# DL014 silent-fallback guard
# --------------------------------------------------------------------------

_NOTERS = frozenset({"note_fallback"})


class SilentFallback:
    """DL014: a capability-gated downgrade that accounts for nothing.

    The shape: a gate built from catalogued capability probes
    (catalog.FALLBACK_GATES — use_pallas / supports_fused), a fast
    path behind ``if gate:``, and a fallthrough or
    ``else`` that quietly takes the slow path. ROADMAP #7's "fp8 + tp>1
    silently takes the XLA path" shipped exactly like this: correct
    output, 0.358x the throughput, zero signal. The downgrade branch
    must call ``ops.fallback.note_fallback(reason)`` (one-shot warning +
    dynamo_fused_fallback_total{reason}) or at least log — then the
    downgrade is a dashboard fact instead of a benchmark surprise.
    """

    id = "DL014"
    name = "silent-fallback"

    def check(self, ctx: ScanContext) -> Iterable[Finding]:
        gates = set(getattr(ctx.catalog, "FALLBACK_GATES", ()) or ())
        if not gates:
            return
        for node in ctx.nodes:
            if not isinstance(node, ast.If):
                continue
            gate = self._gate_of(node, gates)
            if gate is None:
                continue
            region = self._fallback_region(node)
            if not region or self._accounted(region):
                continue
            yield Finding(
                rule=self.id, path=ctx.path,
                line=node.lineno, col=node.col_offset,
                message=f"capability gate {gate}() downgrades to a "
                        "fallback path that neither counts nor logs "
                        "itself — the slow path ships invisibly "
                        "(the ROADMAP #7 fp8+tp>1 XLA-fallback class)",
                hint="call dynamo_tpu.ops.fallback.note_fallback("
                     "\"<reason>\") in the fallback branch (one-shot "
                     "warning + dynamo_fused_fallback_total{reason})",
                context=qualname(node),
                detail=f"silent-fallback:{qualname(node)}:{gate}",
            )

    @staticmethod
    def _gate_of(node: ast.If, gates: set[str]) -> str | None:
        """Gate name when the test (or the local boolean it was assigned
        from) contains a catalogued capability-probe call."""
        exprs = [node.test]
        if isinstance(node.test, ast.Name) or (
            isinstance(node.test, ast.UnaryOp)
            and isinstance(node.test.op, ast.Not)
            and isinstance(node.test.operand, ast.Name)
        ):
            var = (
                node.test.id if isinstance(node.test, ast.Name)
                else node.test.operand.id
            )
            fn = enclosing_function(node)
            scope = fn if fn is not None else None
            if scope is not None:
                for n in ast.walk(scope):
                    if (
                        isinstance(n, ast.Assign)
                        and len(n.targets) == 1
                        and isinstance(n.targets[0], ast.Name)
                        and n.targets[0].id == var
                    ):
                        exprs.append(n.value)
        for expr in exprs:
            for n in ast.walk(expr):
                if isinstance(n, ast.Call):
                    last = _last(dotted(n.func))
                    if last in gates:
                        return last
        return None

    @staticmethod
    def _fallback_region(node: ast.If) -> list[ast.stmt] | None:
        """The statements the downgrade takes. ``if not gate:`` puts the
        fallback in the body; ``if gate:`` puts it in the else, or — when
        the fast body returns — in the remainder of the parent block."""
        if isinstance(node.test, ast.UnaryOp) and isinstance(
            node.test.op, ast.Not
        ):
            return node.body
        if node.orelse:
            return node.orelse
        if not any(isinstance(s, ast.Return) for s in node.body):
            return None  # no clear fast/slow split: stay quiet
        parent = getattr(node, "_dl_parent", None)
        body = getattr(parent, "body", None)
        if isinstance(body, list) and node in body:
            return body[body.index(node) + 1:]
        return None

    @staticmethod
    def _accounted(region: list[ast.stmt]) -> bool:
        for stmt in region:
            for n in ast.walk(stmt):
                if not isinstance(n, ast.Call):
                    continue
                d = dotted(n.func) or ""
                last = _last(d)
                if last in _NOTERS:
                    return True
                recv = d.rsplit(".", 1)[0] if "." in d else ""
                if last in _LOG_METHODS and (
                    "log" in recv.lower() or recv == "logging"
                ):
                    return True
                if d == "warnings.warn":
                    return True
        return False


# --------------------------------------------------------------------------
# DL015 lock-discipline
# --------------------------------------------------------------------------


class LockDiscipline:
    """DL015: threading locks across await; lock-order inversion.

    Two checks over the whole project index:

      * a *sync* ``with <lock>:`` whose body awaits, inside an ``async
        def`` — a threading.Lock held across a suspension point blocks
        every OTHER event-loop coroutine AND every thread contending the
        lock for as long as the awaited thing takes; under kill-9 churn
        that's the step-thread/asyncio deadlock shape;
      * interprocedural lock-order inversion — function F takes lock A
        then (directly or via resolvable callees) lock B, while G takes
        B then A. Lock identity is ``Class.attr`` for ``self.X``
        receivers and ``path:name`` for module globals; callee
        resolution is single-candidate only (precision over recall — a
        false inversion report would train people to ignore the rule).
    """

    id = "DL015"
    name = "lock-discipline"

    def check(self, ctx: ScanContext) -> Iterable[Finding]:
        return ()  # project-level rule: see check_project

    def check_project(self, project: ProjectIndex) -> Iterable[Finding]:
        for ctx in project.contexts:
            yield from self._check_sync_lock_across_await(ctx)
        yield from self._check_lock_order(project)

    # -- (a) sync lock across await ----------------------------------------

    def _check_sync_lock_across_await(self, ctx) -> Iterable[Finding]:
        for node in ctx.nodes:
            if not isinstance(node, ast.With):
                continue
            fn = enclosing_function(node)
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            lock_src = self._lock_src(node)
            if lock_src is None:
                continue
            aw = next(
                (
                    n for stmt in node.body for n in ast.walk(stmt)
                    if isinstance(n, (ast.Await, ast.AsyncFor,
                                      ast.AsyncWith))
                ),
                None,
            )
            if aw is None:
                continue
            yield Finding(
                rule=self.id, path=ctx.path,
                line=node.lineno, col=node.col_offset,
                message=f"sync `with {lock_src}:` holds a threading lock "
                        f"across an await (line {aw.lineno}) — the loop "
                        "suspends with the lock held, stalling every "
                        "contending thread AND coroutine for the full "
                        "await",
                hint="use asyncio.Lock for loop-side critical sections, "
                     "or snapshot under the lock and await after release",
                context=qualname(node),
                detail=f"lock-await:{qualname(node)}:{lock_src}",
            )

    @staticmethod
    def _lock_src(node) -> str | None:
        for item in node.items:
            try:
                src = ast.unparse(item.context_expr)
            # dynalint: disable=DL003 -- defensive: an unparse failure
            # just means "not a lock expr"; nothing to report
            except Exception:  # pragma: no cover - defensive
                continue
            if "lock" in src.lower() and "_phase" not in src:
                return src
        return None

    # -- (b) lock-order inversion ------------------------------------------

    def _check_lock_order(self, project) -> Iterable[Finding]:
        # per-function: direct acquisitions (lock id -> With node) and the
        # transitive closure of locks acquired anywhere inside
        direct: dict[tuple[str, str], list[tuple[str, ast.AST]]] = {}
        for key, info in project.functions.items():
            direct[key] = [
                (lid, w) for lid, w in self._acquisitions(info)
            ]
        closure: dict[tuple[str, str], set[str]] = {
            key: {lid for lid, _ in acqs} for key, acqs in direct.items()
        }
        changed = True
        while changed:
            changed = False
            for key, info in project.functions.items():
                for name, _call in info.calls:
                    cands = project._resolve(info, name)
                    if len(cands) != 1:
                        continue  # precision: only unambiguous callees
                    k2 = (cands[0].path, cands[0].qualname)
                    extra = closure.get(k2, set()) - closure[key]
                    if extra:
                        closure[key] |= extra
                        changed = True
        # edges: lock A held (With span) while lock B is acquired inside —
        # directly nested or via a resolvable call
        edges: dict[tuple[str, str], list] = {}

        def note(a: str, b: str, info, node) -> None:
            if a != b:
                edges.setdefault((a, b), []).append((info, node))

        for key, info in project.functions.items():
            for lid, w in direct[key]:
                for stmt in w.body:
                    for n in ast.walk(stmt):
                        if isinstance(n, (ast.With, ast.AsyncWith)):
                            for lid2, w2 in self._acquisitions_of(info, n):
                                note(lid, lid2, info, w2)
                        elif isinstance(n, ast.Call):
                            name = dotted(n.func)
                            if not name:
                                continue
                            cands = project._resolve(info, name)
                            if len(cands) != 1:
                                continue
                            k2 = (cands[0].path, cands[0].qualname)
                            for lid2 in closure.get(k2, ()):
                                note(lid, lid2, info, n)
        for (a, b), sites in sorted(edges.items()):
            if (b, a) not in edges:
                continue
            info, node = sites[0]
            other_info, other_node = edges[(b, a)][0]
            yield Finding(
                rule=self.id, path=info.path,
                line=node.lineno, col=node.col_offset,
                message=f"lock-order inversion: {info.qualname} takes "
                        f"{a} then {b}, while {other_info.qualname} "
                        f"({other_info.path}:{other_node.lineno}) takes "
                        f"{b} then {a} — two contenders deadlock",
                hint="pick one global order for the two locks and "
                     "restructure the second site (or collapse to one "
                     "lock)",
                context=info.qualname,
                detail=f"inversion:{a}->{b}",
            )

    def _acquisitions(self, info) -> list[tuple[str, ast.AST]]:
        """(lock id, With node) pairs acquired directly by this function."""
        out: list[tuple[str, ast.AST]] = []
        for n in ast.walk(info.node):
            if isinstance(n, (ast.With, ast.AsyncWith)):
                out.extend(self._acquisitions_of(info, n))
        return out

    def _acquisitions_of(self, info, node) -> list[tuple[str, ast.AST]]:
        out = []
        for item in node.items:
            d = dotted(item.context_expr)
            if d is None or "lock" not in d.lower():
                continue
            out.append((self._lock_id(info, d), node))
        return out

    @staticmethod
    def _lock_id(info, d: str) -> str:
        if d.startswith("self.") and info.cls:
            return f"{info.cls}.{d[5:]}"
        if "." in d:
            return f"{info.path}:{d}"
        return f"{info.path}:{d}"
