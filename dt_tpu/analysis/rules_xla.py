"""dtxla — compile-boundary, transfer & donation rules (DT015-DT017).

The r18 device observatory (``dt_tpu/obs/device.py``) can only verify
the two invariants the ROADMAP's perf arc depends on AT RUNTIME:
program signatures stay stable (no recompile storms — cf. *Automatic
Cross-Replica Sharding of Weight Update Computation*, arXiv:2004.13336)
and the hot path never round-trips through the host (the failure that
makes the host-packed 2-bit wire path lose; cf.
*EQuARX*, arXiv:2506.17615, which wins by keeping quantization in XLA).
These rules move both to lint time, on the :mod:`dt_tpu.analysis.flow`
jax-dataflow substrate (reference gap: the reference's executor rebinds
silently on reshape — ``executor_group.py`` — and ``make cpplint``
checked neither transfers nor aliasing, ``Makefile:140-160``).

- DT015 compile-boundary: every ``jax.jit``/``pjit`` construction lives
  at module level, behind a cache (``self.<attr>`` assignment — the
  Module/Trainer ``_build`` idiom — ``lru_cache``, a factory
  ``return``), or through ``obs.device.instrument``; plus unhashable
  ``static_argnums`` arguments and bare ``lower().compile()`` outside a
  ``compile.*`` span (the observatory contract).
- DT016 transfer-discipline: implicit synchronous D2H in hot-path
  scopes — ``float``/``int``/``bool``/``.item()``/``.tolist()``/
  ``np.asarray`` / truthiness on values the dataflow types as jax
  device arrays.
- DT017 donation-safety: flow-sensitive use-after-donate,
  donate-of-a-pending-``copy_to_host_async`` buffer, and
  donate-without-backend-guard promoted from DT003's enclosing-scope
  text check to actual value flow.

Pure stdlib ``ast`` — imports without jax, like the rest of the engine.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Tuple

from dt_tpu.analysis import flow
from dt_tpu.analysis.engine import (FileContext, Finding, ProjectContext,
                                    Rule)
from dt_tpu.analysis.flow import _attr_name, _self_attr


def _scope_walk(scope: ast.AST) -> Iterable[ast.AST]:
    """Walk a function/module subtree WITHOUT entering nested function
    definitions (their spans/compiles are their own scope's business)."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(node))


def _scopes(tree: ast.AST) -> Iterable[ast.AST]:
    yield tree
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield n


def _literal_prefix(arg: ast.AST) -> str:
    """Literal (or f-string prefix) of a span-name argument: the DT011
    resolution idiom — ``"compile.bench"`` and ``f"compile.{what}"``
    both resolve to a ``compile.``-prefixed name."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.JoinedStr) and arg.values and \
            isinstance(arg.values[0], ast.Constant) and \
            isinstance(arg.values[0].value, str):
        return arg.values[0].value
    return ""


def _opens_compile_span(scope: ast.AST) -> bool:
    """Whether this scope opens a ``compile.*`` obs span (``tr.begin``/
    ``complete_span``/``span`` with a compile.-prefixed literal name) —
    the observatory contract that makes an AOT compile visible to the
    hang watchdog's compile labeling."""
    for n in _scope_walk(scope):
        if isinstance(n, ast.Call) and n.args and \
                _attr_name(n.func) in ("begin", "complete_span", "span"):
            if _literal_prefix(n.args[0]).startswith("compile."):
                return True
    return False


def _calls_with_scope(tree: ast.AST):
    """Yield ``(enclosing_function_or_None, Call)`` pairs, lambdas not
    treated as scopes."""
    def rec(node, fn):
        for child in ast.iter_child_nodes(node):
            nxt = fn
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nxt = child
            if isinstance(child, ast.Call):
                yield fn, child
            yield from rec(child, nxt)
    yield from rec(tree, None)


def _file_model(ctx: FileContext, project: ProjectContext):
    """Per-file jax model shared by DT016/DT017: jit attr/module
    bindings plus one :class:`~dt_tpu.analysis.flow.JaxDataflow` per
    host-side function (computed once, both rules read it)."""
    cache = project.data.setdefault("xla_models", {})
    model = cache.get(ctx.path)
    if model is None:
        if "jax" not in ctx.source and "jnp" not in ctx.source:
            model = ({}, {}, [])
        else:
            jit_attrs = flow.collect_jit_attrs(ctx.tree)
            module_jits = flow.collect_module_jits(ctx.tree)
            flows = [(fn, flow.JaxDataflow(body, jit_attrs, module_jits))
                     for fn, body in flow.analyzable_functions(ctx.tree)]
            model = (jit_attrs, module_jits, flows)
        cache[ctx.path] = model
    return model


# ---------------------------------------------------------------------------
# DT015 compile-boundary
# ---------------------------------------------------------------------------


class CompileBoundary(Rule):
    """DT015: jit/pjit constructed outside a caching boundary — a
    recompile per call, invisible to the r18 recompile-cause ledger.

    Re-wrapping ``jax.jit(fn)`` keys the trace cache on the NEW wrapper
    object: construct-and-call is a guaranteed retrace (and usually a
    recompile) every time it executes.  Sanctioned boundaries: module
    level (one construction at import), a ``self.<attr> = ...``
    assignment (the Module/Trainer ``_build`` cached-step idiom,
    optionally through ``obs.device.instrument``), an ``lru_cache``/
    ``cache``-decorated function, or a factory ``return jax.jit(...)``
    (the caller owns the cache).  Library code (``dt_tpu/``) is held to
    the full contract; one-shot drivers (``tools/``, ``examples/``) may
    bind a jit to a local, but construct-and-call is flagged everywhere.
    Also: unhashable literals (list/dict/set) passed at
    ``static_argnums`` positions (a ``TypeError`` at dispatch), and
    bare ``lower().compile()`` outside a ``compile.*`` span — the
    observatory contract (``dt_tpu/obs/device.py`` ``_first_call``)
    that keeps AOT compiles visible to the hang watchdog's
    compile-in-progress labeling.

    Known limits: a ``self.<attr>`` assignment sanctions from ANY
    method (the attribute IS the cache; a rebind-per-call method slips
    through unless it sits in a loop), bare ``@jax.jit`` decorators are
    module-level by construction and not inspected, and factories
    called per step are interprocedural — not seen.
    """

    id = "DT015"
    name = "compile-boundary"
    hint = ("hoist the jit to module level / a cached self.<attr> "
            "(optionally via obs.device.instrument), or wrap the AOT "
            "compile in a compile.<what> span")

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        out: List[Finding] = []
        in_lib = ctx.path.startswith("dt_tpu/")
        parents = flow._parent_map(ctx.tree)
        self._check_ctors(ctx, parents, in_lib, out)
        for scope in _scopes(ctx.tree):
            self._check_static_args(ctx, scope, out)
            self._check_bare_compile(ctx, scope, out)
        return out

    # -- arm 1-3: ctor placement ------------------------------------------

    def _check_ctors(self, ctx, parents, in_lib, out) -> None:
        def stmt_of(node):
            cur = node
            while cur in parents and not isinstance(cur, ast.stmt):
                cur = parents[cur]
            return cur if isinstance(cur, ast.stmt) else None

        def visit(node, func_stack, loop_depth):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                func_stack = func_stack + (node,)
                loop_depth = 0
            elif isinstance(node, (ast.For, ast.While)):
                loop_depth += 1
            if flow.is_jit_ctor(node):
                self._ctor_site(ctx, node, parents, stmt_of, func_stack,
                                loop_depth, in_lib, out)
            for child in ast.iter_child_nodes(node):
                visit(child, func_stack, loop_depth)

        visit(ctx.tree, (), 0)

    def _ctor_site(self, ctx, call, parents, stmt_of, func_stack,
                   loop_depth, in_lib, out) -> None:
        p = parents.get(call)
        used_inline = (isinstance(p, ast.Call) and p.func is call) or \
            (isinstance(p, ast.Attribute) and p.value is call)
        if not func_stack:
            return  # module level: one construction at import time
        if used_inline:
            out.append(ctx.finding(
                self, call,
                "jit wrapper constructed and immediately used — the "
                "trace cache keys on the wrapper object, so this is a "
                "fresh trace/compile every call; bind it once "
                "(module level, cached attr, or a hoisted local)"))
            return
        if not in_lib:
            return  # tools/examples: bound one-shot constructions OK
        instrumented = isinstance(p, ast.Call) and call in p.args and \
            _attr_name(p.func) == "instrument"
        stmt = stmt_of(call)
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, ast.AnnAssign):
            targets = [stmt.target]
        self_attr_assign = any(_self_attr(t) is not None
                               for t in targets)
        factory_return = isinstance(stmt, ast.Return)
        cached_scope = any(
            any("lru_cache" in ast.dump(d) or "cache" in ast.dump(d)
                for d in f.decorator_list)
            for f in func_stack)
        builder = func_stack[-1].name.startswith(
            ("_build", "_make", "build_", "make_"))
        if loop_depth:
            out.append(ctx.finding(
                self, call,
                "jit constructed inside a loop — a fresh trace cache "
                "every iteration; construct once outside the loop"))
            return
        if not (instrumented or self_attr_assign or factory_return or
                cached_scope or builder):
            out.append(ctx.finding(
                self, call,
                "in-body jit construction in library code — cache it "
                "(self.<attr> assignment, lru_cache, module level, the "
                "_build idiom) or route it through "
                "obs.device.instrument"))

    # -- arm 4: unhashable static args ------------------------------------

    @staticmethod
    def _static_positions(call: ast.Call) -> List[int]:
        for kw in call.keywords:
            if kw.arg != "static_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return [v.value]
            if isinstance(v, (ast.Tuple, ast.List)):
                return [e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)]
        return []

    def _check_static_args(self, ctx, scope, out) -> None:
        static_of: Dict[str, List[int]] = {}
        unhashable = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                      ast.DictComp, ast.SetComp)

        def check_call(call: ast.Call, positions: List[int]) -> None:
            for pos in positions:
                if pos < len(call.args) and \
                        isinstance(call.args[pos], unhashable):
                    out.append(ctx.finding(
                        self, call,
                        f"unhashable argument at static_argnums "
                        f"position {pos} — jit static args must be "
                        f"hashable (TypeError at dispatch); pass a "
                        f"tuple or hoist the value"))

        nodes = list(_scope_walk(scope))
        for n in nodes:  # bindings first: _scope_walk order is LIFO
            if isinstance(n, ast.Assign) and \
                    flow.is_jit_ctor(n.value):
                pos = self._static_positions(n.value)
                for t in n.targets:
                    if isinstance(t, ast.Name) and pos:
                        static_of[t.id] = pos
        for n in nodes:
            if not isinstance(n, ast.Call):
                continue
            if flow.is_jit_ctor(n.func):
                check_call(n, self._static_positions(n.func))
            elif isinstance(n.func, ast.Name) and \
                    n.func.id in static_of:
                check_call(n, static_of[n.func.id])

    # -- arm 5: bare lower().compile() ------------------------------------

    def _check_bare_compile(self, ctx, scope, out) -> None:
        lowered: set = set()
        for n in _scope_walk(scope):
            if isinstance(n, ast.Assign) and \
                    isinstance(n.value, ast.Call) and \
                    isinstance(n.value.func, ast.Attribute) and \
                    n.value.func.attr == "lower":
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        lowered.add(t.id)
        span_ok: Optional[bool] = None  # computed lazily, once
        for n in _scope_walk(scope):
            if not (isinstance(n, ast.Call) and
                    isinstance(n.func, ast.Attribute) and
                    n.func.attr == "compile"):
                continue
            base = n.func.value
            from_lower = (
                isinstance(base, ast.Call) and
                isinstance(base.func, ast.Attribute) and
                base.func.attr == "lower") or (
                isinstance(base, ast.Name) and base.id in lowered)
            if not from_lower:
                continue  # re.compile() and friends
            if span_ok is None:
                span_ok = _opens_compile_span(scope)
            if not span_ok:
                out.append(ctx.finding(
                    self, n,
                    "bare lower().compile() outside a compile.* span — "
                    "invisible to the hang watchdog's "
                    "compile-in-progress labeling; open a "
                    "compile.<what> span around it (or route through "
                    "obs.device.instrument)"))


# ---------------------------------------------------------------------------
# DT016 transfer-discipline
# ---------------------------------------------------------------------------


class TransferDiscipline(Rule):
    """DT016: implicit synchronous D2H on the hot path — the
    one-host-sync-per-step contract, flow-checked.

    In hot-path scopes (``training/``, ``parallel/``, ``ops/``,
    ``elastic/dataplane.py``, ``elastic/client.py``), a ``float(x)``/
    ``int(x)``/``bool(x)``, ``.item()``/``.tolist()``, ``np.asarray(x)``
    or truthiness/comparison test on a value the dataflow types as a
    jax device array blocks the dispatch queue mid-step — the exact
    host round-trip that generalizes DT004's bench-local check to the
    fleet (and that makes host-packed wire paths lose).
    Explicit ``jax.device_get`` is the sanctioned spelling: it
    documents the transfer and the StagingPool D2H sites build on it.

    Known limits: parameters are untyped (the ``_health_step`` sentinel
    fetch on pre-fetched host values stays silent by construction) and
    list comprehensions don't propagate types (the StagingPool bucket
    slices stay silent); interprocedural flows are not seen.
    Deliberate syncs (the fused sentinel's one-scalar fetch) carry a
    reasoned ``# dtlint: ignore[DT016]``.
    """

    id = "DT016"
    name = "transfer-discipline"
    hint = ("fetch through an explicit np.asarray(jax.device_get(...)) "
            "at a sanctioned boundary, keep the value on device, or "
            "suppress with a reasoned # dtlint: ignore[DT016]")

    _HOT = ("dt_tpu/training/", "dt_tpu/parallel/", "dt_tpu/ops/")

    def applies_to(self, relpath: str) -> bool:
        if relpath.endswith(("elastic/dataplane.py",
                             "elastic/client.py")):
            return True
        return any(seg in relpath for seg in self._HOT)

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        out: List[Finding] = []
        seen = set()
        _attrs, _mods, flows = _file_model(ctx, project)
        for _fn, df in flows:
            for s in df.syncs:
                if (s.line, s.kind) in seen:
                    continue
                seen.add((s.line, s.kind))
                out.append(ctx.finding(
                    self, s.line,
                    f"implicit synchronous D2H on the hot path: "
                    f"{s.kind} forces a device sync on a jax value "
                    f"({s.expr})"))
        return out


# ---------------------------------------------------------------------------
# DT017 donation-safety
# ---------------------------------------------------------------------------


class DonationSafety(Rule):
    """DT017: donated-buffer misuse, flow-checked — use-after-donate,
    async-capture, and unguarded donation.

    ``donate_argnums`` hands the input buffer to XLA: on TPU the
    argument is DELETED after the call; reading it afterwards raises
    (or, with aliasing, yields garbage).  The dataflow tracks each
    donating callable (``self.<attr>`` jit bindings and local/module
    ``x = jax.jit(f, donate_argnums=...)``, donate tuples resolved
    through assignments and one conditional) and flags: (1) a binding
    passed at a donated position and READ after the call without a
    rebind (the same-statement ``state, loss = step(state, ...)``
    rebind is the sanctioned shape); (2) a donated argument with a
    pending ``copy_to_host_async`` — the async D2H may read freed
    memory (the GradSyncEngine staging hazard); (3) a resolved
    non-empty donate tuple whose VALUE neither data- nor
    control-depends on ``jax.default_backend()`` — DT003's
    enclosing-scope text check is satisfied by any unrelated mention,
    this arm requires the donate tuple itself to be conditional
    (CLAUDE.md: XLA CPU + donate + multi-device allreduce segfaults).

    Known limits: interprocedural donation (a jit returned from a
    factory and called elsewhere) and container-held buffers are not
    tracked; ``donate_argnames`` stays DT003's business.
    """

    id = "DT017"
    name = "donation-safety"
    hint = ("rebind the donated name in the same statement "
            "(state, ... = step(state, ...)), drop the stale alias, "
            "and guard donation as "
            "(0,) if jax.default_backend() != 'cpu' else ()")

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        out: List[Finding] = []
        _attrs, _mods, flows = _file_model(ctx, project)
        for _fn, df in flows:
            for u in df.donation_uses:
                if u.kind == "async-capture":
                    out.append(ctx.finding(
                        self, u.line,
                        f"'{u.var}' has a copy_to_host_async pending "
                        f"(line {u.donated_line}) and is then donated "
                        f"to {u.callee} — the async D2H may read freed "
                        f"memory"))
                else:
                    out.append(ctx.finding(
                        self, u.line,
                        f"use after donate: '{u.var}' was donated to "
                        f"{u.callee} at line {u.donated_line} and is "
                        f"read afterwards — the buffer is deleted on "
                        f"TPU (garbage under aliasing)"))
        if "donate" in ctx.source:
            self._check_guard_flow(ctx, out)
        return out

    def _check_guard_flow(self, ctx, out) -> None:
        parents = flow._parent_map(ctx.tree)
        for scope, call in _calls_with_scope(ctx.tree):
            if not flow.is_jit_ctor(call):
                continue
            jb = flow.resolve_donate(call, scope or ctx.tree)
            if not jb.donate or jb.guarded:
                continue
            cur = call
            guarded = False
            while cur in parents:
                cur = parents[cur]
                if isinstance(cur, (ast.If, ast.IfExp)) and \
                        "default_backend" in ast.dump(cur.test):
                    guarded = True
                    break
            if not guarded:
                out.append(ctx.finding(
                    self, call,
                    "donation does not flow through a "
                    "jax.default_backend() guard — make the donate "
                    "tuple itself conditional: "
                    "(0,) if jax.default_backend() != 'cpu' else ()"))
