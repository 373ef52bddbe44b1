"""TPU/jax gotcha rules (DT001-DT004) — CLAUDE.md's "cost hours when
rediscovered" list, machine-checked.

Each rule encodes one failure mode this project actually hit (the
reference's analog discipline was cpplint + operator unit gates,
``Makefile:140-160``); the catalog in ``docs/dtlint_rules.md`` carries a
bad/good example per rule.  All checks are static heuristics over stdlib
``ast`` — they flag the *decidable* instances (literal shapes, direct
call patterns) and stay silent where shapes/dtypes are symbolic; the
per-line ``# dtlint: ignore[...]`` escape covers intentional
exceptions.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from dt_tpu.analysis.engine import FileContext, Finding, ProjectContext, Rule

_UNSIGNED = {"uint8", "uint16", "uint32", "uint64"}
_REDUCTIONS = {"sum", "prod", "cumsum", "cumprod", "max", "min", "argmax",
               "argmin", "mean"}


def _attr_name(node: ast.AST) -> str:
    """Rightmost attribute/name token of a dotted expression."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _mentions_unsigned(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Attribute, ast.Name)) and \
                _attr_name(sub) in _UNSIGNED:
            return True
        if isinstance(sub, ast.Constant) and \
                isinstance(sub.value, str) and sub.value in _UNSIGNED:
            return True
    return False


def _kernel_names(tree: ast.AST) -> Set[str]:
    """Functions used as pallas_call kernels (directly or through
    functools.partial)."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and
                _attr_name(node.func) == "pallas_call" and node.args):
            continue
        kern = node.args[0]
        if isinstance(kern, ast.Call) and _attr_name(kern.func) == \
                "partial" and kern.args:
            kern = kern.args[0]
        if isinstance(kern, ast.Name):
            names.add(kern.id)
    return names


class PallasTiling(Rule):
    """DT001: Pallas block shapes must tile the TPU (8, 128) register
    layout, and kernels must not reduce over unsigned ints (Mosaic has no
    unsigned reductions on real TPU; interpret mode hides it —
    CLAUDE.md "Pallas on REAL TPU")."""

    id = "DT001"
    name = "pallas-tiling"
    hint = ("make the last two block dims multiples of (8, 128) or equal "
            "to the array dims; pack unsigned reductions via int32 + "
            "bitcast")

    def applies_to(self, relpath: str) -> bool:
        return relpath.endswith(".py")

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        if "pallas" not in ctx.source:
            return
        # literal BlockSpec shapes whose last two dims can't tile (8, 128)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and
                    _attr_name(node.func) == "BlockSpec" and node.args):
                continue
            shape = node.args[0]
            if not isinstance(shape, ast.Tuple) or len(shape.elts) < 2:
                continue
            last2 = shape.elts[-2:]
            dims = [e.value for e in last2
                    if isinstance(e, ast.Constant) and
                    isinstance(e.value, int)]
            if len(dims) != 2:
                continue  # symbolic dims: may equal the array dims
            sub, lane = dims
            if sub % 8 == 0 and lane % 128 == 0:
                continue
            if lane == 1 or sub == 1:
                # a literal 1 is the idiomatic "equals the array dim"
                # squeeze axis (e.g. packed-word (W, 1) outputs); real-TPU
                # validity then depends on the array shape, undecidable
                # here
                continue
            yield ctx.finding(
                self, node,
                f"BlockSpec last-two dims ({sub}, {lane}) neither tile "
                f"(8, 128) nor are symbolic array dims")
        # reductions over unsigned ints inside kernel bodies
        kernels = _kernel_names(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.FunctionDef) and
                    node.name in kernels):
                continue
            for call in ast.walk(node):
                if not (isinstance(call, ast.Call) and
                        _attr_name(call.func) in _REDUCTIONS):
                    continue
                if any(_mentions_unsigned(a) for a in call.args) or any(
                        _mentions_unsigned(k.value) for k in call.keywords):
                    yield ctx.finding(
                        self, call,
                        f"reduction '{_attr_name(call.func)}' over an "
                        f"unsigned-int operand inside Pallas kernel "
                        f"'{node.name}' (Mosaic rejects this on real TPU)")


class Bf16Downcast(Rule):
    """DT002: ``preferred_element_type=f32`` + immediate downcast inside
    an op breaks the conv/dot transpose rule under bf16 autodiff
    (CLAUDE.md "bf16 autodiff"); the MXU accumulates f32 natively, so
    the cast is also pointless."""

    id = "DT002"
    name = "bf16-downcast"
    hint = ("drop the astype: MXU accumulates f32 natively and the "
            "transpose sees mixed dtypes otherwise (CLAUDE.md bf16 "
            "autodiff gotcha)")

    def applies_to(self, relpath: str) -> bool:
        return "dt_tpu/ops/" in relpath

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            # pattern: CALL(..., preferred_element_type=<f32>).astype(X)
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr == "astype"):
                continue
            inner = node.func.value
            if not isinstance(inner, ast.Call):
                continue
            pet = next((k.value for k in inner.keywords
                        if k.arg == "preferred_element_type"), None)
            if pet is None or "float32" not in ast.dump(pet):
                continue
            target = node.args[0] if node.args else None
            if target is not None and "float32" in ast.dump(target):
                continue  # astype(f32) is a no-op, not a downcast
            yield ctx.finding(
                self, node,
                "dot/conv with preferred_element_type=float32 downcast "
                "in the same expression — breaks the transpose rule "
                "under bf16 autodiff")


class CpuDonate(Rule):
    """DT003: ``donate_argnums`` without a backend guard — XLA CPU +
    donation + multi-device allreduce segfaults (CLAUDE.md, jax 0.9.0);
    every donating jit must branch on ``jax.default_backend()``."""

    id = "DT003"
    name = "cpu-donate"
    hint = ("gate donation on the backend: donate = (0,) if "
            "jax.default_backend() != 'cpu' else ()  (see "
            "training/module.py _build_steps)")

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        # map each donate_argnums call to its enclosing def chain
        for scope, node in _calls_with_scope(ctx.tree):
            kw = next((k for k in node.keywords
                       if k.arg in ("donate_argnums", "donate_argnames")),
                      None)
            if kw is None:
                continue
            if isinstance(kw.value, ast.Tuple) and not kw.value.elts:
                continue  # donate_argnums=() donates nothing
            guard_scope = scope if scope is not None else ctx.tree
            if "default_backend" in ast.dump(guard_scope):
                continue
            yield ctx.finding(
                self, node,
                "donate_argnums with no jax.default_backend() guard in "
                "scope (XLA CPU donation + collectives segfaults)")


class PartialBlock(Rule):
    """DT004: timing code that blocks on the scalar loss instead of the
    full output state — a timed window ends when every output of its
    last step exists, and the loss is only one of them (a step of two
    programs, ``grad_step`` then ``apply_step``, has its loss before
    its update)."""

    id = "DT004"
    name = "partial-block"
    hint = ("block on the full step output, e.g. "
            "jax.block_until_ready((state, loss))")

    #: lines of separation within which a time.* call makes a block
    #: "timing-adjacent"
    WINDOW = 10
    _SCALAR_NAMES = {"loss", "losses", "loss_val"}
    _TIMING = {"time", "perf_counter", "monotonic", "process_time"}

    def applies_to(self, relpath: str) -> bool:
        base = relpath.rsplit("/", 1)[-1]
        return relpath.startswith("tools/") or "bench" in base

    def check_file(self, ctx: FileContext,
                   project: ProjectContext) -> Iterable[Finding]:
        timing_lines: List[int] = []
        blocks: List[ast.Call] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _attr_name(node.func)
            if fn in self._TIMING and isinstance(node.func, ast.Attribute) \
                    and _attr_name(node.func.value) == "time":
                timing_lines.append(node.lineno)
            elif fn == "block_until_ready":
                blocks.append(node)
        for node in blocks:
            arg: Optional[ast.AST] = node.args[0] if node.args else None
            if isinstance(node.func, ast.Attribute) and not node.args:
                arg = node.func.value  # x.block_until_ready() form
            if not isinstance(arg, (ast.Name, ast.Attribute)):
                continue  # tuples/containers = full state, fine
            if _attr_name(arg) not in self._SCALAR_NAMES:
                continue
            if any(abs(t - node.lineno) <= self.WINDOW
                   for t in timing_lines):
                yield ctx.finding(
                    self, node,
                    f"block_until_ready({_attr_name(arg)}) next to timing "
                    f"code — queued programs may still be executing")


def _calls_with_scope(tree: ast.AST):
    """(enclosing FunctionDef | None, Call) pairs."""
    out = []

    def visit(node, scope):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scope = node
        if isinstance(node, ast.Call):
            out.append((scope, node))
        for child in ast.iter_child_nodes(node):
            visit(child, scope)

    visit(tree, None)
    return out
