"""FIG002 — retrace hazards around `jax.jit` dispatch signatures.

Zero-retrace serving rests on two structural facts that nothing at runtime
enforces until a trace-counter test happens to cover the broken path:

  * ``static_argnames`` handed to `jax.jit` must name real parameters of the
    jitted callable, and a parameter marked static must not default to an
    unhashable literal (list/dict/set) — both fail only at first dispatch;
  * a function closed over a plan and then jitted re-traces per plan object
    and pins the plan's buffers in jit's cache. Plans must pass *through*
    jit as pytree arguments (the engine's whole design); deliberate
    plan-closed benchmark helpers carry a suppression with their reason.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..framework import FileContext, Finding, Rule, Severity

_PLAN_BUILDERS = frozenset({"build_plan", "build_capacity_plan", "plan_for",
                            "refresh_plan"})


def _is_jit(ctx: FileContext, func: ast.AST) -> bool:
    dotted = ctx.resolve(func)
    return dotted is not None and (dotted == "jax.jit"
                                   or dotted.endswith(".jax.jit"))


def _jit_call(ctx: FileContext, node: ast.Call) -> bool:
    """True for ``jax.jit(...)`` and ``functools.partial(jax.jit, ...)``."""
    if _is_jit(ctx, node.func):
        return True
    dotted = ctx.resolve(node.func)
    return (dotted in ("functools.partial", "partial") and node.args
            and _is_jit(ctx, node.args[0]))


def _static_argnames(node: ast.Call) -> tuple[ast.keyword | None, list[str]]:
    for kw in node.keywords:
        if kw.arg == "static_argnames":
            names: list[str] = []
            if isinstance(kw.value, (ast.Tuple, ast.List, ast.Set)):
                for elt in kw.value.elts:
                    if isinstance(elt, ast.Constant) and isinstance(elt.value,
                                                                    str):
                        names.append(elt.value)
                    else:
                        return kw, []  # non-literal entry: not checkable
            elif isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, str):
                names.append(kw.value.value)
            else:
                return kw, []  # computed at run time: not checkable
            return kw, names
    return None, []


def _param_names(fn: ast.FunctionDef) -> set[str]:
    a = fn.args
    return {p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)}


def _unhashable_defaults(fn: ast.FunctionDef, static: set[str]) -> list[str]:
    a = fn.args
    out = []
    pos = a.posonlyargs + a.args
    for param, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        if param.arg in static and isinstance(default,
                                              (ast.List, ast.Dict, ast.Set)):
            out.append(param.arg)
    for param, default in zip(a.kwonlyargs, a.kw_defaults):
        if default is not None and param.arg in static and isinstance(
                default, (ast.List, ast.Dict, ast.Set)):
            out.append(param.arg)
    return out


def _free_names(fn: ast.AST) -> set[str]:
    """Names a function body loads but never binds — its closure surface.
    Approximate (no global/nonlocal handling): good enough to spot a
    captured plan."""
    bound: set[str] = set()
    loaded: set[str] = set()
    fns = [fn]
    while fns:
        f = fns.pop()
        if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            a = f.args
            bound |= {p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)}
            for p in (a.vararg, a.kwarg):
                if p is not None:
                    bound.add(p.arg)
        body = f.body if isinstance(f.body, list) else [f.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name):
                    if isinstance(node.ctx, ast.Load):
                        loaded.add(node.id)
                    else:
                        bound.add(node.id)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    bound.add(node.name)
    return loaded - bound


class _Scope:
    """One enclosing function: local defs, plan-ish names, jit calls."""

    def __init__(self, fn: ast.FunctionDef | None):
        self.fn = fn
        self.local_defs: dict[str, ast.FunctionDef] = {}
        self.planish: set[str] = set()


def _planish_names(fn: ast.FunctionDef, ctx: FileContext) -> set[str]:
    """Names in ``fn`` that look like FiGaRo plans: parameters or locals
    named/annotated so, or assigned from a plan builder."""
    out: set[str] = set()
    a = fn.args
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        ann = p.annotation
        ann_s = ast.unparse(ann) if ann is not None else ""
        if p.arg == "plan" or p.arg.endswith("_plan") or "FigaroPlan" in ann_s:
            out.add(p.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            callee = ctx.resolve(node.value.func)
            base = callee.rsplit(".", 1)[-1] if callee else ""
            if base in _PLAN_BUILDERS:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
    return out


class RetraceHazardRule(Rule):
    rule_id = "FIG002"
    severity = Severity.ERROR
    fix_hint = ("pass plans through jit as pytree arguments and name only "
                "real, hashable parameters in static_argnames")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        yield from self._check_jit_calls(ctx)

    # -- jit call sites ------------------------------------------------------

    def _check_jit_calls(self, ctx: FileContext) -> Iterator[Finding]:
        # Decorated defs: @functools.partial(jax.jit, static_argnames=...)
        # and @jax.jit-with-kwargs forms.
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in fn.decorator_list:
                if isinstance(dec, ast.Call) and _jit_call(ctx, dec):
                    yield from self._check_static_names(ctx, dec, fn)
        # Call-form jits inside a function scope: jax.jit(local_fn, ...).
        for scope_fn in ast.walk(ctx.tree):
            if not isinstance(scope_fn, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                continue
            local_defs = {stmt.name: stmt for stmt in ast.walk(scope_fn)
                          if isinstance(stmt, ast.FunctionDef)
                          and stmt is not scope_fn}
            planish = _planish_names(scope_fn, ctx)
            for node in ast.walk(scope_fn):
                if not (isinstance(node, ast.Call)
                        and _is_jit(ctx, node.func) and node.args):
                    continue
                target = node.args[0]
                inner = None
                if isinstance(target, ast.Name):
                    inner = local_defs.get(target.id)
                elif isinstance(target, ast.Lambda):
                    inner = target
                if inner is None:
                    continue
                if isinstance(inner, ast.FunctionDef):
                    yield from self._check_static_names(ctx, node, inner)
                captured = sorted(_free_names(inner) & planish)
                if captured:
                    yield self.finding(
                        ctx, node,
                        f"jitted closure captures plan value(s) "
                        f"{captured} — each plan object traces its own "
                        f"executable and pins its buffers in jit's cache; "
                        f"pass the plan as a pytree argument instead")

    def _check_static_names(self, ctx: FileContext, call: ast.Call,
                            fn: ast.FunctionDef | ast.Lambda
                            ) -> Iterator[Finding]:
        kw, names = _static_argnames(call)
        if kw is None or not names or isinstance(fn, ast.Lambda):
            return
        params = _param_names(fn)
        unknown = sorted(set(names) - params)
        if unknown:
            yield self.finding(
                ctx, call,
                f"static_argnames {unknown} are not parameters of "
                f"{fn.name}() — jit raises at first dispatch")
        bad_defaults = _unhashable_defaults(fn, set(names))
        for name in bad_defaults:
            yield self.finding(
                ctx, call,
                f"static parameter {name!r} of {fn.name}() defaults to an "
                f"unhashable literal — jit's static-arg hashing fails at "
                f"first dispatch")
