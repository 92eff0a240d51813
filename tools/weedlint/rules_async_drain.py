"""W301: the streaming hot loop must never block on fetch.

Ported from tools/check_async_drain.py (PR 7).  The async multi-
buffered drain only pays off while nothing reintroduces a blocking
full-block fetch on the critical thread — a regression that stays
byte-correct and therefore invisible to every differential test:

  1. `_encode_file_staged` and `_encode_file_mesh` must each construct
     the AsyncDrainer (directly, or as per-device lanes through a
     DrainerGroup).
  2. Inside them, blocking-fetch calls (`_fetch`, `fetch`, `asarray`,
     `device_get`, `block_until_ready`) may appear ONLY within nested
     drain helpers (functions named `drain*`) — including the
     per-device `drain_fetch_dev`/`drain_write_dev` lane callbacks.
  3. Every `faultinject.hit("ec.drain")` in the package must sit
     lexically inside `with ... span("pipeline.drain", ...)` so
     delay-only slow-drain drills keep attributing to the drain stage.
"""

from __future__ import annotations

import ast
import os

from .engine import Finding, Repo, Rule, register

PACKAGE = "seaweedfs_tpu"
STREAMING_REL = os.path.join(PACKAGE, "ec", "streaming.py")
HOT_FUNCS = ("_encode_file_staged", "_encode_file_mesh")
DRAINER_CTORS = {"AsyncDrainer", "DrainerGroup"}
BLOCKING_CALLS = {"_fetch", "fetch", "asarray", "device_get",
                  "block_until_ready"}
DRAIN_PREFIXES = ("drain", "_drain")


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _is_drain_helper(name: str) -> bool:
    return name.startswith(DRAIN_PREFIXES)


def _check_hot_func(fn: ast.AST, path: str) -> list[Finding]:
    problems: list[Finding] = []

    def walk(node: ast.AST, inside_drain: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                walk(child, inside_drain or _is_drain_helper(child.name))
                continue
            if isinstance(child, ast.Call) and not inside_drain:
                name = _call_name(child)
                if name in BLOCKING_CALLS:
                    problems.append(Finding(
                        "W301", path, child.lineno,
                        f"blocking `{name}()` on the streaming hot "
                        f"loop (inside {fn.name}) — kernel output must "
                        f"come back through the async drainer (a "
                        f"drain* helper), not block the critical "
                        f"thread"))
            walk(child, inside_drain)

    walk(fn, False)
    return problems


def check_streaming_source(src: str, path: str) -> list[Finding]:
    """Rules 1+2 on ec/streaming.py."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("W301", path, e.lineno or 0,
                        f"does not parse: {e.msg}")]
    problems: list[Finding] = []
    fns = {node.name: node for node in ast.walk(tree)
           if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for name in HOT_FUNCS:
        fn = fns.get(name)
        if fn is None:
            problems.append(Finding(
                "W301", path, 0,
                f"{name} not found — the async-drain contract covers "
                f"it by name"))
            continue
        calls = {_call_name(c) for c in ast.walk(fn)
                 if isinstance(c, ast.Call)}
        if not (DRAINER_CTORS & calls):
            problems.append(Finding(
                "W301", path, fn.lineno,
                f"{name} no longer constructs AsyncDrainer (or a "
                f"DrainerGroup of per-device lanes) — the drain would "
                f"run inline on the critical thread and the drain-wait "
                f"stall returns"))
        problems.extend(_check_hot_func(fn, path))
    return problems


def check_drain_fault_source(src: str, path: str,
                             tree=None) -> list[Finding]:
    """Rule 3 on any package module."""
    if tree is None:
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError as e:
            return [Finding("W301", path, e.lineno or 0,
                            f"does not parse: {e.msg}")]
    problems: list[Finding] = []

    def span_names(with_node: ast.With) -> set[str]:
        names: set[str] = set()
        for item in with_node.items:
            ctx = item.context_expr
            if isinstance(ctx, ast.Call) and _call_name(ctx) == "span" \
                    and ctx.args \
                    and isinstance(ctx.args[0], ast.Constant):
                names.add(str(ctx.args[0].value))
        return names

    def walk(node: ast.AST, spans: frozenset) -> None:
        for child in ast.iter_child_nodes(node):
            child_spans = spans
            if isinstance(child, ast.With):
                child_spans = spans | span_names(child)
            if isinstance(child, ast.Call) \
                    and _call_name(child) == "hit" \
                    and child.args \
                    and isinstance(child.args[0], ast.Constant) \
                    and child.args[0].value == "ec.drain" \
                    and "pipeline.drain" not in spans:
                problems.append(Finding(
                    "W301", path, child.lineno,
                    'faultinject.hit("ec.drain") outside a `with '
                    'span("pipeline.drain")` block — delay-only '
                    'slow-drain drills would stop attributing to the '
                    'drain stage'))
            walk(child, child_spans)

    walk(tree, frozenset())
    return problems


@register
class AsyncDrainRule(Rule):
    id = "W301"
    name = "async-drain"
    summary = ("streaming encode hot loops must drain through "
               "AsyncDrainer, never block on fetch")

    def check(self, repo: Repo) -> list[Finding]:
        problems: list[Finding] = []
        streaming = repo.get(STREAMING_REL)
        if streaming is not None:
            problems.extend(
                check_streaming_source(streaming.source, STREAMING_REL))
        else:
            problems.append(Finding("W301", STREAMING_REL, 0, "missing"))
        for ctx in repo.package_files(PACKAGE):
            problems.extend(
                check_drain_fault_source(ctx.source, ctx.rel, ctx.tree))
        return problems
