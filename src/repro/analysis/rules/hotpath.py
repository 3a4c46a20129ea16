"""R5 ``hotpath-alloc`` — the vectorized hot path stays allocation-lean.

PR 2 rebuilt the arrival/dispatch path around bulk numpy passes
(``BinScoreModel.score_many`` + ``HullQueue.insert_many``); its perf floor
is CI-gated via ``BENCH_sched.json``.  The contract: inside the hot
functions, *per-item loops must not allocate containers* — one bulk
allocation per call is the approved shape, a dict/list/set birth per
request is the regression this rule catches before the benchmark does.

Scope is an explicit allowlist of (file suffix, qualified function) pairs
— the scheduler arrival path and the event-loop inner loop — so ordinary
code keeps full freedom.  Within those functions the rule flags, *inside
any loop body*: container literals/displays, ``list``/``dict``/``set``
constructor calls, comprehensions, and ``lambda`` creation.  Allocations
that are semantically required (per-request feasibility state, the
coalescing buffers) carry inline ``# simlint: ignore[R5]`` justifications
— the suppression is the documentation.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding

# (path suffix, qualified scope) — the PR-2 scheduler hot path plus the
# event loop: its one body and the per-event code of its two event
# sources (the array source's whole point is bulk columnar work; a
# per-event container birth there is a regression)
_SOURCE_METHODS = ("events", "push", "started", "finished", "unstarted", "alone")
HOT_FUNCTIONS: tuple[tuple[str, str], ...] = (
    ("core/scheduler.py", "OrlojScheduler.on_arrivals"),
    ("core/scheduler.py", "OrlojScheduler.next_batch"),
    ("core/eventloop.py", "run_event_loop"),
    ("core/eventloop.py", "run_event_loop.try_dispatch"),
    ("core/eventloop.py", "run_event_loop.abort_batch"),
    ("core/eventloop.py", "run_event_loop.credit_busy_until"),
) + tuple(
    ("core/eventloop.py", f"{source}.{method}")
    for source in ("_HeapSource", "_ArraySource")
    for method in _SOURCE_METHODS
)

_CTOR_CALLS = {"list", "dict", "set"}
_LOOPS = (ast.For, ast.AsyncFor, ast.While)
_SCOPE_BARRIERS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)


class HotPathAllocRule:
    rule_id = "R5"
    name = "hotpath-alloc"
    zones = ("src/repro/core",)
    description = (
        "per-item container allocation inside the vectorized scheduler/"
        "event-loop hot path (PR 2 contract)"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        hot_scopes = {
            scope for suffix, scope in HOT_FUNCTIONS if ctx.path.endswith(suffix)
        }
        if not hot_scopes:
            return
        index = _function_index(ctx.tree)
        seen: set[tuple[int, int]] = set()  # dedupe under nested loops
        for qual, fn in index.items():
            if qual not in hot_scopes:
                continue
            for loop in _scoped_nodes(fn.body):
                if not isinstance(loop, _LOOPS):
                    continue
                for node in _scoped_nodes(loop.body):
                    kind = _alloc_kind(node)
                    pos = (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
                    if kind is not None and pos not in seen:
                        seen.add(pos)
                        yield ctx.finding(
                            self,
                            node,
                            f"{kind} allocated inside a `{qual}` loop body — "
                            "the hot path allocates in bulk, once per call "
                            "(PR 2 vectorization contract)",
                        )


def _alloc_kind(node: ast.AST) -> str | None:
    if isinstance(node, ast.ListComp):
        return "list comprehension"
    if isinstance(node, ast.SetComp):
        return "set comprehension"
    if isinstance(node, ast.DictComp):
        return "dict comprehension"
    if isinstance(node, ast.List):
        return "list literal"
    if isinstance(node, ast.Dict):
        return "dict literal"
    if isinstance(node, ast.Set):
        return "set literal"
    if isinstance(node, ast.Lambda):
        return "lambda"
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id in _CTOR_CALLS
    ):
        return f"{node.func.id}() call"
    return None


def _scoped_nodes(body: list[ast.stmt]) -> Iterator[ast.AST]:
    """Pre-order walk not descending into nested defs (a nested helper is
    its own hot-list entry if it matters)."""
    stack: list[ast.AST] = list(reversed(body))
    while stack:
        node = stack.pop()
        if isinstance(node, _SCOPE_BARRIERS):
            continue
        yield node
        stack.extend(reversed(list(ast.iter_child_nodes(node))))


def _function_index(
    tree: ast.Module,
) -> dict[str, ast.FunctionDef | ast.AsyncFunctionDef]:
    out: dict[str, ast.FunctionDef | ast.AsyncFunctionDef] = {}

    def visit(node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}.{child.name}" if prefix else child.name
                out[qual] = child
                visit(child, qual)
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}.{child.name}" if prefix else child.name)
            else:
                visit(child, prefix)

    visit(tree, "")
    return out
