"""PF001: raw ``feed.next_batch`` feeding a jitted step inside a loop.

The pattern

.. code-block:: python

    while not feed.should_stop():
        batch = feed.next_batch(bs)
        state, loss = step(state, batch)   # step is jitted

serializes the feed pull + host columnize + H2D transfer with the device
step: the accelerator idles through the whole input path every
iteration. ``feed.prefetch.DevicePrefetcher`` (``from_feed``) moves the
pull/stage/transfer onto a producer thread so batch N+1's input cost
hides behind step N's compute (what that is worth per iteration is not
measured on this installation).

Heuristic (deliberately narrow, near-zero FP):

- "jitted step" = a name bound from ``jax.jit(...)`` / ``jit(...)``, a
  function decorated with ``@jax.jit`` (bare or via ``functools.partial``),
  or a name bound from the repo's jit-returning factory
  ``build_train_step(...)``. Names are collected module-wide.
- a ``For``/``While`` loop whose own body (nested defs excluded — a
  producer generator for a prefetcher is the FIX, not a violation) both
  calls ``<expr>.next_batch(...)`` and calls a jitted name is flagged at
  the ``next_batch`` call.

Suppress a justified site with a baseline entry (ratchet semantics) —
e.g. a debug loop where overlap is deliberately disabled.
"""

from __future__ import annotations

import ast

from tensorflowonspark_tpu.analysis.core import Config, Finding, Module, Package

__all__ = ["check"]

_JIT_FACTORIES = {"build_train_step"}


def _dotted(node: ast.AST) -> str | None:
    """'a.b.c' for an Attribute/Name chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit(...)`` / ``jit(...)`` / ``partial(jax.jit, ...)`` /
    ``build_train_step(...)`` (the repo's jit-returning factory)."""
    if not isinstance(node, ast.Call):
        return False
    name = _dotted(node.func)
    if name is None:
        return False
    tail = name.rsplit(".", 1)[-1]
    if tail == "jit" or tail in _JIT_FACTORIES:
        return True
    if tail == "partial" and node.args:
        inner = _dotted(node.args[0])
        return inner is not None and inner.rsplit(".", 1)[-1] == "jit"
    return False


def _jitted_names(tree: ast.AST) -> set:
    """Module-wide names that hold a jitted callable."""
    out: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            if _is_jit_expr(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                name = _dotted(dec)
                if _is_jit_expr(dec) or (
                    name is not None and name.rsplit(".", 1)[-1] == "jit"
                ):
                    out.add(node.name)
    return out


def _loop_body_nodes(loop: ast.AST):
    """Nodes of a loop body, not descending into nested function defs
    (a producer generator inside the loop is the prefetcher pattern)."""
    stack = list(ast.iter_child_nodes(loop))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def check(pkg: Package, cfg: Config) -> list:
    findings: list = []
    for mod in pkg.modules:
        jitted = _jitted_names(mod.tree)
        if not jitted:
            continue
        for loop in ast.walk(mod.tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            next_batch_calls: list = []
            step_called = False
            for node in _loop_body_nodes(loop):
                if not isinstance(node, ast.Call):
                    continue
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "next_batch"
                ):
                    next_batch_calls.append(node)
                elif (
                    isinstance(node.func, ast.Name)
                    and node.func.id in jitted
                ):
                    step_called = True
            if step_called:
                for call in next_batch_calls:
                    findings.append(
                        Finding(
                            "PF001",
                            mod.relpath,
                            call.lineno,
                            call.col_offset,
                            "raw feed.next_batch() feeds a jitted step in "
                            "this loop — the device idles through the pull "
                            "+ columnize + H2D every iteration; route the "
                            "feed through feed.prefetch.DevicePrefetcher "
                            "(from_feed) so transfer overlaps step compute",
                        )
                    )
    return findings
