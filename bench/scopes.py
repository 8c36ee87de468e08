"""The JAX scope each device op of a profiler trace was emitted under, for
the readers that need it (``layer_metrics/active_mask_ms_per_gen.py``).

``trace.reduce`` reads a trace through ``jax.profiler.ProfileData``, which
drops the stats of an event's metadata.  There a device op's ``tf_op``
stat holds its scope path, for example
``jit(evolve_chunk)/while/body/closed_call/vmap(active_mask)/while/body/or``.
This module reads that stat with ``bench/xspace.py`` and gives each op of
the reduced trace, matched by name, its scope: the path less the op itself
and the control flow it ends in (``while``, ``body``, ``cond``,
``closed_call``), so a loop's body and the copies of its carry share the
loop's scope, here ``jit(evolve_chunk)/while/body/closed_call/
vmap(active_mask)``.  Not every op has that stat (a ``while`` has none,
many fusions and copies neither); such an op takes a scope by nesting:

* one whose interval holds scoped ops takes their scope, if they all have
  the same one (the active-mask scan takes ``active_mask`` from its body;
  the generation scan, which holds the kernel, mutation and the
  active-mask scan, stays unscoped);
* one nested inside a scoped op takes that op's scope.

An op name that two metadata entries give different scopes stays unscoped
and is counted.

A reader gets the reduced trace, not its file: ``xplane`` finds the file
where ``bench/run.py`` writes it, ``<WORK_DIR>/<pid>/trace``, unless the
caller has set ``run.xplane``.
"""
from __future__ import annotations

import functools
import os
import re

from bench import trace

#: the event-metadata stat that holds a device op's scope path
SCOPE_STAT = "tf_op"
#: control-flow components of a scope path, which open no scope of their own
CONTROL_FLOW = ("while", "body", "cond", "closed_call")
_MIXED = object()


def xplane(run) -> str | None:
    """The trace file a reader's ``run`` was reduced from, if found."""
    path = getattr(run, "xplane", None)
    if path is not None:
        return path
    from bench import run as bench_run

    try:  # bench/run.py: work_dir = WORK_DIR/<pid>, log_dir = work_dir/trace
        return trace.find_xplane(os.path.join(
            bench_run.WORK_DIR, str(os.getpid()), "trace"))
    except FileNotFoundError:
        return None


def scope_of(tf_op: str) -> str:
    """The scope of an op from its ``tf_op`` stat (``path:type``, where a
    fused op's path may go on after a ``;``): the first path less its last
    component (the op) and the control flow it ends in."""
    parts = tf_op.rsplit(":", 1)[0].split(";")[0].split("/")[:-1]
    while parts and parts[-1] in CONTROL_FLOW:
        parts.pop()
    return "/".join(parts)


def op_scopes(path: str) -> tuple[dict[str, str], int]:
    """(op name -> scope, count of op names left out because their
    metadata gives two scopes), over every device plane of the trace."""
    from bench import xspace

    seen: dict[str, set[str]] = {}
    for plane in xspace.read(path):
        if trace.DEVICE_PLANE.match(plane.name):
            for name, stats in plane.event_metadata.values():
                tf_op = stats.get(SCOPE_STAT)
                if tf_op:
                    seen.setdefault(name, set()).add(scope_of(tf_op))
    scopes = {name: s.pop() for name, s in seen.items() if len(s) == 1}
    return scopes, len(seen) - len(scopes)


def nest(ops: list[trace.Event], scope: list[str]) -> list[str]:
    """The scopes of one chip's ``ops``, ``scope`` (their own, "" where
    they have none) completed by nesting (module docstring)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    scope = list(scope)
    parent: dict[int, int] = {}
    stack: list[int] = []
    for i in order:
        e = ops[i]
        while stack and ops[stack[-1]].end <= e.start:
            stack.pop()
        # the innermost op above it that it ends inside: it may overlap the
        # op on top without nesting
        p = next((j for j in reversed(stack) if e.end <= ops[j].end), None)
        if p is not None:
            parent[i] = p
        stack.append(i)
    # what each op holds: the one scope of its scoped ops, or _MIXED; an op
    # comes after the op that holds it, so walk backwards
    held: dict[int, object] = {}
    for i in reversed(order):
        inner = held.pop(i, None)
        if not scope[i] and isinstance(inner, str):
            scope[i] = inner
        p = parent.get(i)
        for s in (inner, scope[i]):
            if p is not None and s:
                held[p] = s if held.get(p, s) == s else _MIXED
    for i in order:
        p = parent.get(i)
        if not scope[i] and p is not None and scope[p]:
            scope[i] = scope[p]
    return scope


def scoped_ops(ops: list[trace.Event], path: str
               ) -> tuple[list[tuple[trace.Event, str]], int]:
    """(each of ``ops`` with its scope, count of op names with two
    scopes), the scopes read from the trace file at ``path``."""
    names, ambiguous = op_scopes(path)
    if not names:
        return [(e, "") for e in ops], ambiguous
    out = []
    for dev in sorted({e.where for e in ops}):
        mine = [e for e in ops if e.where == dev]
        out.extend(zip(mine, nest(mine, [names.get(e.name, "")
                                         for e in mine])))
    return out, ambiguous


@functools.lru_cache(maxsize=4096)
def in_scope(scope: str, name: str) -> bool:
    """Whether ``name`` is a component of the scope path, bare or inside
    transforms (``vmap(active_mask)``)."""
    rx = re.compile(rf"(\w+\()*{re.escape(name)}\)*")
    return any(rx.fullmatch(c) for c in scope.split("/"))
