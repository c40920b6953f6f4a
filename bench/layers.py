"""Per-layer reductions of the program's own instrumentation.

The search program names its layers with ``jax.named_scope``
(``core/engine.py::three_pass_search``): ``pass1.lut``,
``pass1.sparse_bias`` (with ``inverted`` and ``head`` inside it),
``pass1.scan``, ``pass2`` and ``pass3``.  A device operation in the
profiler's trace is named by its HLO instruction alone; the scope is in the
compiled program's text (``ScoringEngine.search_hlo``), as the
instruction's ``metadata={op_name="jit(three_pass_search)/<scope>/..."}``.
``scope_map`` reads that text into instruction -> scope, and
``scope_seconds`` sums the device time of a trace's search runs by scope.

``QueryService`` with tracing on records ``serve.*`` span trees
(``Tracer.take()``); ``self_seconds`` and ``root_tags`` read them.

Nothing in ``run.py`` calls these yet: a run's ``RunData`` carries neither
the program's HLO text nor its spans (PERF.md, Open questions).
"""

from __future__ import annotations

import bisect
import collections
import re

import tracing

__all__ = ["TOP_SCOPES", "scope_of", "scope_map", "search_ops",
           "scope_seconds", "seconds_under", "self_seconds", "root_tags"]

TOP_SCOPES = ("pass1.lut", "pass1.sparse_bias", "pass1.scan", "pass2",
              "pass3")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|to_apply|condition|body|"
                     r"called_computations|branch_computations)="
                     r"(\{[^}]*\}|%?[\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The named-scope path of an instruction's ``op_name``: the components
    after the program's ``jit(...)`` up to the next transformation or the
    primitive, "" when there are none.  ``jit(f)/pass2/jit(g)/add`` is in
    ``pass2``; ``jit(f)/pass1.sparse_bias/head/slice`` in
    ``pass1.sparse_bias/head``."""
    parts = op_name.split("/")
    if len(parts) < 3 or not parts[0].startswith("jit("):
        return ""
    path = []
    for p in parts[1:-1]:
        if "(" in p:
            break
        path.append(p)
    return "/".join(path)


def scope_map(hlo_text: str) -> dict[str, str]:
    """Instruction name (without ``%``) -> scope path, over every
    computation of an HLO module's text.

    The compiler adds instructions with no metadata (layout copies,
    fusions, sorts, loops).  Each takes, in this order: the most common
    scope of the computations it calls (a fusion); the one scope of its
    nearest scoped users, or else of its nearest scoped operands, when
    they agree (a copy made for one layer's use); the scope of the
    instruction that calls its computation (a loop body).  Otherwise it
    stays ""."""
    own: dict[str, str] = {}
    refs: dict[str, list[str]] = {}
    calls: dict[str, list[str]] = {}
    comp_of: dict[str, str] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s or s.startswith("HloModule"):
            if s.endswith("{"):
                comp = s.removeprefix("ENTRY ").split()[0].lstrip("%")
            continue
        name, rhs = s.removeprefix("ROOT ").split(" = ", 1)
        name = name.lstrip("%")
        m = _OP_NAME.search(rhs)
        own[name] = scope_of(m.group(1)) if m else ""
        refs[name] = _REF.findall(rhs)
        calls[name] = [c.strip().lstrip("%")
                       for g in _CALLED.findall(rhs)
                       for c in g.strip("{}").split(",") if c.strip()]
        comp_of[name] = comp
        members[comp].append(name)

    # a fusion or call: the most common scope of what it calls
    comp_scope: dict[str, str] = {}

    def called_scope(c: str) -> str:
        if c not in comp_scope:
            comp_scope[c] = ""          # a computation calling itself
            votes = collections.Counter(inner(i) for i in members.get(c, ()))
            votes.pop("", None)
            comp_scope[c] = votes.most_common(1)[0][0] if votes else ""
        return comp_scope[c]

    def inner(i: str) -> str:
        return own[i] or next(
            (sc for c in calls[i] if (sc := called_scope(c))), "")

    scope = {i: inner(i) for i in own}

    # a copy or relayout: the scope its neighbours agree on
    users: dict[str, list[str]] = collections.defaultdict(list)
    operands: dict[str, list[str]] = {}
    for i, rs in refs.items():
        operands[i] = [r for r in rs if r != i
                       and comp_of.get(r) == comp_of[i]]
        for r in operands[i]:
            users[r].append(i)

    def nearest(i: str, step) -> set[str]:
        found, seen, todo = set(), {i}, list(step(i))
        while todo:
            j = todo.pop()
            if j in seen:
                continue
            seen.add(j)
            if scope[j]:
                found.add(scope[j])
            else:
                todo.extend(step(j))
        return found

    guessed = {}
    for i in own:
        if not scope[i]:
            for step in (lambda j: users[j], lambda j: operands[j]):
                near = nearest(i, step)
                if len(near) == 1:
                    guessed[i] = near.pop()
                    break
    scope.update(guessed)

    # a loop body or condition: the scope of the loop
    for i in list(own):
        if scope[i]:
            for c in calls[i]:
                for j in members.get(c, ()):
                    if not scope[j]:
                        scope[j] = scope[i]
    return scope


def search_ops(trace: tracing.Trace,
               module: str = tracing.SEARCH_MODULE) -> list[tracing.Event]:
    """The device operations that ran inside the runs of the search program
    that started in the window, over all devices."""
    w = trace.window
    if w is None:
        return []
    rx = re.compile(module)
    out = []
    for plane, mods in trace.modules.items():
        runs = sorted((e.start_ns, e.end_ns) for e in mods
                      if rx.search(e.name) and w[0] <= e.start_ns < w[1])
        starts = [r[0] for r in runs]
        for e in trace.ops.get(plane, ()):
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k >= 0 and e.start_ns < runs[k][1]:
                out.append(e)
    return out


def scope_seconds(trace: tracing.Trace, scopes: dict[str, str],
                  module: str = tracing.SEARCH_MODULE
                  ) -> dict[str, float] | None:
    """Device seconds per run of the search program, by scope path ("" for
    time under no scope).  None when the window has no run, or when an
    operation of a run is missing from ``scopes``: a map of another
    compilation gives no number rather than a wrong one."""
    n = tracing.calls(trace, module)
    if n == 0:
        return None
    totals: dict[str, float] = collections.defaultdict(float)
    for e, ns in _self_ns(search_ops(trace, module)):
        scope = scopes.get(e.name.lstrip("%"))
        if scope is None:
            return None
        totals[scope] += ns
    return {k: v / n * 1e-9 for k, v in totals.items()}


def _self_ns(events) -> list[tuple[tracing.Event, float]]:
    """Each event with its self time: a loop's event encloses the events
    of its body, whose time is theirs and not the loop's."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent[1] -= min(e.end_ns, parent[0].end_ns) - e.start_ns
        item = [e, e.dur_ns]
        stack.append(item)
        out.append(item)
    return [(e, ns) for e, ns in out]


def seconds_under(seconds: dict[str, float] | None, *prefixes: str
                  ) -> float | None:
    """The sum of ``seconds`` over the scopes at or under any of
    ``prefixes`` (``"pass2"`` takes ``"pass2"`` and ``"pass2/..."``)."""
    if seconds is None:
        return None
    return sum(v for k, v in seconds.items()
               if any(k == p or k.startswith(p + "/") for p in prefixes))


def _walk(node: dict):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


def self_seconds(roots: list[dict], name: str) -> list[float]:
    """Self time of every span named ``name`` in the span trees ``roots``
    (``Tracer.take()``): its duration less its children's."""
    return [n["duration_s"] - sum(c["duration_s"] or 0.0
                                  for c in n.get("children", ()))
            for r in roots for n in _walk(r)
            if n["name"] == name and n["duration_s"] is not None]


def root_tags(roots: list[dict], tag: str) -> list:
    """The values of tag ``tag`` on the roots that carry it."""
    return [r["tags"][tag] for r in roots if tag in r.get("tags", {})]
