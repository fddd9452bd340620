"""The program's own spans in a profiler trace, and what they own of the
device's time.

The program wraps its host sections in ``jax.profiler.TraceAnnotation``s
(``serve/step``, ``serve/decode_step/read``, ``stoke/loss``, ``stoke/apply``,
...: ``docs/observability.md`` lists them), which land on the host plane of
the same ``.xplane.pb`` that holds the device's events, on one timeline, each
with its attributes as the event's stats.  The profiler sets the device's
clock against the host's once per session, and on the v5e machine the two
were found 0.7 to 2.2 ms apart (``PERF.md`` section 5): what an idle gap's
*edges* fall into (the tail of a blocking read, the dispatch before the next
program) is that uncertain, so no metric reads those; the sum over owners,
and the owners in the middle of a gap (between a read's end and the next
upload), do not depend on it.
Everything here but ``load`` is a pure function over lists of

- spans ``(name, start_ns, dur_ns, stats)`` of ONE host thread, and
- device events ``(name, start_ns, dur_ns)`` of ONE device.

``load`` is the adapter from the trace file.  A trace of a program that has
no such spans (an older commit) gives empty lists, and every function then
returns nothing to read, never an error.
"""

from __future__ import annotations

import functools
import glob
import os
import sys
import time
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.lib.trace_reduce import (
    DEVICE_PLANE_PREFIX,
    HOST_MARK,
    HOST_PLANE,
    MODULES_LINE,
    OPS_LINE,
)

Span = Tuple[str, int, int, dict]
Event = Tuple[str, int, int]
Interval = Tuple[int, int]

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
PROGRAM_PREFIXES = ("serve/", "stoke/")
OUTSIDE = "outside"  # no program span covers the instant: the caller's time


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def nest(spans: Iterable[Span]) -> List[Tuple[Span, Optional[int]]]:
    """``[(span, index of its parent or None), ...]`` in order of start,
    by nesting: a span's parent is the innermost span that starts no later
    and ends no earlier.  A span that only overlaps the open one is not its
    child (one thread's annotations never do that)."""
    ordered = sorted(spans, key=lambda s: (s[1], -(s[1] + s[2])))
    out: List[Tuple[Span, Optional[int]]] = []
    stack: List[int] = []
    for span in ordered:
        start, end = span[1], span[1] + span[2]
        while stack:
            top = out[stack[-1]][0]
            if start >= top[1] and end <= top[1] + top[2]:
                break
            stack.pop()
        out.append((span, stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def self_time(spans: Iterable[Span]) -> List[Tuple[Span, int]]:
    """``[(span, ns of it that none of its children covers), ...]``."""
    nested = nest(spans)
    own = [span[2] for span, _ in nested]
    for span, parent in nested:
        if parent is not None:
            own[parent] -= span[2]
    return [(span, ns) for (span, _), ns in zip(nested, own)]


def inside(spans: Iterable[Span], is_outer) -> List[Span]:
    """The spans that have an ancestor for which ``is_outer(span)``."""
    nested = nest(spans)
    flag: List[bool] = []
    out = []
    for span, parent in nested:
        under = parent is not None and (
            flag[parent] or is_outer(nested[parent][0])
        )
        flag.append(under)
        if under:
            out.append(span)
    return out


def hold(spans: Iterable[Span], inner: str, outer: str) -> List[int]:
    """For every ``inner`` span with an ancestor named ``outer``: the
    nearest such ancestor's end minus its own end, in ns.  With ``inner``
    the prefill and ``outer`` the engine step this is how long a finished
    first token waits before the caller of ``step()`` can see it."""
    nested = nest(spans)
    out = []
    for span, parent in nested:
        if span[0] != inner:
            continue
        while parent is not None and nested[parent][0][0] != outer:
            parent = nested[parent][1]
        if parent is not None:
            up = nested[parent][0]
            out.append((up[1] + up[2]) - (span[1] + span[2]))
    return out


def idle_intervals(device_ops: Iterable[Event]) -> List[Interval]:
    """The gaps of the union of the device's events, from the first start
    to the last end: the same window and the same idle time as
    ``trace_reduce.reduce_events`` gives without a window."""
    evs = sorted((s, s + d) for _, s, d in device_ops if d > 0)
    gaps: List[Interval] = []
    if not evs:
        return gaps
    cover = evs[0][0]
    for s, e in evs:
        if s > cover:
            gaps.append((cover, s))
        cover = max(cover, e)
    return gaps


def ownership(spans: Iterable[Span]) -> List[Tuple[int, int, str]]:
    """The thread's time cut into ``(start, end, name)`` pieces in order,
    each named by the deepest program span (a name starting ``serve/`` or
    ``stoke/``) that covers it; time no program span covers is left out."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Span] = []  # the open spans, innermost last
    cursor = 0  # pieces are cut up to here

    def cut(upto: int) -> None:
        nonlocal cursor
        if upto > cursor:
            pieces.append((cursor, upto, stack[-1][0]))
            cursor = upto

    for span, _ in nest(s for s in spans if is_program_span(s[0])):
        while stack and stack[-1][1] + stack[-1][2] <= span[1]:
            cut(stack[-1][1] + stack[-1][2])
            stack.pop()
        if stack:
            cut(span[1])
        cursor = max(cursor, span[1])
        stack.append(span)
    while stack:
        cut(stack[-1][1] + stack[-1][2])
        stack.pop()
    return pieces


def idle_by_owner(device_ops: Iterable[Event],
                  spans: Iterable[Span]) -> Dict[str, int]:
    """``{owner: ns}``: every idle interval of the device split *by
    overlap* over the deepest program span covering each instant, the rest
    under ``outside`` (the caller, between two calls into the program).
    The values sum to the idle time exactly.  A gap that runs from the tail
    of a read through bookkeeping into the next upload has no single owner,
    which is why nothing here picks one."""
    owned: Dict[str, int] = {}
    pieces = ownership(spans)
    at = 0
    for g0, g1 in idle_intervals(device_ops):
        left = g1 - g0
        while at < len(pieces) and pieces[at][1] <= g0:
            at += 1
        k = at
        while k < len(pieces) and pieces[k][0] < g1:
            p0, p1, name = pieces[k]
            overlap = min(g1, p1) - max(g0, p0)
            if overlap > 0:
                owned[name] = owned.get(name, 0) + overlap
                left -= overlap
            k += 1
        if left:
            owned[OUTSIDE] = owned.get(OUTSIDE, 0) + left
    return owned


def per_execution(modules: Iterable[Event],
                  name: str) -> Optional[Tuple[int, int]]:
    """``(ns, executions)`` of the program called ``name`` on the device's
    ``XLA Modules`` line; None when it never ran."""
    durations = [d for n, _, d in modules if n == name]
    if not durations:
        return None
    return sum(durations), len(durations)


# --------------------------------------------------------------------------- #
# the adapter from the trace file
# --------------------------------------------------------------------------- #


def _process_started() -> float:
    """When this process started, on the files' clock: ``run.py`` takes
    ``_T_START`` first thing.  0 under any other ``__main__``."""
    t_start = getattr(sys.modules.get("__main__"), "_T_START", None)
    if t_start is None:
        return 0.0
    return time.time() - (time.perf_counter() - t_start)


def newest_xplane(root: str = CHECKOUT) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``<root>/.bench_traces/*/``, where
    ``run.py`` has every traced window written before any reader runs;
    never one from before this process started (another cell's, left by an
    earlier run)."""
    since = _process_started()
    found = [
        path for path in glob.glob(os.path.join(
            root, ".bench_traces", "*", "plugins", "profile", "*",
            "*.xplane.pb"))
        if os.path.getmtime(path) >= since
    ]
    return max(found, key=os.path.getmtime) if found else None


def _busy(ops: List[Event]) -> int:
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    return (last - first) - sum(b - a for a, b in idle_intervals(ops))


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData

    devices: Dict[str, dict] = {}
    spans: List[Span] = []
    marks = -1
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, {})["ops"] = [
                        ("", int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events
                    ]
                elif line.name == MODULES_LINE:
                    devices.setdefault(plane.name, {})["modules"] = [
                        (ev.name.split("(", 1)[0], int(ev.start_ns),
                         int(ev.duration_ns))
                        for ev in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = list(line.events)
                n = sum(ev.name.startswith(HOST_MARK) for ev in evs)
                if n > marks:
                    marks = n
                    with warnings.catch_warnings():
                        # the stats iterator's type has no __module__
                        warnings.simplefilter("ignore", DeprecationWarning)
                        spans = [
                            (ev.name, int(ev.start_ns), int(ev.duration_ns),
                             dict(ev.stats))
                            for ev in evs if is_program_span(ev.name)
                        ]
    ran = [d for d in devices.values() if d.get("ops")]
    busiest = max(ran, key=lambda d: _busy(d["ops"])) if ran else {}
    return {"spans": spans, "ops": busiest.get("ops", []),
            "modules": busiest.get("modules", [])}


def load(path: Optional[str] = None) -> dict:
    """``{"spans": [...], "ops": [...], "modules": [...]}`` of a trace
    (default: the newest under ``.bench_traces``): the program's spans on
    the host's Python thread (the line ``trace_reduce.read_trace`` picks,
    with each event's stats kept), and the ``XLA Ops`` and ``XLA Modules``
    lines of the busiest chip.  Empty lists when there is no trace, no
    device plane (a CPU run) or no span.  Parsed once per file."""
    path = path or newest_xplane()
    if path is None:
        return {"spans": [], "ops": [], "modules": []}
    return _load(path, os.path.getmtime(path))


# --------------------------------------------------------------------------- #
# selecting spans in a metric file
# --------------------------------------------------------------------------- #


def matcher(selectors: Sequence):
    """``span -> bool``: whether any selector matches.  A selector is a
    name, or ``{"name": .., "attr": .., "in": [..]}``: that name with the
    attribute among the values (``stoke/dispatch`` whose ``program`` is
    ``fused``)."""
    names = {s for s in selectors if isinstance(s, str)}
    rules = [s for s in selectors if not isinstance(s, str)]

    def match(span: Span) -> bool:
        return span[0] in names or any(
            span[0] == r["name"] and span[3].get(r["attr"]) in r["in"]
            for r in rules
        )

    return match


def select(spans: Iterable[Span], selectors: Sequence) -> List[Span]:
    return list(filter(matcher(selectors), spans))
