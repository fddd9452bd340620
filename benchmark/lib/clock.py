"""The device's clock set against the host's by causality, and a serve
step's idle gap split by it.

A profiler trace holds the program's spans on the host's clock and the
device's events on the device's, which the profiler sets against the host's
once a session, to a millisecond or two (``lib/spans.py``).  That is as long
as the two ends of the gap between two programs: the tail of the blocking
read after the device finished, and the time from the dispatch call to the
device's first operation.  But the program's own spans bound the error.  A
program cannot start on the device before the host began to dispatch it, and
the read that waited for it cannot return before it ended.  With

- ``D`` the start of the ``<site>/dispatch`` span,
- ``S``, ``E`` the start and end of the program on the ``XLA Modules`` line,
- ``R`` the end of the ``<site>/read`` span,

the correction ``d`` to add to the device's clock satisfies ``D <= S + d``
and ``E + d <= R`` for every execution: ``d`` lies in ``[max(D - S),
min(R - E)]``, an interval as wide as the shortest launch plus the shortest
read tail the window saw (1.1-1.3 ms on the v5e machine, PR 37).  Two of
the runtime's own events on the host plane, where the trace has them, narrow
it to a third of a millisecond (``runtime_marks``): the program is handed to
the device inside ``DoEnqueueProgram``, so that event's start precedes
``S``, and the host has read the device's completion flag when
``ReadSyncFlag`` ends, so that end follows ``E``.  One constant fitted every
15 s window looked at (the bounds by quarter of a window agree to 0.06 ms; a
line's slope came out under 12 ppm): nothing here fits a drift.

Everything but ``runtime_marks`` is a pure function over the lists
``lib.spans.load()`` returns, in nanoseconds.
"""

from __future__ import annotations

import functools
import os
from bisect import bisect_left, bisect_right
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.lib.spans import Event, Span, newest_xplane
from benchmark.lib.trace_reduce import HOST_PLANE

# the TPU runtime's events, on whichever host thread runs them (libtpu's
# names, looked at by hand, PR 37; a trace without them gives no mark and
# the spans' own bounds stand)
ENQUEUED = "DoEnqueueProgram"  # its START precedes the program's start
COMPLETED = "ReadSyncFlag"  # its END follows the program's end

HostPair = Tuple[int, int]  # (D, R)
Execution = Tuple[int, int, int, int]  # (D, S, E, R)


def host_pairs(spans: Iterable[Span], sites: Iterable[str]) -> List[HostPair]:
    """``[(D, R), ...]`` in time order, all ``sites`` together: the start of
    each ``<site>/dispatch`` span and the end of the ``<site>/read`` span
    that follows it.  What the trace's edges cut is dropped: a read whose
    dispatch began before the trace, a dispatch whose read ended after it."""
    pairs: List[HostPair] = []
    for site in sites:
        dispatch, read = site + "/dispatch", site + "/read"
        began: Optional[int] = None
        for name, start, dur, _ in sorted(
                (s for s in spans if s[0] in (dispatch, read)),
                key=lambda s: s[1]):
            if name == dispatch:
                began = start
            elif began is not None:
                pairs.append((began, start + dur))
                began = None
    return sorted(pairs)


def turnarounds(pairs: Sequence[HostPair]) -> List[int]:
    """Host time from each read's return to the next dispatch's start: the
    host's own clock, no device in it."""
    return [nxt[0] - cur[1] for cur, nxt in zip(pairs, pairs[1:])]


def executions(modules: Iterable[Event], spans: Iterable[Span],
               programs: Dict[str, str]) -> List[Execution]:
    """``[(D, S, E, R), ...]`` in time order: the k-th execution of each
    program of ``programs`` (``{name on the XLA Modules line: site}``) with
    the k-th dispatch and read of its site.  Which execution is a site's
    first in the trace is settled by position: an execution belongs to the
    dispatch-to-read interval that holds its midpoint (the two clocks differ
    by far less than half a program).  A module whose dispatch began before
    the trace and a span whose module the trace does not hold are dropped,
    and so that consecutive entries are consecutive programs, only the
    longest run of matched pairs is returned."""
    spans = list(spans)
    tagged = []  # (D, R, (S, E) or None)
    for program, site in programs.items():
        ran = sorted((s, s + d) for n, s, d in modules if n == program)
        at = 0
        for D, R in host_pairs(spans, [site]):
            # twice the midpoint, to stay in whole nanoseconds
            while at < len(ran) and ran[at][0] + ran[at][1] < 2 * D:
                at += 1
            found = at < len(ran) and ran[at][0] + ran[at][1] <= 2 * R
            tagged.append((D, R, ran[at] if found else None))
            at += found
    runs: List[List[Execution]] = [[]]
    for D, R, module in sorted(tagged):
        if module is None:
            runs.append([])
        else:
            runs[-1].append((D, module[0], module[1], R))
    return max(runs, key=len)


def offset_bounds(executed: Iterable[Execution],
                  enqueued: Sequence[int] = (),
                  completed: Sequence[int] = ()) -> Optional[Tuple[int, int]]:
    """``(lowest, highest)`` correction to the device's clock that no
    execution contradicts; ``lowest > highest`` when no constant fits (the
    device's clock drifts against the host's inside the window, or a mark
    is not what it is taken for).  None without an execution.

    ``enqueued`` and ``completed`` are instants on the host's clock, sorted,
    that tighten the interval (``runtime_marks``): an ``enqueued`` one
    inside an execution's ``[D, R]`` precedes that program's start, a
    ``completed`` one there follows its end.  They only ever narrow the
    bounds; what is measured from ``D`` and to ``R`` stays measured so."""
    lows, highs = [], []
    for D, S, E, R in executed:
        before = enqueued[bisect_left(enqueued, D):bisect_right(enqueued, R)]
        after = completed[bisect_left(completed, D):bisect_right(completed, R)]
        lows.append(max([D, *before]) - S)
        highs.append(min([R, *after]) - E)
    return (max(lows), min(highs)) if lows else None


def split(executed: Sequence[Execution], d: int) -> Dict[str, List[int]]:
    """With ``d`` added to the device's clock: per execution ``launch``
    (dispatch call to the program's first instant) and ``tail`` (its last
    instant to the read's return), and between consecutive executions
    ``turnaround``.  ``tail[k] + turnaround[k] + launch[k + 1]`` is ``S[k +
    1] - E[k]``, the device's idle time between the two programs, whatever
    ``d`` is."""
    return {
        "launch": [S + d - D for D, S, _, _ in executed],
        "tail": [R - E - d for _, _, E, R in executed],
        "turnaround": turnarounds([(D, R) for D, _, _, R in executed]),
    }


# --------------------------------------------------------------------------- #
# the adapter from the trace file
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=1)
def _marks(path: str, mtime: float) -> Tuple[List[int], List[int]]:
    from jax.profiler import ProfileData

    enqueued: List[int] = []
    completed: List[int] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ENQUEUED:
                    enqueued.append(int(ev.start_ns))
                elif ev.name == COMPLETED:
                    completed.append(int(ev.start_ns) + int(ev.duration_ns))
    return sorted(enqueued), sorted(completed)


def runtime_marks(path: Optional[str] = None) -> Tuple[List[int], List[int]]:
    """``(enqueued, completed)`` for ``offset_bounds``, sorted, on the host's
    clock: the starts of the runtime's ``DoEnqueueProgram`` events and the
    ends of its ``ReadSyncFlag`` events on any thread of the host plane of a
    trace (default: the newest under ``.bench_traces``).  Empty without a
    trace or without such events.  Parsed once per file."""
    path = path or newest_xplane()
    if path is None:
        return [], []
    return _marks(path, os.path.getmtime(path))
