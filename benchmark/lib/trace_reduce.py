"""From a profiler trace to busy time, idle gaps and time per operation.

``reduce_events`` is the whole reduction: one function over a list of
``(name, start_ns, dur_ns)`` events of ONE device.  ``device_events`` is the
thin adapter that turns an ``.xplane.pb`` written by ``jax.profiler`` into
such lists, one per device.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]

# What a v5e trace holds (looked at by hand, PR 24): one plane per chip,
# "/device:TPU:<n>", with the lines "Steps", "XLA Modules" (one event per
# executed program, named "jit_<fn>(<fingerprint>)"), "XLA Ops" (one event
# per executed HLO instruction, named by the instruction's whole text; a
# Mosaic kernel is a custom-call named after the kernel, "%attention.<n>"
# for flash) and "Async XLA Ops" (copy-start/-done windows, which overlap
# compute and are not busy time).  The host is the plane "/host:CPU"; its
# line for the Python thread carries "PjitFunction(<fn>)", "DevicePut",
# "np.asarray(jax.Array)" and the program's own TraceAnnotations, on the
# same clock as the device lines.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
HOST_MARK = "PjitFunction("  # the host line with most of these is Python's
LABEL_MAX = 120
TOP = 10  # entries of each list of the breakdown
GAPS_CONSIDERED = 200  # longest idle gaps that get an owner and are summed

_LAYOUT = re.compile(r"\{[^{}]*\}")
_NUMBER = re.compile(r"\.\d+$")


def op_label(hlo: str) -> str:
    """``'%fusion.12 = bf16[4,1024]{1,0:T(8,128)} fusion(...)'`` ->
    ``'fusion bf16[4,1024]'``: the instruction's name without its number,
    and its output shape without layouts.  The same operation of every layer
    then carries the same label, and their times add up."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:LABEL_MAX]
    rest = _LAYOUT.sub("", rest)
    end = rest.index(")") + 1 if rest.startswith("(") else rest.find(" ")
    shape = rest[:end] if end > 0 else rest
    return f"{_NUMBER.sub('', lhs.lstrip('%'))} {shape}"[:LABEL_MAX]


def reduce_events(
    events: Iterable[Event],
    window: Optional[Tuple[int, int]] = None,
    top: int = 10,
) -> dict:
    """Busy and idle time of one device over ``window`` (default: first
    start to last end), the ``top`` operations by self time, and the ``top``
    longest idle gaps.

    Busy is the union of the event intervals, so overlapping and nested
    events count once.  An operation's self time is its duration minus what
    events nested inside it cover, so a ``while`` does not also claim its
    body; an event that only overlaps another keeps its whole duration.  A
    gap is ``(name, ns, start_ns)``, named by the first word of the
    operations on either side of it.
    """
    evs = sorted(
        ((int(s), int(s) + int(d), str(n)) for n, s, d in events if d > 0),
        key=lambda e: (e[0], -e[1]),
    )
    if window is None:
        if not evs:
            raise ValueError("no device event and no window given")
        window = (evs[0][0], max(e[1] for e in evs))
    w0, w1 = window
    self_ns: Dict[str, int] = {}
    gaps: List[Tuple[str, int, int]] = []
    word = lambda name: name.split(" ", 1)[0]
    busy = 0
    cover_end, cover_name = w0, "window-start"
    stack: List[list] = []  # [end, name, start, ns covered by children]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, start, covered = stack.pop()
            self_ns[name] = self_ns.get(name, 0) + (end - start) - covered
            if stack:
                stack[-1][3] += end - start

    for s, e, name in evs:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        close(s)
        if stack and e > stack[-1][0]:
            # overlaps the open event without lying inside it: not its
            # child, so it keeps its whole duration and charges nobody
            self_ns[name] = self_ns.get(name, 0) + (e - s)
        else:
            stack.append([e, name, s, 0])
        if s > cover_end:
            gaps.append(
                (f"{word(cover_name)} -> {word(name)}", s - cover_end,
                 cover_end)
            )
        if e > cover_end:
            busy += e - max(s, cover_end)
            cover_end, cover_name = e, name
    close(w1)
    if w1 > cover_end:
        gaps.append(
            (f"{word(cover_name)} -> window-end", w1 - cover_end, cover_end)
        )
    by_time = lambda kv: -kv[1]
    return {
        "window_ns": w1 - w0,
        "busy_ns": busy,
        "idle_ns": (w1 - w0) - busy,
        "ops": sorted(self_ns.items(), key=by_time)[:top],
        "gaps": sorted(gaps, key=by_time)[:top],
        "n_events": len(evs),
    }


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read_trace(xplane_path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}``, every list of ``(name, start_ns, dur_ns)``: the ``XLA Ops``
    and ``XLA Modules`` lines of every TPU plane, operations under their
    ``op_label``, and the host's Python thread.  No device when the trace
    has no TPU plane (a CPU run)."""
    from jax.profiler import ProfileData

    def events(line, rename=None):
        # a trace repeats a few thousand names half a million times
        names: Dict[str, str] = {}
        out = []
        for ev in line.events:
            name = ev.name
            if rename is not None:
                if name not in names:
                    names[name] = rename(name)
                name = names[name]
            out.append((name, int(ev.start_ns), int(ev.duration_ns)))
        return out

    devices: Dict[str, dict] = {}
    host: List[Event] = []
    host_marks = 0
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, {})["ops"] = events(
                        line, op_label)
                elif line.name == MODULES_LINE:
                    devices.setdefault(plane.name, {})["modules"] = events(
                        line, lambda n: n.split("(", 1)[0])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = events(line)
                marks = sum(n.startswith(HOST_MARK) for n, _, _ in evs)
                if marks > host_marks:
                    host, host_marks = evs, marks
    return {"devices": {k: v for k, v in devices.items() if v.get("ops")},
            "host": host}


def host_owner(host: List[Event], start: int, end: int) -> Optional[str]:
    """What the host's Python thread was doing during ``[start, end)``: the
    shortest host event that covers at least half of it, else the one that
    overlaps it most; None when nothing overlaps."""
    best, best_key = None, None
    for name, s, d in host:
        overlap = min(end, s + d) - max(start, s)
        if overlap <= 0:
            continue
        covers = 2 * overlap >= end - start
        key = (covers, -d if covers else overlap)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


class Tracer:
    """Start and stop of ``jax.profiler`` around a job's window, and the
    reduction of what it wrote.  The job calls ``start()`` when its window
    opens and ``stop()`` when it closes, with the device's work finished."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        # device events and host TraceMe spans; no Python-frame tracing,
        # which slows the host loop being measured
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        """Busy, window and idle time averaged over the devices that ran
        anything; of the busiest device, the operations by self time, the
        programs by time, and the longest idle gaps, each with what the
        host was doing in it, summed over gaps of the same name.  None when
        the trace holds no device event (a CPU run)."""
        trace = read_trace(find_xplane(self.trace_dir))
        per_device = {
            name: reduce_events(lines["ops"], top=GAPS_CONSIDERED)
            for name, lines in trace["devices"].items()
        }
        if not per_device:
            return None
        n = len(per_device)
        name, busiest = max(per_device.items(),
                            key=lambda kv: kv[1]["busy_ns"])
        modules: Dict[str, int] = {}
        for module, _, dur in trace["devices"][name].get("modules", []):
            modules[module] = modules.get(module, 0) + dur
        # a gap that recurs every step is one line: total time and count
        gaps: Dict[str, list] = {}
        for label, ns, start in busiest["gaps"]:
            owner = host_owner(trace["host"], start, start + ns)
            entry = gaps.setdefault(f"{label} | host: {owner}", [0, 0])
            entry[0] += ns
            entry[1] += 1
        by_time = lambda kv: -kv[1]
        return {
            "devices": n,
            "busy_ns": sum(r["busy_ns"] for r in per_device.values()) / n,
            "window_ns": sum(r["window_ns"] for r in per_device.values()) / n,
            "idle_ns": sum(r["idle_ns"] for r in per_device.values()) / n,
            "ops": busiest["ops"][:TOP],
            "modules": sorted(modules.items(), key=by_time)[:TOP],
            "gaps": sorted(
                ((f"{label} x{count}"[:LABEL_MAX], ns)
                 for label, (ns, count) in gaps.items()), key=by_time,
            )[:TOP],
            "n_events": sum(r["n_events"] for r in per_device.values()),
        }
