"""One part of the device's idle gap between two serve programs, in
milliseconds per span that ``args["per"]`` selects, from the newest profiler
trace with the device's clock set against the host's by causality
(``lib/clock.py``).  ``args["programs"]`` maps a program's name on the ``XLA
Modules`` line to its site (``<site>/dispatch``, ``<site>/read``);
``args["part"]`` is

- ``"read_tail"``: from a program's last instant on the device to the return
  of the read that waited for it;
- ``"launch"``: from the start of a dispatch span to its program's first
  instant on the device (both summed between the window's first and last
  execution, so that tail + turnaround + launch is the device's idle time
  between programs);
- ``"slack"``: half the width of the interval the correction lies in (the
  spans' own bounds, narrowed by the runtime's marks where the trace has
  them): the +/- on the two above, an execution (not divided by ``per``);
- ``"turnaround"``: host time from a read's return to the next dispatch's
  start.  The host's clock alone: it reads without a device plane too.

None where there is nothing to read: no such span (an older commit), for the
three device parts no device plane (a CPU run), and for ``read_tail`` and
``launch`` bounds that cross (no constant correction fits the window).
"""

from benchmark.lib import clock
from benchmark.lib import spans as S


def read(observations: dict, args: dict):
    trace = S.load()
    per = len(S.select(trace["spans"], args["per"]))
    programs, part = args["programs"], args["part"]
    if part not in ("read_tail", "launch", "slack", "turnaround"):
        raise ValueError(f"gap_part_ms: unknown part {part!r}")
    if part == "turnaround":
        pairs = clock.host_pairs(trace["spans"], programs.values())
        if not per or len(pairs) < 2:
            return None
        return sum(clock.turnarounds(pairs)) / per / 1e6
    executed = clock.executions(trace["modules"], trace["spans"], programs)
    if not per or not executed:
        return None
    bounds = clock.offset_bounds(executed, *clock.runtime_marks())
    lowest, highest = bounds
    if part == "slack":
        return (highest - lowest) / 2 / 1e6
    if lowest > highest:
        return None
    parts = clock.split(executed, (lowest + highest) // 2)
    # between programs: not the launch ahead of the window's first execution
    # nor the tail behind its last, which launch_gap_ms.serve does not hold
    between = parts["tail"][:-1] if part == "read_tail" else parts["launch"][1:]
    return sum(between) / per / 1e6
