"""Mean milliseconds between the end of a span named ``args["inner"]`` and
the end of the span named ``args["outer"]`` around it, from the newest
profiler trace (``lib.spans.hold``)."""

from benchmark.lib import spans as S


def read(observations: dict, args: dict):
    held = S.hold(S.load()["spans"], args["inner"], args["outer"])
    if not held:
        return None
    return sum(held) / len(held) / 1e6
