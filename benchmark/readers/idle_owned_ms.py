"""Milliseconds of device idle time that the program's spans named in
``args["owners"]`` own (``lib.spans.idle_by_owner``: split by overlap over
the deepest span covering each instant; ``"outside"`` is the time no span
covers, ``["*"]`` all idle time), per span named by ``args["per"]``.  None
without a device plane in the newest profiler trace."""

from benchmark.lib import spans as S


def read(observations: dict, args: dict):
    trace = S.load()
    per = len(S.select(trace["spans"], args["per"]))
    if not per or not trace["ops"]:
        return None
    owned = S.idle_by_owner(trace["ops"], trace["spans"])
    owners = owned if args["owners"] == ["*"] else args["owners"]
    return sum(owned.get(name, 0) for name in owners) / per / 1e6
