"""``scale`` x the mean of attribute ``args["attr"]`` over the program's
spans named ``args["span"]``, from the newest profiler trace."""

from benchmark.lib import spans as S


def read(observations: dict, args: dict):
    values = [
        s[3][args["attr"]] for s in S.select(S.load()["spans"], [args["span"]])
        if args["attr"] in s[3]
    ]
    if not values:
        return None
    return float(args.get("scale", 1.0)) * sum(values) / len(values)
