"""``scale * observations[num] / observations[den]``."""


def read(observations: dict, args: dict):
    num, den = observations.get(args["num"]), observations.get(args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
