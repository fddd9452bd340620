"""``scale * observations[key]``."""


def read(observations: dict, args: dict):
    value = observations.get(args["key"])
    if value is None:
        return None
    return float(args.get("scale", 1.0)) * value
