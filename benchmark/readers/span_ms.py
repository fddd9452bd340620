"""Milliseconds in the program's spans that ``args["spans"]`` selects, per
span that ``args["per"]`` selects, from the newest profiler trace.

``"self": true`` counts only what a span's children do not cover;
``"except": [..]`` takes away what descendants selected by it cover (the
facade's calls without the engine's dispatches inside them).  Selectors are
as ``lib.spans.matcher`` takes them.
"""

from benchmark.lib import spans as S


def read(observations: dict, args: dict):
    spans = S.load()["spans"]
    per = len(S.select(spans, args["per"]))
    wanted = S.matcher(args["spans"])
    if not per or not any(map(wanted, spans)):
        return None
    if args.get("self"):
        ns = sum(own for span, own in S.self_time(spans) if wanted(span))
    else:
        ns = sum(span[2] for span in spans if wanted(span))
    if args.get("except"):
        ns -= sum(span[2] for span in
                  S.select(S.inside(spans, wanted), args["except"]))
    return ns / per / 1e6
