"""Two shares of a roofline for a serve cell, from the newest profiler trace
and the counts of the configuration's family module (``serve_flops``,
``decode_bytes_per_step`` in ``lib/families/<family>.py``).
``args["config"]`` names the configuration file, ``args["what"]`` the share:

- ``"mfu"``: the whole step's share of the compute peak.  ``serve_flops`` of
  the traced window's prefill tokens (``prompt_len`` of the ``serve/prefill``
  spans), decode tokens (``active`` of ``serve/decode_step``) and context
  tokens (``context_tokens`` of ``serve/commit``), over the window's seconds
  x ``device.peak_flops_per_s`` (chips x the published bf16 peak).
- ``"decode_hbm"``: the decode program's share of its memory roofline.
  ``decode_bytes_per_step`` at the window's mean live rows and mean context
  tokens a step, over the device time of one execution of
  ``args["module"]`` x the published HBM bytes/s of the device kind.

None, never an error, where there is nothing to read: no peak (a CPU run),
no device plane, a program without the ``context_tokens`` attribute (an
older commit), a family without the count.
"""

import json
import os

from benchmark.lib import spans as S
from benchmark.lib.model import family


def _attr(spans, name: str, attr: str) -> list:
    return [s[3][attr] for s in S.select(spans, [name]) if attr in s[3]]


def read(observations: dict, args: dict):
    with open(os.path.join(S.CHECKOUT, args["config"])) as f:
        config = json.load(f)
    fam = family(config, args["config"])
    trace = S.load()
    context = _attr(trace["spans"], "serve/commit", "context_tokens")
    active = _attr(trace["spans"], "serve/decode_step", "active")
    if not context or not active:
        return None
    if args["what"] == "mfu":
        peak = observations.get("device.peak_flops_per_s")
        window_s = observations.get("serve.window_s")
        if not peak or not window_s or not hasattr(fam, "serve_flops"):
            return None
        prompts = _attr(trace["spans"], "serve/prefill", "prompt_len")
        flops = fam.serve_flops(
            config, sum(prompts), sum(active), sum(context),
            sum(p * (p + 1) / 2 for p in prompts),
        )
        return 100.0 * flops / (window_s * peak)
    if args["what"] != "decode_hbm":
        raise ValueError(f"serve_roofline: unknown share {args['what']!r}")
    found = S.per_execution(trace["modules"], args["module"])
    if found is None or not hasattr(fam, "decode_bytes_per_step"):
        return None
    import jax

    with open(os.path.join(S.CHECKOUT, "benchmark", "lib", "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    ns, executions = found
    needed = fam.decode_bytes_per_step(
        config, sum(active) / len(active), sum(context) / len(context))
    return 100.0 * needed / (
        ns / executions / 1e9 * peaks[kind]["hbm_bytes_per_s"])
