"""The one place that knows how a configuration becomes code: the lookup from
a configuration file's ``"family"`` to its module under
``benchmark/lib/families/``.

A family module holds everything the benchmark knows about one kind of
model, under these names, and the jobs ask for nothing else:

- ``build_model(config)``: the program's model at the configuration's sizes.
- ``init_params(model, seed, seq_len)``: the model's variables, made on the
  device in one jitted call from the seed; ``["params"]`` of them is the tree
  the reference reads.
- ``logits_at(params, ids, positions)`` and ``causal_lm_loss(params, ids)``:
  the plain float32 reference, which imports nothing of the program.  A
  family whose weights do not fit in float32 computes them in blocks behind
  the same names.
- ``train_flops_per_token(config, seq_len)``: operations the algorithm needs,
  from the configuration's shapes; a kernel's operation and byte counts sit
  beside it.

A configuration's key names are its family's own business.  The one key every
family's configuration carries is ``vocab_size``: both jobs draw tokens
from it.
"""

from __future__ import annotations

import importlib

NAMES = ("build_model", "init_params", "logits_at", "causal_lm_loss",
         "train_flops_per_token")


def family(config: dict, where: str | None = None):
    """The module of ``config["family"]``.  No default: a configuration
    without the key, or naming a module that does not exist or lacks one of
    ``NAMES``, is an error that names the file (``where``, else the
    configuration's ``name``)."""
    where = where or f"configuration {config.get('name')!r}"
    name = config.get("family")
    if not name:
        raise ValueError(
            f"{where}: no \"family\" key; it names the module "
            f"benchmark/lib/families/<family>.py")
    if "vocab_size" not in config:
        raise ValueError(f"{where}: no \"vocab_size\" key")
    try:
        module = importlib.import_module(f"benchmark.lib.families.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.lib.families.{name}":
            raise
        raise ValueError(
            f"{where}: unknown family {name!r}: no "
            f"benchmark/lib/families/{name}.py") from None
    missing = [n for n in NAMES if not hasattr(module, n)]
    if missing:
        raise ValueError(f"{where}: family {name!r} lacks {missing}")
    return module
