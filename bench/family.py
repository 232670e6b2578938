"""The one resolver from a configuration to the code of its model family
(the contract is in ``bench/families/__init__.py``)."""
from __future__ import annotations

import importlib
import os
from types import ModuleType
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
# what every family module exports (bench/families/__init__.py)
EXPORTS = ("model_config", "Forward", "init_std", "forward_flops",
           "decode_step_least", "TINY")


def load(c: Dict) -> ModuleType:
    """``bench.families.<c["program"]["family"]>``.  There is no default:
    a configuration that names no family, or one with no module or
    without all of :data:`EXPORTS`, is an error that names the file looked
    for."""
    name = c.get("program", {}).get("family")
    path = os.path.join(HERE, "families", f"{name}.py")
    if not isinstance(name, str) or not name.isidentifier():
        raise LookupError(f"configuration {c.get('source')!r} names no model "
                          f"family in program.family ({name!r}); a family is "
                          f"a module bench/families/<family>.py")
    try:
        mod = importlib.import_module(f"bench.families.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.families.{name}":
            raise
        raise LookupError(f"unknown model family {name!r}: no {path}") from e
    missing = [k for k in EXPORTS if not hasattr(mod, k)]
    if missing:
        raise LookupError(f"model family {name!r} ({path}) lacks {missing}")
    return mod
