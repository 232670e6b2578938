"""A configuration file ``bench/configs/<name>.json`` -> the program's
``ModelConfig``, from the published keys alone, by the configuration's
model family (``bench/family.py``)."""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from bench import family

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config_file(name: str) -> Dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_model_config(name: str) -> Tuple[Dict, object]:
    c = load_config_file(name)
    return c, family.load(c).model_config(name, c)
