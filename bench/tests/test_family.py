"""The harness reaches a model family's code through the family's name
alone (``bench/family.py``)."""
import collections
import glob
import os
import sys
import types

import pytest

from bench import family
from bench import run as R
from bench.families import qwen
from bench.model_config import load_config_file
from bench.tests.tiny import tiny_cell

WORKLOAD = "qwen3-0.6b.grpo-longtail"
CONFIGS = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(R.BENCH, "configs", "*.json")))


def spy_family(calls):
    """The qwen family, with a counter on each of its entry points."""
    mod = types.ModuleType("bench.families.spy")
    mod.__dict__.update({k: v for k, v in vars(qwen).items()
                         if not k.startswith("__")})

    def counted(name, fn):
        def call(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return call

    for name in ("model_config", "init_std", "forward_flops",
                 "decode_step_least"):
        setattr(mod, name, counted(name, getattr(qwen, name)))

    class Forward(qwen.Forward):
        def logits(self, params, tokens):
            calls["forward"] += 1
            return super().logits(params, tokens)

    mod.Forward = Forward
    return mod


def test_harness_reaches_the_family_by_its_name(monkeypatch):
    calls = collections.Counter()
    monkeypatch.setitem(sys.modules, "bench.families.spy", spy_family(calls))
    base = tiny_cell()
    c = dict(base["config"],
             program=dict(base["config"]["program"], family="spy"))
    spy = dict(base, config=c,
               mcfg=family.load(c).model_config("qwen3-0.6b", c))
    assert spy["mcfg"] == base["mcfg"]
    numbers = {}
    for name, cell in (("qwen", base), ("spy", spy)):
        before = dict(calls)
        res, verdict = R.run(WORKLOAD, 2 ** 33 + 3, 2.0, False,
                             require_tpu=False, cell_override=cell,
                             persistent_cache=False)
        assert res["correct"], verdict
        numbers[name] = {k: v["value"] for k, v in verdict.items()}
        if name == "qwen":
            assert dict(calls) == before            # the spy is not reached
    # weights, the reference's forward (logprobs and the followed
    # updates) and the window's counts all came through the spy
    for k in ("model_config", "init_std", "forward", "forward_flops"):
        assert calls[k] > 0, (k, calls)
    assert numbers["spy"] == numbers["qwen"]        # bit for bit


@pytest.mark.parametrize("name", ["no_such_family", None])
def test_a_family_that_is_not_there_is_refused(name):
    c = load_config_file("qwen3-0.6b")
    prog = {k: v for k, v in c["program"].items() if k != "family"}
    if name:
        prog["family"] = name
    with pytest.raises(LookupError, match="bench/families/"):
        family.load(dict(c, program=prog))


def test_a_family_without_the_whole_contract_is_refused(monkeypatch):
    mod = types.ModuleType("bench.families.partial")
    mod.model_config = qwen.model_config
    monkeypatch.setitem(sys.modules, "bench.families.partial", mod)
    c = load_config_file("qwen3-0.6b")
    with pytest.raises(LookupError, match="lacks"):
        family.load(dict(c, program=dict(c["program"], family="partial")))


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_names_its_family(config):
    c = load_config_file(config)
    fam = family.load(c)
    mcfg = fam.model_config(config, c)
    assert mcfg.name == config
    assert fam.forward_flops(c, 1, 1) > 0
    assert fam.model_config(config, dict(c, **fam.TINY)).name == config
