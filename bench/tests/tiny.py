"""A tiny stand-in for a cell, for runs of the harness on the CPU: the
cell's configuration at its family's small size (``TINY``), and its mix
with few slots and short outputs."""
from bench import family
from bench.model_config import load_config_file
from bench.traffic import load_mix

# Limits of ``correct`` for the tiny stand-in, set like the cell's from its
# own readings (CPU, 6 seeds): largest program readings lp 0.00272, loss
# 0.000275, grad 0.00197, update 0.00235; the float8 control's least
# 0.0265, 0.00066, 0.0106, 0.0061.  The cell's limits, set from chip
# readings at full size, are far above what the tiny model can err by.
TINY_LIMITS = {"lp_gap": 0.009, "loss_gap": 0.0008, "grad_gap": 0.006,
               "update_gap": 0.006}


def tiny_cell(config="qwen3-0.6b", mix="grpo-longtail", limits=TINY_LIMITS,
              **kw):
    c = load_config_file(config)
    c = dict(c, **family.load(c).TINY)
    m = dict(load_mix(mix), slots=4, max_total_len=128,
             output_len={"kind": "lognormal_strata", "median": 20,
                         "sigma": 0.9})
    m.update(kw)
    return {"config": c, "mcfg": family.load(c).model_config(config, c),
            "mix": m, "limits": limits}
