"""engine.device_idle_share: share of the traced stretch in which the
device idled while the innermost program span open on the host was an
``engine.*`` span (``bench/span_reduce.py``: idle gaps split by overlap)."""
from bench import span_reduce


def read(ctx):
    return span_reduce.idle_share(ctx, "engine.")
