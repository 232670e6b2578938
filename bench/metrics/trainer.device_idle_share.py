"""trainer.device_idle_share: share of the traced stretch in which the
device idled while the innermost program span open on the host was a
``trainer.*`` span (``bench/span_reduce.py``)."""
from bench import span_reduce


def read(ctx):
    return span_reduce.idle_share(ctx, "trainer.")
