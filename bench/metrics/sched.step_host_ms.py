"""sched.step_host_ms: the scheduler's self time (``sched.*`` spans less
the engine and trainer spans inside them) in the traced stretch, per
``engine.step`` span there."""
from bench import span_reduce


def read(ctx):
    r = span_reduce.for_run(ctx)
    steps = r["spans"].get("engine.step", [0])[0] if r else 0
    if not steps:
        return None
    own = sum(s[2] for k, s in r["spans"].items() if k.startswith("sched."))
    return 1e3 * own / steps
