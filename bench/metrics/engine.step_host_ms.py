"""engine.step_host_ms: mean, over the program's ``engine.step`` spans in
the traced stretch, of the span less its ``engine.step.wait`` child: the
host's own time in a decode step (preparation, dispatch, bookkeeping), on
the profiler's clock."""
from bench import span_reduce


def read(ctx):
    r = span_reduce.for_run(ctx)
    steps = r["engine_step_host_s"] if r else []
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)
