#!/usr/bin/env python3
"""Profiler trace -> the device's idle time by the program span open over it.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, on the trace's own
clock, as ``trace_reduce`` does, and adds the program's own spans
(``repro.core.trace``): host events whose names start with ``engine.``,
``sched.`` or ``trainer.``, nested by containment on their thread.

* the stretch, the device's busy intervals and ``window_s``/``busy_s`` are
  ``trace_reduce.reduce``'s, so a reduction can be matched to the run's;
* each idle gap of the device inside the stretch is split by overlap, not
  by its midpoint: each part goes to the innermost program span open over
  it, else to ``harness:<span>`` when only a harness span is open, else to
  ``none``.  The parts sum to the stretch's idle time (``idle_by_span``);
* the program spans that lie inside the stretch are summed by name (count,
  seconds, self seconds: less their program children), and each
  ``engine.step`` gives its host time less its ``engine.step.wait``.

A trace with no program span (a program without them) reduces to
``spans == {}``; the metric readers then report nothing.

    python3 bench/span_reduce.py [<trace dir>]   # prints the reduction
"""
from __future__ import annotations

import functools
import json
import math
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as T  # noqa: E402

PREFIXES = ("engine.", "sched.", "trainer.")
# where bench/run.py's Tracer writes the trace of a run
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench_out", "trace")


def load(path: str) -> Dict:
    """``trace_reduce.load``'s planes plus ``"program"``: the program's
    spans as (name, start, end, thread) in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev: Dict[str, List] = {}
    host: List = []
    prog: List = []
    for p, plane in enumerate(pd.planes):
        if re.match(r"/device:TPU:\d+$", plane.name) and not dev:
            for line in plane.lines:
                dev[line.name] = T._events(line)
        elif plane.name.startswith("/host"):
            for t, line in enumerate(plane.lines):
                for e in T._events(line):
                    if e[0] in T.SPANS:
                        host.append(e)
                    elif e[0].startswith(PREFIXES):
                        prog.append((*e, (p, t)))
    return {"device": dev, "host": host, "program": prog}


def _nest(prog: List[Tuple]) -> List[int]:
    """Parent index of each span (-1 at the top), by containment on its
    thread."""
    parent = [-1] * len(prog)
    stacks: Dict = {}
    for i in sorted(range(len(prog)),
                    key=lambda i: (prog[i][1], -prog[i][2])):
        _, a, b, th = prog[i]
        st = stacks.setdefault(th, [])
        while st and prog[st[-1]][2] < b:
            st.pop()
        parent[i] = st[-1] if st else -1
        st.append(i)
    return parent


def _idle_by_span(gaps, prog, depth, host) -> Dict[str, float]:
    """ns of the gaps under each label, swept over every span edge."""
    pts = []
    for i, (_, a, b, _th) in enumerate(prog):
        pts += [(a, 1, 0, i), (b, 0, 0, i)]
    for i, (_, a, b) in enumerate(host):
        pts += [(a, 1, 1, i), (b, 0, 1, i)]
    for a, b in gaps:
        pts += [(a, 1, 2, 0), (b, 0, 2, 0)]
    pts.sort(key=lambda p: (p[0], p[1]))          # ends first at a tie
    open_p, open_h = set(), set()
    idle, prev = False, None
    out: Dict[str, float] = {}
    for t, start, kind, i in pts:
        if idle and t > prev:
            if open_p:
                label = prog[max(open_p, key=lambda j: (depth[j],
                                                        prog[j][1]))][0]
            elif open_h:
                label = "harness:" + host[max(open_h,
                                              key=lambda j: host[j][1])][0]
            else:
                label = "none"
            out[label] = out.get(label, 0.0) + (t - prev)
        if kind == 2:
            idle = bool(start)
        else:
            s = open_p if kind == 0 else open_h
            (s.add if start else s.discard)(i)
        prev = t
    return out


def reduce(tr: Dict) -> Dict:
    base = T.reduce(tr)
    host = sorted(tr["host"], key=lambda e: e[1])
    w0, w1 = host[0][1], max(e[2] for e in host)
    dev = tr["device"]
    mods = [e for n in T.MODULE_LINES for e in dev.get(n, [])]
    ops = [e for n in T.OP_LINES for e in dev.get(n, [])]
    busy = T._union([(max(a, w0), min(b, w1)) for _, a, b in (mods or ops)
                     if b > w0 and a < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    prog = tr["program"]
    parent = _nest(prog)
    depth = [0] * len(prog)
    for i in sorted(range(len(prog)), key=lambda i: prog[i][1]):
        depth[i] = depth[parent[i]] + 1 if parent[i] >= 0 else 0
    ns = 1e-9
    idle = _idle_by_span(gaps, prog, depth, host)
    inside = [w0 <= a and b <= w1 for _, a, b, _th in prog]
    own = [b - a for _, a, b, _th in prog]
    wait = [0.0] * len(prog)
    for i, (name, a, b, _th) in enumerate(prog):
        if parent[i] >= 0:
            own[parent[i]] -= b - a
            if name == "engine.step.wait":
                wait[parent[i]] += b - a
    spans: Dict[str, List[float]] = {}
    step_host: List[float] = []
    for i, (name, a, b, _th) in enumerate(prog):
        if not inside[i]:
            continue
        s = spans.setdefault(name, [0, 0.0, 0.0])
        s[0] += 1
        s[1] += (b - a) * ns
        s[2] += own[i] * ns
        if name == "engine.step":
            step_host.append((b - a - wait[i]) * ns)
    harness: Dict[str, List[float]] = {}
    for name, a, b in host:
        h = harness.setdefault(name, [0, 0.0])
        h[0] += 1
        h[1] += (b - a) * ns
    return {
        "window_s": base["window_s"], "busy_s": base["busy_s"],
        "idle_s": sum(idle.values()) * ns,
        "idle_by_span": sorted(([k, v * ns] for k, v in idle.items()),
                               key=lambda kv: -kv[1]),
        "spans": spans, "engine_step_host_s": step_host,
        "harness": harness,
    }


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime: float) -> Dict:
    return reduce(load(path))


def for_run(ctx) -> Optional[Dict]:
    """The reduction of the traced run behind ``ctx`` (the metric readers'
    context), or None: no trace, no program span in it, or no trace file
    in ``TRACE_DIR`` that matches the run's own reduction."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    try:
        path = T.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    r = _reduce_file(path, os.path.getmtime(path))
    if not (math.isclose(r["window_s"], tr["window_s"], rel_tol=1e-12)
            and math.isclose(r["busy_s"], tr["busy_s"], rel_tol=1e-12)):
        return None
    return r if r["spans"] else None


def idle_share(ctx, prefix: str) -> Optional[float]:
    """% of the traced stretch in which the device idled under a program
    span whose name starts with ``prefix``."""
    r = for_run(ctx)
    if r is None:
        return None
    idle = sum(v for k, v in r["idle_by_span"] if k.startswith(prefix))
    return 100.0 * idle / r["window_s"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    d = argv[0] if argv else TRACE_DIR
    r = reduce(load(T.find_xplane(d)))
    shares = {}
    for k, v in r["idle_by_span"]:
        g = k.split(".")[0] if "." in k and ":" not in k else k.split(":")[0]
        shares[g] = shares.get(g, 0.0) + 100.0 * v / r["window_s"]
    r["idle_share_by_layer"] = shares
    r["device_idle_share"] = 100.0 * (1 - r["busy_s"] / r["window_s"])
    del r["engine_step_host_s"]
    print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
