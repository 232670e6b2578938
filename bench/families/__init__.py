"""Model families: everything of the harness that depends on a model's
architecture, one module per family.

A configuration file ``bench/configs/<name>.json`` names its family in
``program.family``; :func:`bench.family.load` imports
``bench.families.<family>`` and nothing else picks a family.  A family
module exports:

``model_config(name, c)``
    The program's ``ModelConfig`` from the configuration's dict ``c``
    (published keys), refusing keys it does not implement.
``Forward(c, mode)``
    The plain float32 decoder forward, written from the published
    equations and importing nothing of the program: ``logits(params,
    tokens)`` maps ``(B, S)`` ids to ``(B, S, V)`` float32 logits.  It reads
    the weight tree of :mod:`bench.weights` by leaf name, and multiplies
    through :func:`bench.reference.mm` with ``mode``, so that the control
    (``mode="fp8"``) rounds every matrix product of every family.
    ``bench/reference.py`` adds what no family changes: logprobs, the row
    loop, the clipped loss and AdamW.
``init_std(leaf_name, init_range)``
    ``(mean, std)`` of the seeded weights of a leaf of the program's tree.
``forward_flops(c, tokens, ctx_sum)``
    FLOPs of a forward pass over ``tokens`` tokens whose attention reads
    ``ctx_sum`` keys in all; ``bench/flops.py`` builds the update's and the
    window's counts on it.
``decode_step_least(c, contexts, peak)``
    Least seconds of one decode step over active slots with these context
    lengths on a chip of these peaks (``bench/peaks.py``).
``TINY``
    Published keys set to a small size, for the harness's CPU tests
    (``bench/tests/tiny.py``).

A new configuration adds only files:

* ``bench/configs/<name>.json``: the published keys, ``program.family``,
  and what was assumed or reduced;
* ``bench/families/<family>.py``, if its family is new;
* ``bench/limits/<cell>.json``: the limits of ``correct`` for each cell;
* ``bench/traffic/<mix>.json``, if its traffic mix is new;
* and its ``workloads`` entry in ``BENCHMARK.json``.
"""
