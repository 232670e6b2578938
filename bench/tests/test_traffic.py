import hashlib
import json

import pytest

from bench.run import prefill_widths
from bench.traffic import Traffic, load_mix, strata_lengths


def groups(seed, n=3):
    t = Traffic(load_mix("grpo-longtail"), seed)
    return [t.next_group() for _ in range(n)], t


def test_same_seed_same_traffic():
    a, _ = groups(2 ** 33 + 5)
    b, _ = groups(2 ** 33 + 5)
    assert [(p, l) for p, _, l in a] == [(p, l) for p, _, l in b]
    assert [[m.prompt_id for m in ms] for _, ms, _ in a] == \
        [[m.prompt_id for m in ms] for _, ms, _ in b]


def by_rank(g):
    """A group's output lengths, in the order of its prompts' ranks by
    length (the order the engine admits them in)."""
    ps, _, ls = g
    order = sorted(range(0, len(ps), 4), key=lambda i: (len(ps[i]), i))
    return [ls[j] for i in order for j in range(i, i + 4)]


def test_seeds_deal_the_same_lengths_by_prompt_rank():
    a, ta = groups(1)
    b, tb = groups(2 ** 33 + 5)
    # the prompts differ, and so does where each length sits in the group
    assert [p for p, _, _ in a] != [p for p, _, _ in b]
    assert [l for _, _, l in a] != [l for _, _, l in b]
    # but every prompt rank gets the same lengths in the same order, up to
    # the cap of its prompt's length; groups differ from each other
    for ga, gb in zip(a, b):
        assert [min(n, 950) for n in by_rank(ga)] == \
            [min(n, 950) for n in by_rank(gb)]
    assert by_rank(a[0]) != by_rank(a[1])
    mix = load_mix("grpo-longtail")
    strata = strata_lengths(32, 280, 0.9)
    for (ps, _, ls) in a + b:
        # every group: the same strata, each capped only by its prompt
        assert len(ls) == 32
        for p, n in zip(ps, ls):
            assert n <= mix["max_total_len"] - 1 - len(p)
        assert sorted(ls)[:20] == strata[:20]


def test_responses_of_a_prompt_share_a_block():
    (g,), t = groups(7, n=1)
    ps, ms, ls = g
    k = t.k
    for j in range(0, 32, k):
        assert len({m.prompt_id for m in ms[j:j + k]}) == 1
        assert len({tuple(p) for p in ps[j:j + k]}) == 1


def test_reward_alternates_by_length():
    (g,), t = groups(3, n=1)
    ps, ms, ls = g
    for j in range(0, 32, t.k):
        lens, metas = ls[j:j + 4], ms[j:j + 4]
        if len(set(lens)) == 4:
            by_len = sorted(zip(lens, metas), key=lambda x: x[0])
            assert [t.reward([0] * n, m) for n, m in by_len] == \
                [1.0, 0.0, 1.0, 0.0]
    assert t.reward([0] * 5, None) == 0.0


def test_strata_are_lognormal_quantiles():
    s = strata_lengths(1000, 280, 0.9)
    assert s == sorted(s)
    assert abs(s[499] - 280) <= 1 and abs(s[500] - 280) <= 1


def test_each_rollout_batch_gets_the_same_lengths_for_every_seed():
    # the engine admits the shortest prompts first (stable on position),
    # 16 slots: the first batch is the 4 shortest prompts' 16 responses
    firsts = []
    for seed in (1, 2, 2 ** 40 + 7):
        (g,), _ = groups(seed, n=1)
        ps, _, ls = g
        order = sorted(range(len(ps)), key=lambda i: (len(ps[i]), i))
        firsts.append(sorted(min(ls[i], 950) for i in order[:16]))
    assert firsts[0] == firsts[1] == firsts[2]


def digest(seed, n=60):
    t = Traffic(load_mix("grpo-longtail"), seed)
    h = hashlib.sha256()
    for _ in range(n):
        p, _, lens = t.next_group()
        h.update(json.dumps([p, lens]).encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [9800000097, 9600000071])
def test_seeds_with_the_longest_prompts_draw_their_groups(seed):
    # each draws a prompt of 39 or more tokens within 16 groups: the mix's
    # prompt_len is what the logic generator's construction can give,
    # 3-5 statements of 6 or 8 tokens between <bos> and <ans>: 20-42
    t = Traffic(load_mix("grpo-longtail"), seed)
    longest = max(len(p) for _ in range(60) for p in t.next_group()[0])
    assert 39 <= longest <= 42


def test_a_seed_draws_what_it_drew_under_the_narrower_range():
    # the digest of these 60 groups under prompt_len [20, 38]
    assert digest(7100000001) == \
        "2b6932114e749639bed5a248a11734944dfa93b9fdf0fd3a4cb679d20bd92745"


@pytest.mark.parametrize("mix", ["grpo-longtail", "grpo-longtail-wide",
                                 "short-answer"])
def test_prefill_widths_of_the_prompt_range(mix):
    # as under prompt_len [20, 38]: warm-up compiles the same prefills
    assert prefill_widths(load_mix(mix)) == [32, 64]
