"""The stratified generator: every seed offers the same work."""

import json
import os

import pytest

from harness import spec, traffic

SEEDS = [0, 7, 2**31 + 12345, 3_000_000_011]
MIXES = ["batchgen_closed", "sharedprefix_open"]


def stream(name, seed, slots=16, vocab=32768):
    return traffic.Stream(spec.load_traffic(name), seed, slots, vocab)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_asks_for_the_same_tokens(name):
    totals = [[stream(name, s).totals(k) for k in range(3)] for s in SEEDS]
    for other in totals[1:]:
        for a, b in zip(totals[0], other):
            assert a["requests"] == b["requests"]
            assert a["prompt_tokens"] == b["prompt_tokens"]
            assert a["output_tokens"] == b["output_tokens"]


@pytest.mark.parametrize("name", MIXES)
def test_seed_permutes_the_multiset_and_keeps_it(name):
    a, b = stream(name, 1), stream(name, 2)
    la = [(len(r.tokens), r.max_new) for r in a.block(0)]
    lb = [(len(r.tokens), r.max_new) for r in b.block(0)]
    assert sorted(x for x, _ in la) == sorted(x for x, _ in lb)
    assert sorted(y for _, y in la) == sorted(y for _, y in lb)
    assert la != lb
    assert [r.tokens for r in a.block(1)] != [r.tokens for r in b.block(1)]


def test_same_seed_same_requests():
    a, b = stream("batchgen_closed", 5), stream("batchgen_closed", 5)
    assert [r.tokens for r in a.block(0)] == [r.tokens for r in b.block(0)]


def test_open_loop_gaps_span_the_same_time():
    spans = []
    for seed in SEEDS:
        s = stream("sharedprefix_open", seed)
        due = [r.due for k in range(3) for r in s.block(k)]
        assert due == sorted(due)
        spans.append(due[-1])
        # a block of n requests spans exactly n / rate seconds
        last_of_block_0 = s.block_size - 1
        assert due[last_of_block_0] == pytest.approx(
            s.block_size / s.traffic["rate_per_s"])
    assert max(spans) - min(spans) < 1e-9


def test_prefix_skew_is_dealt_not_drawn():
    counts = traffic.zipf_counts(8, 1.0, 32)
    assert sum(counts) == 32 and counts == sorted(counts, reverse=True)
    assert counts[0] == 12 and counts[-1] >= 1
    for seed in SEEDS:
        s = stream("sharedprefix_open", seed)
        used = sorted(r.prefix for r in s.block(0))
        assert used == sorted(k for k, c in enumerate(counts)
                              for _ in range(c))


@pytest.mark.parametrize("name", MIXES)
def test_shapes_fit_the_engines_program_caches(name):
    s = stream(name, 3)
    limit = (traffic.MAX_SUFFIX_SHAPES if s.prefix
             else traffic.MAX_WHOLE_PROMPT_SHAPES)
    assert len(s.shapes) <= limit
    own = {len(r.tokens) - (s.prefix["tokens"] if s.prefix else 0)
           for k in range(2) for r in s.block(k)}
    assert own <= set(s.shapes)
    warmed = {len(r.tokens) - (s.prefix["tokens"] if s.prefix else 0)
              for r in s.warmup()}
    assert warmed == set(s.shapes)
    if s.prefix:
        assert {r.prefix for r in s.warmup()} == set(range(s.prefix["count"]))


@pytest.mark.parametrize("name", MIXES)
def test_first_own_tokens_never_repeat(name):
    s = stream(name, 11)
    skip = s.prefix["tokens"] if s.prefix else 0
    reqs = s.warmup() + s.lead_in() + [r for k in range(4) for r in s.block(k)]
    firsts = [r.tokens[skip] for r in reqs]
    assert len(set(firsts)) == len(firsts)
    if s.prefix:
        heads = [p[0] for p in s.prefixes]
        assert len(set(heads + firsts)) == len(heads) + len(firsts)


def test_too_many_shapes_is_refused():
    mix = dict(spec.load_traffic("batchgen_closed"))
    mix["prompt"] = dict(mix["prompt"], grid=None)
    with pytest.raises(ValueError, match="distinct prompt shapes"):
        traffic.Stream(mix, 0, 16, 32768)


def test_quantiles_keep_the_stated_spread():
    mix = spec.load_traffic("batchgen_closed")
    s = stream("batchgen_closed", 0)
    assert min(s.own_lens) == 128 and max(s.own_lens) == 1024
    assert min(s.out_lens) == 64 and max(s.out_lens) == 384
    ordered = sorted(s.own_lens)
    assert ordered[len(ordered) // 2] in (384, 448)
    assert 140 <= sorted(s.out_lens)[len(s.out_lens) // 2] <= 180
    assert mix["eos_tokens"] == []          # no stop token


def test_traffic_files_state_why():
    folder = os.path.join(spec.BENCH_DIR, "traffic")
    for name in os.listdir(folder):
        with open(os.path.join(folder, name)) as fh:
            assert len(json.load(fh)["why"]) > 20
