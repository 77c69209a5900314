"""The traffic generator: deterministic per seed, inside its clips, and the
same work for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import generator

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted((BENCH / "traffic").glob("*.json"))


def _mix(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = _mix(path)
    a = generator.make_requests(mix, 2 ** 31 + 17, 30, 1000)
    b = generator.make_requests(mix, 2 ** 31 + 17, 30, 1000)
    assert [(r.due, r.max_tokens) for r in a] == \
        [(r.due, r.max_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = generator.make_requests(mix, 2 ** 31 + 18, 30, 1000)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_within_clips_and_vocab(path):
    mix = _mix(path)
    reqs = generator.make_requests(mix, 5, 30, 1000)
    out = mix["output"]
    assert all(out["min"] <= r.max_tokens <= out["max"] for r in reqs)
    longest = generator.longest_prompt(mix)
    shortest = (mix["prefix"]["tokens"] + mix["suffix"]["min"]
                if "prefix" in mix else mix["prompt"]["min"])
    for r in reqs:
        assert shortest <= len(r.prompt) <= longest
        assert r.prompt.dtype == np.int32
        assert 1 <= r.prompt.min() and r.prompt.max() < 1000


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_work(path):
    """Seeds reorder one multiset of sizes and gaps; they never change it."""
    mix = _mix(path)
    a = generator.make_requests(mix, 1, 30, 1000)
    b = generator.make_requests(mix, 99, 30, 1000)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    if mix["arrivals"] == "poisson":
        def gaps(rs):   # the first request is due half a gap in
            due = np.array([r.due for r in rs])
            return np.sort(np.concatenate([[2 * due[0]], np.diff(due)]))
        assert np.allclose(gaps(a), gaps(b))


def test_a_part_of_the_requests_is_the_same_work_for_every_seed():
    """Each block of consecutive requests takes one size from each band of
    the sorted sizes, so a window that serves only the first few blocks of
    a backlog sees the same work on every seed."""
    mix = _mix(BENCH / "traffic" / "repo_batch.json")
    n = 6 * generator.BLOCK
    means = [np.mean([len(r.prompt) for r in generator.make_requests(
        mix, seed, 30, 1000)[:n]]) for seed in range(1, 9)]
    whole = np.mean(generator.quantiles(mix["prompt"], mix["requests"]))
    assert max(abs(m - whole) for m in means) < 0.03 * whole
    k = generator.BLOCK
    values = np.arange(3 * k)
    got = generator.spread(np.random.default_rng(0), values)
    assert sorted(got) == list(values)
    for b in range(3):      # a block is a residue class of the sorted ranks
        assert len({int(v) % 3 for v in got[b * k:(b + 1) * k]}) == 1


def test_open_loop_is_due_inside_the_window():
    mix = _mix(BENCH / "traffic" / "chat_shared.json")
    reqs = generator.make_requests(mix, 3, 30, 1000)
    assert len(reqs) == int(mix["rate_rps"] * 30)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 30
    rate = len(reqs) / 30
    assert abs(rate - mix["rate_rps"]) < 0.1


def test_shared_prefixes_follow_zipf():
    mix = _mix(BENCH / "traffic" / "chat_shared.json")
    reqs = generator.make_requests(mix, 4, 30, 1000)
    k = mix["prefix"]["tokens"]
    heads = {}
    for r in reqs:
        heads.setdefault(r.prompt[:k].tobytes(), 0)
        heads[r.prompt[:k].tobytes()] += 1
    assert sorted(heads.values(), reverse=True) == list(
        generator.zipf_counts(mix["prefix"]["count"], 1.0, len(reqs)))
    unique = generator.make_requests(
        _mix(BENCH / "traffic" / "chat_unique.json"), 4, 30, 1000)
    assert len({r.prompt[:k].tobytes() for r in unique}) == len(unique)


def test_backlog_is_queued_before_the_window():
    mix = _mix(BENCH / "traffic" / "repo_batch.json")
    reqs = generator.make_requests(mix, 3, 30, 1000)
    assert len(reqs) == mix["requests"]
    assert all(r.due == 0.0 for r in reqs)
