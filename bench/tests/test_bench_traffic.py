"""The seeded open-loop traffic generator (bench/traffic.py)."""
import json
from collections import Counter

import numpy as np
import pytest

from bench import traffic
from bench.cells import ROOT

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def mix(name, vocab=151936):
    return traffic.Mix(json.loads(
        (ROOT / "bench" / "traffic" / f"{name}.json").read_text()), vocab)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = mix(name).window(20.0, 2**31 + 7)
    b = mix(name).window(20.0, 2**31 + 7)
    assert a == b
    assert mix(name).fill(8, 5) == mix(name).fill(8, 5)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_and_gaps(name):
    """Two seeds offer the same work in another order."""
    m = mix(name)
    a, b = m.window(20.0, 1), m.window(20.0, 2)
    assert len(a) == len(b) == round(m.rate * 20.0)
    for size in (lambda r: len(r.prompt) - r.prefix_len,
                 lambda r: r.prefix_len, lambda r: r.max_tokens):
        assert sorted(map(size, a)) == sorted(map(size, b))
    ga = np.diff([r.due_s for r in a] + [20.0])
    gb = np.diff([r.due_s for r in b] + [20.0])
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9)
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_arrivals_as_stated(name):
    m = mix(name)
    spec = m.spec
    reqs = m.window(30.0, 3)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    body = [len(r.prompt) - r.prefix_len for r in reqs]
    out = [r.max_tokens for r in reqs]
    for vals, s in ((body, spec["prompt"]), (out, spec["output"])):
        assert min(vals) >= s["min"] and max(vals) <= s["max"]
        assert abs(np.median(vals) - s["median"]) <= 0.05 * s["median"] + 1
    assert all(len(r.prompt) + r.max_tokens <= m.max_context() for r in reqs)


def test_shared_prefixes_follow_zipf():
    m = mix("chat-prefix")
    reqs = m.window(100.0, 9)
    pre = m.spec["shared_prefix"]
    counts = Counter(r.group for r in reqs)
    want = traffic.zipf_counts(len(reqs), pre["groups"], pre["zipf_s"])
    assert [counts.get(g, 0) for g in range(pre["groups"])] == list(want)
    by_group = {}
    for r in reqs:
        assert pre["min"] <= r.prefix_len <= pre["max"]
        p = r.prompt[:r.prefix_len]
        assert by_group.setdefault(r.group, p) == p
    shared = sum(r.prefix_len for r in reqs) / sum(len(r.prompt)
                                                  for r in reqs)
    assert shared > 0.8


def test_topics_draw_from_their_slice():
    m = traffic.Mix({"rate_per_s": 5.0,
                     "prompt": {"median": 64, "sigma": 0.3, "min": 32,
                                "max": 128},
                     "output": {"median": 8, "sigma": 0.3, "min": 4,
                                "max": 16},
                     "topics": {"groups": 8, "zipf_s": 1.2}}, vocab=800)
    reqs = m.window(40.0, 4)
    counts = Counter(r.group for r in reqs)
    assert [counts[g] for g in range(8)] == list(
        traffic.zipf_counts(len(reqs), 8, 1.2))
    for r in reqs:
        assert all(r.group * 100 <= t < (r.group + 1) * 100 for t in r.prompt)


def test_fill_staggers_outputs():
    m = mix("reasoning-saturated")
    fill = m.fill(48, 11)
    assert len(fill) == 48 and all(r.due_s == 0.0 for r in fill)
    assert len({r.max_tokens for r in fill}) > 40
    assert max(r.max_tokens for r in fill) <= m.spec["output"]["max"]


def test_stored_prefixes_are_the_windows_prefixes():
    m = mix("chat-prefix")
    stored = m.stored_prefixes(2**31 + 5)
    window = m.window(60.0, 2**31 + 5)
    assert [r.group for r in stored] == list(
        range(m.spec["shared_prefix"]["groups"]))
    by_group = {r.group: r.prompt for r in stored}
    for r in window:
        assert r.prompt[:r.prefix_len] == by_group[r.group]
        assert len(r.prompt) > r.prefix_len
    assert all(r.prefix_len == len(r.prompt) for r in stored)
    assert mix("reasoning-saturated").stored_prefixes(1) == []


def test_backlog_and_preroll_as_stated():
    m = mix("reasoning-saturated")
    queued = m.backlog(2**31 + 9)
    assert len(queued) == m.spec["backlog_at_setup"]
    out = m.spec["output"]
    assert all(out["min"] <= r.max_tokens <= out["max"] and r.due_s == 0.0
               for r in queued)
    fill = {r.prompt for r in m.fill(28, 2**31 + 9)}
    assert not {r.prompt for r in queued} & fill
    chat = mix("chat-prefix")
    assert chat.backlog(1) == [] and chat.fill(8, 1) == []
    pre = chat.window(chat.preroll_s, 3, salt=3)
    assert len(pre) == round(chat.rate * chat.preroll_s)
    window = {r.prompt for r in chat.window(30.0, 3)}
    assert not {r.prompt for r in pre} & window
