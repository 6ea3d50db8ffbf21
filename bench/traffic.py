"""Seeded open-loop traffic from a mix's parameter file.

A mix file (`bench/traffic/<name>.json`) states the arrival rate, the
length distributions of prompts and outputs, and optionally shared
prefixes (system prompts chosen by Zipf popularity), topics (each topic's
tokens drawn from its own slice of the vocabulary), and how the server is
brought to steady state before the window: slots filled
(`fill_slots_at_setup`), a queue behind them (`backlog_at_setup`), or the
mix itself offered for `preroll_s` seconds. One general generator reads
every mix.

Every seed gets the same multiset of sizes and of inter-arrival gaps:
lengths are the stratified quantiles of their distribution, gaps the
stratified quantiles of the exponential distribution scaled so that the
requests due in the window span it exactly, and prefix groups are dealt
in proportion to their popularity. The seed only permutes them and draws
the token ids, so two seeds offer the same work in another order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_STREAMS = {"gaps": 1, "prompt": 2, "output": 3, "groups": 4, "tokens": 5,
            "prefix": 6, "fill": 7}


@dataclass(frozen=True)
class Request:
    due_s: float          # offset from the window's start
    prompt: tuple         # token ids
    max_tokens: int
    group: int            # shared-prefix group or topic, -1 for none
    prefix_len: int       # tokens shared with the group's other prompts


def _rng(seed: int, stream: str, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, _STREAMS[stream], salt]))


def strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n stratified lengths of a lognormal with the given median and sigma,
    clipped to [min, max]."""
    inv = NormalDist().inv_cdf
    z = np.array([inv(u) for u in strata(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_offsets(rate: float, seconds: float, seed: int,
                    salt: int = 0) -> np.ndarray:
    """round(rate * seconds) Poisson arrival offsets in [0, seconds): the
    exponential gaps' stratified quantiles in seeded order, scaled so that
    they sum to `seconds`."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-strata(n))
    gaps = _rng(seed, "gaps", salt).permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def zipf_counts(n: int, groups: int, s: float) -> np.ndarray:
    """n requests dealt to `groups` groups in proportion to 1/(rank+1)^s
    (largest remainder)."""
    p = 1.0 / np.arange(1, groups + 1) ** s
    p /= p.sum()
    raw = n * p
    counts = np.floor(raw).astype(np.int64)
    for g in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[g] += 1
    return counts


class Mix:
    """One traffic mix, read from its parameter file."""

    def __init__(self, spec: dict, vocab: int):
        self.spec = spec
        self.vocab = int(vocab)
        self.rate = float(spec["rate_per_s"])
        self.prefix = spec.get("shared_prefix")
        self.topics = spec.get("topics")
        self.fill_slots = bool(spec.get("fill_slots_at_setup", False))
        self.preroll_s = float(spec.get("preroll_s", 0.0))

    def max_context(self) -> int:
        """Longest prompt plus output this mix can send."""
        pre = self.prefix["max"] if self.prefix else 0
        return pre + self.spec["prompt"]["max"] + self.spec["output"]["max"]

    def _prefixes(self, seed: int) -> list:
        if not self.prefix:
            return []
        g = self.prefix["groups"]
        lens = np.rint(np.linspace(self.prefix["min"], self.prefix["max"],
                                   g)).astype(np.int64)
        rng = _rng(seed, "prefix")
        return [tuple(rng.integers(0, self.vocab, int(n)).tolist())
                for n in lens]

    def _groups(self, n: int, seed: int, salt: int) -> np.ndarray:
        grp = self.prefix or self.topics
        if not grp:
            return -np.ones(n, np.int64)
        counts = zipf_counts(n, grp["groups"], grp["zipf_s"])
        ids = np.repeat(np.arange(grp["groups"]), counts)
        return _rng(seed, "groups", salt).permutation(ids)

    def _tokens(self, rng, n: int, group: int) -> list:
        if self.topics and group >= 0:
            width = self.vocab // self.topics["groups"]
            return (group * width + rng.integers(0, width, n)).tolist()
        return rng.integers(0, self.vocab, n).tolist()

    def _build(self, n: int, seed: int, streams: tuple, salt: int) -> list:
        plen = _rng(seed, streams[0], salt).permutation(
            lengths(self.spec["prompt"], n))
        olen = _rng(seed, streams[1], salt).permutation(
            lengths(self.spec["output"], n))
        groups = self._groups(n, seed, salt)
        prefixes = self._prefixes(seed)
        rng = _rng(seed, streams[2], salt)
        out = []
        for i in range(n):
            g = int(groups[i])
            pre = prefixes[g] if prefixes and g >= 0 else ()
            body = tuple(self._tokens(rng, int(plen[i]), g))
            out.append((pre + body, int(olen[i]), g, len(pre)))
        return out

    def window(self, seconds: float, seed: int,
               salt: int = 0) -> list[Request]:
        """The requests due in a window of `seconds`, in due order. Another
        `salt` draws other requests over the same system prompts (the
        pre-roll's)."""
        due = arrival_offsets(self.rate, seconds, seed, salt)
        reqs = self._build(len(due), seed, ("prompt", "output", "tokens"),
                           salt)
        return [Request(float(t), p, o, g, k)
                for t, (p, o, g, k) in zip(due, reqs)]

    def stored_prefixes(self, seed: int) -> list[Request]:
        """One request per shared prefix whose prompt is that prefix alone.
        The server stores every prompt it prefills, so once these are
        served each later request over a prefix resumes where it ends, as
        in a server that has run this traffic for a while. Empty for a mix
        without shared prefixes."""
        return [Request(0.0, p, 4, g, len(p))
                for g, p in enumerate(self._prefixes(seed))]

    def backlog(self, seed: int) -> list[Request]:
        """Requests queued behind the full slots when the window opens
        (`backlog_at_setup`), so that a cell above the knee starts with the
        queue it would have built: every slot that frees is taken again at
        once."""
        n = int(self.spec.get("backlog_at_setup", 0))
        return [Request(0.0, p, o, g, k) for p, o, g, k in self._build(
            n, seed, ("fill", "fill", "fill"), 1)] if n else []

    def fill(self, slots: int, seed: int) -> list[Request]:
        """Requests that fill every decode slot before the window opens (a
        saturated cell starts at steady state). Each keeps a staggered share
        of its drawn output length, so that slots free up across the window
        as they would in a queue that has run for a while."""
        if not self.fill_slots:
            return []
        reqs = self._build(slots, seed, ("fill", "fill", "fill"), 0)
        keep = _rng(seed, "fill").permutation(strata(slots))
        return [Request(0.0, p, max(int(math.ceil(o * k)), 2), g, n)
                for (p, o, g, n), k in zip(reqs, keep)]
