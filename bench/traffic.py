"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Every seed asks for the same work.  Lengths are the quantiles
``(i + 0.5) / n`` of the mix's distributions, and the mix's own
``order_seed`` fixes how they are ordered and paired; the run's seed only
draws the token ids.  So two seeds send the same sizes in the same order,
and what differs between them is the tokens (and with them the routing).

The loop is closed: ``clients`` callers each send their next request when
the previous one ends, taking it from one queue of ``blocks`` blocks of
``block`` requests (each block its own set of sizes).  ``fill: staggered``
starts every client inside a request, at evenly spread points of its
output, so completions spread through the window from its first second.

Prompts are either drawn lengths or (a mix with ``system_prompts``) a
shared system prompt, drawn with Zipf popularity, followed by a user
turn; ``repeat_share`` of the requests ask an earlier request's prompt
again (a regenerated answer).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request the generator asks for."""
    prompt: np.ndarray            # (n,) int32 token ids
    max_new_tokens: int


@dataclasses.dataclass
class Traffic:
    fill: list                    # one Spec per client, sent in set-up
    queue: list                   # the Specs sent, in order, as clients finish
    warm: list = dataclasses.field(default_factory=list)
    # served once in set-up: each shared system prompt, so that the prefix
    # cache starts the window as a running deployment's would


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(n: int, d: dict) -> np.ndarray:
    """n lengths at the quantiles of lognormal(median, sigma), clipped to
    [min, max]."""
    z = np.array([NormalDist().inv_cdf(q) for q in quantiles(n)])
    x = d["median"] * np.exp(d["sigma"] * z)
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of n requests go to each of k ranks under Zipf(s),
    rounded by largest remainder so that they sum to n."""
    w = 1.0 / np.arange(1, k + 1) ** s
    raw = n * w / w.sum()
    out = np.floor(raw).astype(np.int64)
    out[np.argsort(-(raw - out), kind="stable")[: n - out.sum()]] += 1
    return out


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, int(n), dtype=np.int32)


class _Prompts:
    """Makes a set of prompts for a set of answer lengths.  ``order`` (from
    the mix) arranges the sizes, ``rng`` (from the seed) draws the tokens.
    A mix with ``system_prompts`` puts one of them (Zipf popularity, a
    length fixed per rank) before a user turn, and asks ``repeat_share`` of
    its requests again with an earlier prompt of the same system prompt; a
    mix without them draws ``prompt`` lengths."""

    def __init__(self, mix: dict, order, rng, vocab: int):
        self.mix, self.order, self.rng, self.vocab = mix, order, rng, vocab
        self.last_of_rank = {}
        sp = mix.get("system_prompts")
        if sp:
            k = sp["count"]
            # fixed length per popularity rank: geometric from len_min to
            # len_max, in the mix's order
            table = np.rint(sp["len_min"] * (sp["len_max"] / sp["len_min"])
                            ** (np.arange(k) / max(k - 1, 1))).astype(np.int64)
            lens = table[order.permutation(k)]
            self.systems = [_tokens(rng, lens[r], vocab) for r in range(k)]

    def warm(self) -> list:
        """Each system prompt once, asking for one token."""
        return [Spec(p, 1) for p in getattr(self, "systems", [])]

    def make(self, outputs: np.ndarray) -> list:
        """(prompt, answer length) for each of ``outputs``."""
        mix, order, rng, n = self.mix, self.order, self.rng, len(outputs)
        sp = mix.get("system_prompts")
        if not sp:
            plen = order.permutation(lognormal_set(n, mix["prompt"]))
            return [(_tokens(rng, plen[i], self.vocab), int(outputs[i]))
                    for i in range(n)]
        k = sp["count"]
        rank = order.permutation(np.repeat(np.arange(k),
                                           zipf_counts(n, k, sp["zipf_s"])))
        user = order.permutation(lognormal_set(n, mix["user_turn"]))
        repeat = np.zeros(n, bool)
        repeat[order.permutation(n)[: round(n * mix.get("repeat_share", 0.0))]
               ] = True
        out = []
        for i in range(n):
            r, new = int(rank[i]), int(outputs[i])
            if repeat[i] and r in self.last_of_rank:
                prompt = self.last_of_rank[r]
                new = min(new, mix["max_total"] - len(prompt))
            else:
                room = mix["max_total"] - new - len(self.systems[r])
                prompt = np.concatenate([self.systems[r], _tokens(
                    rng, max(1, min(int(user[i]), room)), self.vocab)])
            self.last_of_rank[r] = prompt
            out.append((prompt, new))
        return out


def generate(mix: dict, seed: int, vocab: int) -> Traffic:
    """Requests for one run."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    order = np.random.default_rng(mix["order_seed"])
    rng = np.random.default_rng(seed)
    c = mix["clients"]
    prompts = _Prompts(mix, order, rng, vocab)
    fill = []
    if mix.get("fill") == "staggered":
        # every client is part-way through a request, and what it already
        # generated is context now
        olen = order.permutation(lognormal_set(c, mix["output"]))
        gone_frac = order.permutation(quantiles(c))
        for (prompt, new), frac in zip(prompts.make(olen), gone_frac):
            gone = min(int(frac * new), new - 1)
            fill.append(Spec(np.concatenate(
                [prompt, _tokens(rng, gone, vocab)]), new - gone))
    queue = []
    for _ in range(mix["blocks"]):
        olen = order.permutation(lognormal_set(mix["block"], mix["output"]))
        queue += [Spec(p, new) for p, new in prompts.make(olen)]
    return Traffic(fill, queue, prompts.warm())


def describe(t: Traffic) -> dict:
    """Length distributions of generated traffic."""
    specs = t.fill + t.queue
    p = np.array([len(s.prompt) for s in specs])
    o = np.array([s.max_new_tokens for s in specs])
    pct = lambda a: {q: int(np.percentile(a, q)) for q in (5, 50, 95)}
    return {"requests": len(specs), "prompt_tokens": pct(p),
            "output_tokens": pct(o)}
