"""One general traffic generator, driven by a data file.

A mix fixes *multisets*: the stated length distributions sampled at
evenly spaced quantiles, one whole spread to every block of requests,
and for an open loop a multiset of inter-arrival gaps (exponential
quantiles, rescaled so a block spans exactly block/rate seconds).
``--seed`` permutes each block, draws the token ids, and deals the
shared prefixes out under the stated skew. It never changes how many
tokens a run asks for, how many requests a stretch of the schedule
holds, or which prompt shapes occur: lengths keep their spread and lose
their luck.

Prompt lengths are snapped to a short grid, because the engine compiles
one prefill program per exact length (``compiled_prefill``,
``lru_cache(16)``) and one suffix program per (bucket, prefix pages)
pair (``lru_cache(32)``); ``warmup()`` returns one request for every
shape the stream can produce, and the run fails on any compilation
inside the window.

Every request's first own token (the prompt's first, or the first after
a shared prefix) is drawn without replacement, so that "nothing shared"
and "a unique tail" hold from the first token: a chance match of one
token would fork a page copy-on-write and take the suffix path with a
shape no warm-up ran.
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist

import numpy as np

MAX_WHOLE_PROMPT_SHAPES = 12   # the engine keeps 16 prefill programs
MAX_SUFFIX_SHAPES = 24         # and 32 suffix programs


@dataclasses.dataclass
class Request:
    index: int                 # position in the seeded stream (-1: warm-up)
    tokens: list
    max_new: int
    due: float = 0.0           # seconds from the schedule's origin (open)
    prefix: int = -1           # which shared prefix, -1 = none
    greedy: bool = True


def quantiles(dist: dict, n: int) -> list[float]:
    """`n` values of `dist` at the quantiles (i + 0.5) / n, ascending."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        values = [math.exp(mu + sigma * NormalDist().inv_cdf(q)) for q in qs]
    elif kind == "uniform":
        values = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        values = [math.exp(lo + q * (hi - lo)) for q in qs]
    elif kind == "exponential":
        values = [-math.log(1.0 - q) * dist["mean"] for q in qs]
    elif kind == "fixed":
        values = [dist["value"]] * n
    else:
        raise ValueError(f"unknown distribution `{kind}`")
    if "min" in dist:
        values = [max(v, dist["min"]) for v in values]
    if "max" in dist:
        values = [min(v, dist["max"]) for v in values]
    return values


def snap(values: list[float], grid: list[int] | None) -> list[int]:
    """Whole numbers; to the nearest grid point (in log space) if a grid
    is given."""
    if not grid:
        return [int(round(v)) for v in values]
    return [min(grid, key=lambda g: abs(math.log(g) - math.log(v)))
            for v in values]


def zipf_counts(n_items: int, exponent: float, total: int) -> list[int]:
    """`total` draws dealt to `n_items` by Zipf weights, largest
    remainders first: the same counts for every seed."""
    weights = [1.0 / (k + 1) ** exponent for k in range(n_items)]
    scale = total / sum(weights)
    exact = [w * scale for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(n_items), key=lambda k: exact[k] - counts[k],
                   reverse=True)
    for k in order[:total - sum(counts)]:
        counts[k] += 1
    return counts


class Stream:
    """The requests of one run: ``warmup()``, ``lead_in()``, then
    ``block(k)`` for k = 0, 1, ... (each `block_size` requests)."""

    def __init__(self, traffic: dict, seed: int, slots: int, vocab: int):
        self.traffic = traffic
        self.seed = int(seed)
        self.slots = slots
        self.vocab = vocab
        self.block_size = int(traffic["block_per_slot"]) * slots
        n = self.block_size
        self.prefix = traffic.get("shared_prefix")
        self.own_lens = snap(quantiles(traffic["prompt"], n),
                             traffic["prompt"].get("grid"))
        self.out_lens = snap(quantiles(traffic["output"], n), None)
        shapes = sorted(set(self.own_lens))
        limit = MAX_SUFFIX_SHAPES if self.prefix else MAX_WHOLE_PROMPT_SHAPES
        if len(shapes) > limit:
            raise ValueError(f"{len(shapes)} distinct prompt shapes; the "
                             f"engine's program cache takes {limit}")
        self.shapes = shapes
        self.gaps = None
        if traffic["kind"] == "open":
            raw = quantiles({"dist": "exponential", "mean": 1.0}, n)
            span = n / float(traffic["rate_per_s"])
            self.gaps = [g * span / sum(raw) for g in raw]
        self.prefix_of = None
        rng = np.random.default_rng([self.seed, 0x70726566])
        # First own tokens, without replacement over the whole run.
        self._firsts = rng.permutation(vocab)
        self._n_first = 0
        self.prefixes = []
        if self.prefix:
            counts = zipf_counts(self.prefix["count"], self.prefix["zipf"], n)
            self.prefix_of = [k for k, c in enumerate(counts)
                              for _ in range(c)]
            for _ in range(self.prefix["count"]):
                body = rng.integers(0, vocab, self.prefix["tokens"]).tolist()
                body[0] = self._first()
                self.prefixes.append(body)

    def _first(self) -> int:
        token = int(self._firsts[self._n_first % self.vocab])
        self._n_first += 1
        return token

    def _prompt(self, rng, own_len: int, prefix: int) -> list:
        own = rng.integers(0, self.vocab, own_len).tolist()
        own[0] = self._first()
        return (self.prefixes[prefix] if prefix >= 0 else []) + own

    def warmup(self) -> list[Request]:
        """One request for every program shape the stream can ask for.
        With shared prefixes: each prefix once with the shortest tail
        (the whole-prompt program, and its pages built in set-up), then
        every tail length against prefix 0 (the suffix programs)."""
        rng = np.random.default_rng([self.seed, 0x7761726D])
        new = int(self.traffic.get("warmup_new", 2))
        out = []
        if self.prefix:
            for p in range(self.prefix["count"]):
                out.append(Request(-1, self._prompt(rng, self.shapes[0], p),
                                   new, prefix=p))
            for own in self.shapes:
                out.append(Request(-1, self._prompt(rng, own, 0), new,
                                   prefix=0))
        else:
            for own in self.shapes:
                out.append(Request(-1, self._prompt(rng, own, -1), new))
        return out

    def lead_in(self) -> list[Request]:
        """Closed loops: `slots` requests in a fixed order whose outputs
        are staggered evenly, so that slots free up one after another
        and the measured requests behind them take the slots at spread
        phases. The window opens when the last of these has finished."""
        lead = self.traffic.get("lead_in")
        if not lead:
            return []
        rng = np.random.default_rng([self.seed, 0x6C656164])
        lo, hi, n = lead["new_from"], lead["new_to"], self.slots
        out = []
        for i in range(n):
            own = self.shapes[i % len(self.shapes)]
            prefix = (i % self.prefix["count"]) if self.prefix else -1
            new = int(round(lo + (hi - lo) * i / max(n - 1, 1)))
            out.append(Request(-1, self._prompt(rng, own, prefix), new,
                               prefix=prefix))
        return out

    def block(self, k: int) -> list[Request]:
        """Block `k`: the whole multiset of lengths, outputs, gaps and
        prefix uses, each permuted by the seed. Call in order of k (the
        first-token draw is sequential)."""
        n = self.block_size
        perm = random.Random(f"{self.seed}:{k}")
        own = list(self.own_lens)
        new = list(self.out_lens)
        perm.shuffle(own)
        perm.shuffle(new)
        prefix = [-1] * n
        if self.prefix_of:
            prefix = list(self.prefix_of)
            perm.shuffle(prefix)
        due = [0.0] * n
        if self.gaps:
            gaps = list(self.gaps)
            perm.shuffle(gaps)
            t = k * sum(self.gaps)
            for i, gap in enumerate(gaps):
                t += gap
                due[i] = t
        rng = np.random.default_rng([self.seed, 0x626C6F63, k])
        return [Request(k * n + i, self._prompt(rng, own[i], prefix[i]),
                        new[i], due=due[i], prefix=prefix[i])
                for i in range(n)]

    def totals(self, k: int) -> dict:
        """What block `k` asks for: the same for every seed."""
        block = self.block(k)
        return {"requests": len(block),
                "prompt_tokens": sum(len(r.tokens) for r in block),
                "output_tokens": sum(r.max_new for r in block),
                "span_s": (max(r.due for r in block) - k * sum(self.gaps)
                           if self.gaps else None)}
