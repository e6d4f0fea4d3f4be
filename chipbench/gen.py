"""The one traffic generator: a traffic file's parameters -> requests.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters
only.  Two random streams feed it:

* ``schedule_seed`` (in the file) draws every size and every arrival:
  which prefix group a request reads, its lengths, when it is due.
  Every run of a cell therefore offers the same work at the same
  times, whatever its ``--seed``;
* the run's ``--seed`` draws the contents: each group's prefix tokens
  and each request's own tokens.

Keys of a traffic file:

``loop``            ``"open"`` (requests due on a schedule) or
                    ``"closed"`` (``outstanding`` requests in flight).
``arrivals``        open loop: ``{"process": "poisson", "rate_per_s": r}``.
``outstanding``     closed loop: requests kept in flight.
``prefix``          optional ``{"groups": G, "zipf_s": s, "tokens":
                    dist}``: G shared prefixes, each request reads one,
                    chosen with Zipf(s) popularity.
``prompt_tokens``   the request's own prompt tokens (after any prefix).
``output_tokens``   tokens generated, greedy, with no end token.
``engine``          the server's settings (``ServeEngine`` keywords).

``source``          where the lengths come from (read by people only).

A length distribution is ``{"dist": "loguniform", "min": a, "max":
b}`` or ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` (drawn, rounded, then clipped to ``[min, max]``).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np

WARMUP_RID = 10 ** 9


@dataclasses.dataclass
class Req:
    """One generated request."""

    rid: int
    prompt: np.ndarray          # int32 token ids, prefix included
    max_new_tokens: int
    group: int = -1             # prefix group, -1 for none
    due: float | None = None    # seconds after the window opens (open loop)


def _stream(seed: int, tag: int) -> np.random.Generator:
    """An independent stream per (seed, use); any whole-number seed."""
    return np.random.default_rng([int(seed), tag])


def draw_len(rng: np.random.Generator, spec: dict) -> int:
    kind = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    if kind == "loguniform":
        x = np.exp(rng.uniform(np.log(lo), np.log(hi)))
    elif kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal())
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), lo), hi))


def len_range(spec: dict) -> tuple[int, int]:
    """Smallest and largest length a distribution can give."""
    return int(spec["min"]), int(spec["max"])


class Traffic:
    """Requests of one traffic file for one run seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.vocab = int(vocab)
        self.seed = int(seed)
        sched = int(spec["schedule_seed"])
        self._sizes = _stream(sched, 1)
        self._gaps = _stream(sched, 2)
        self._content = _stream(seed, 3)
        pre = spec.get("prefix")
        self.prefixes: list[np.ndarray] = []
        self._popularity = None
        if pre:
            g = int(pre["groups"])
            lens = [draw_len(self._sizes, pre["tokens"]) for _ in range(g)]
            self.prefixes = [self._tokens(n) for n in lens]
            w = 1.0 / np.arange(1, g + 1) ** float(pre["zipf_s"])
            self._popularity = w / w.sum()
        self._rid = itertools.count()

    def _tokens(self, n: int) -> np.ndarray:
        return self._content.integers(0, self.vocab, n, dtype=np.int32)

    def _next(self, due: float | None = None) -> Req:
        group = -1
        if self._popularity is not None:
            group = int(self._sizes.choice(len(self._popularity),
                                           p=self._popularity))
        n_own = draw_len(self._sizes, self.spec["prompt_tokens"])
        n_out = draw_len(self._sizes, self.spec["output_tokens"])
        own = self._tokens(n_own)
        prompt = own if group < 0 else np.concatenate([self.prefixes[group],
                                                       own])
        return Req(rid=next(self._rid), prompt=prompt, max_new_tokens=n_out,
                   group=group, due=due)

    def open_schedule(self, seconds: float) -> list[Req]:
        """Every request due in ``[0, seconds)``, in due order."""
        arr = self.spec["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        rate = float(arr["rate_per_s"])
        out, t = [], 0.0
        while True:
            t += self._gaps.exponential(1.0 / rate)
            if t >= seconds:
                return out
            out.append(self._next(due=t))

    def closed_stream(self) -> Iterator[Req]:
        """Requests in the order a closed loop sends them."""
        while True:
            yield self._next()

    def max_prompt_len(self) -> int:
        lo, hi = len_range(self.spec["prompt_tokens"])
        if self.spec.get("prefix"):
            hi += len_range(self.spec["prefix"]["tokens"])[1]
        return hi

    def min_prompt_len(self) -> int:
        lo, _ = len_range(self.spec["prompt_tokens"])
        if self.spec.get("prefix"):
            lo += len_range(self.spec["prefix"]["tokens"])[0]
        return lo

    def warmup(self) -> list[Req]:
        """Requests that compile every program shape this traffic uses
        and no other, put each shared prefix in the cache, and pass
        through every slot: one request per prefix group; one prompt
        per prefill bucket the traffic's lengths reach; one chunked
        prompt if prompts can exceed the chunk; then short prompts
        until every slot has held one.  Each generates one decode
        block's tokens."""
        eng = self.spec["engine"]
        k = int(eng.get("steps_per_dispatch", 1))
        chunk = eng.get("prefill_chunk")
        lo, hi = self.min_prompt_len(), self.max_prompt_len()
        lens = []
        unchunked_hi = hi if chunk is None else min(hi, int(chunk))
        prev = 0
        for b in sorted(eng.get("bucket_sizes") or ()):
            if lo <= b and prev < unchunked_hi:
                lens.append(max(min(b, unchunked_hi), prev + 1, lo))
            prev = b
        rng = _stream(self.seed, 4)

        def toks(n):
            return rng.integers(0, self.vocab, n, dtype=np.int32)
        n_own = len_range(self.spec["prompt_tokens"])[0]
        reqs = [np.concatenate([p, toks(n_own)]) for p in self.prefixes]
        reqs += [toks(n) for n in lens]
        if chunk is not None and hi > int(chunk) and \
                not any(len(p) > int(chunk) for p in reqs):
            reqs.append(toks(max(int(chunk) + 1, lo)))
        while len(reqs) < int(eng["num_slots"]):
            reqs.append(toks(min(lens) if lens else lo))
        return [Req(rid=WARMUP_RID + i, prompt=p, max_new_tokens=k + 1)
                for i, p in enumerate(reqs)]
