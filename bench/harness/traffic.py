"""One general generator for every traffic mix (``bench/traffic/<mix>.json``).

Every seed gets the same work in another order. Lengths, gaps between
arrivals, think times, turn counts and system-prompt choices are drawn from a
*deck*: the distribution's quantiles at ``(i + 0.5) / n``, shuffled by the
seed and reshuffled each time it runs out. ``n`` is the mix's ``deck``
(default 512). A window that serves a few whole decks serves the same
lengths on every seed; the seed moves only the order and the few draws at
the window's edges. Token contents are drawn from the seed. Arrivals are
clocked in seconds of the host's monotonic clock, never in engine steps,
so a slower system sees the same offered load and its queue grows.

Loop kinds:

- ``closed``: ``clients`` callers, each sending its next request when the
  previous one finishes. With ``stationary_start`` each client's first
  request already carries a uniform share ``u`` of its output as prompt and
  generates the rest, so the window opens on a batch in steady state.
- ``sessions``: an open loop of chat sessions. Sessions start as a Poisson
  process; each picks a system prompt by Zipf popularity and runs a number
  of turns. A turn is due when the session's previous turn finished plus an
  exponential think time; its prompt is the system prompt, the history
  (earlier messages and served answers) and a new message. A session ends
  early when its next prompt plus output would pass ``max_len``.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from statistics import NormalDist
from typing import Optional

import numpy as np

DECK = 512  # quantiles in a deck, unless the mix sets "deck"


def quantile_deck(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the quantiles ``(i + 0.5) / n`` of ``dist``:
    ``lognormal`` (median, sigma; clipped to min, max and rounded),
    ``exponential`` (mean), ``uniform_int`` (min, max inclusive) or
    ``zipf`` (s over ``n_items`` items; counts proportional to 1 / rank^s)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.round(v), dist["min"], dist["max"]).astype(np.int64)
    if kind == "exponential":
        return -np.log1p(-u) * dist["mean"]
    if kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        return (lo + np.floor(u * (hi - lo + 1))).astype(np.int64)
    if kind == "zipf":
        w = 1.0 / np.arange(1, dist["n_items"] + 1) ** dist["s"]
        cdf = np.cumsum(w) / w.sum()
        return np.searchsorted(cdf, u).astype(np.int64)
    raise ValueError(f"unknown distribution {kind!r}")


class Deck:
    """Quantile deck drawn in a seeded order; reshuffled when it runs out."""

    def __init__(self, dist: dict, n: int, rng: np.random.Generator):
        self.values = quantile_deck(dist, n)
        self.rng = rng
        self.order: list = []

    def draw(self):
        if not self.order:
            self.order = list(self.rng.permutation(len(self.values)))
        return self.values[self.order.pop()].item()


@dataclasses.dataclass(eq=False)
class Item:
    """One request the generator wants sent: its prompt, its output length,
    and when it is due (seconds from the generator's start)."""

    prompt: np.ndarray
    max_new: int
    due: float
    owner: int  # client or session index


class Generator:
    """Base: a heap of items by due time plus follow-ups on completion."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(int(seed) % (1 << 64))
        self._heap: list = []
        self._tie = itertools.count()

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, int(n)).astype(np.int32)

    def _deck(self, dist: dict) -> Deck:
        return Deck(dist, int(self.mix.get("deck", DECK)),
                    np.random.default_rng(self.rng.integers(1 << 62)))

    def push(self, item: Item) -> None:
        heapq.heappush(self._heap, (item.due, next(self._tie), item))

    def next_due(self) -> Optional[float]:
        return self._heap[0][0] if self._heap else None

    def pop_due(self, now: float) -> list:
        out = []
        while self._heap and self._heap[0][0] <= now:
            out.append(heapq.heappop(self._heap)[2])
        return out

    def start(self) -> None:
        raise NotImplementedError

    def finished(self, item: Item, served: list, now: float) -> None:
        """The request of ``item`` finished at ``now`` with ``served`` tokens."""
        raise NotImplementedError


class ClosedLoop(Generator):
    def __init__(self, mix: dict, seed: int, vocab: int):
        super().__init__(mix, seed, vocab)
        c = mix["closed"]
        self.clients = int(c["clients"])
        self.think = float(c.get("think_s", 0.0))
        self.prompt = self._deck(mix["prompt"])
        self.output = self._deck(mix["output"])
        self.stationary = bool(c.get("stationary_start", False))

    def _request(self, client: int, due: float) -> Item:
        return Item(self._tokens(self.prompt.draw()), int(self.output.draw()), due, client)

    def start(self) -> None:
        shares = self.rng.permutation((np.arange(self.clients) + 0.5) / self.clients)
        for c in range(self.clients):
            it = self._request(c, 0.0)
            if self.stationary:
                done = int(shares[c] * it.max_new)
                it.prompt = np.concatenate([it.prompt, self._tokens(done)])
                it.max_new = max(1, it.max_new - done)
            self.push(it)

    def finished(self, item: Item, served: list, now: float) -> None:
        self.push(self._request(item.owner, now + self.think))


class Sessions(Generator):
    def __init__(self, mix: dict, seed: int, vocab: int):
        super().__init__(mix, seed, vocab)
        s = mix["sessions"]
        self.max_len = int(mix["engine"]["max_len"])
        self.systems = [self._tokens(s["system_len"]) for _ in range(s["system_prompts"])]
        self.gap = self._deck({"dist": "exponential", "mean": 1.0 / s["rate_per_s"]})
        self.think = self._deck({"dist": "exponential", "mean": s["think_mean_s"]})
        self.turns = self._deck({"dist": "uniform_int", "min": s["turns_min"],
                                 "max": s["turns_max"]})
        self.pick = self._deck({"dist": "zipf", "s": s["zipf_s"],
                                "n_items": s["system_prompts"]})
        self.message = self._deck(mix["prompt"])
        self.output = self._deck(mix["output"])
        self.sessions: list = []  # per session: [history tokens, turns left]
        self.horizon = float(s.get("horizon_s", 3600.0))

    def _turn(self, sid: int, due: float) -> Optional[Item]:
        hist, left = self.sessions[sid]
        if left <= 0:
            return None
        prompt = np.concatenate([hist, self._tokens(self.message.draw())])
        max_new = int(self.output.draw())
        if len(prompt) + max_new > self.max_len:
            return None  # the client cuts the session, as clients cut history
        self.sessions[sid][1] = left - 1
        return Item(prompt, max_new, due, sid)

    def start(self) -> None:
        t = 0.0
        while t < self.horizon:
            t += self.gap.draw()
            sid = len(self.sessions)
            self.sessions.append([self.systems[self.pick.draw()], self.turns.draw()])
            it = self._turn(sid, t)
            if it is not None:
                self.push(it)

    def finished(self, item: Item, served: list, now: float) -> None:
        sid = item.owner
        self.sessions[sid][0] = np.concatenate(
            [item.prompt, np.asarray(served, np.int32)])
        it = self._turn(sid, now + self.think.draw())
        if it is not None:
            self.push(it)


LOOPS = {"closed": ClosedLoop, "sessions": Sessions}


def make_generator(mix: dict, seed: int, vocab: int) -> Generator:
    return LOOPS[mix["loop"]](mix, seed, vocab)
