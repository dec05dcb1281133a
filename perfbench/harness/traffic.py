"""The one traffic generator. A mix is a data file under ``traffic/``;
this module turns it and a seed into sessions of requests.

A session is what one user does: an optional document, then one or more
turns, each a request whose prompt is

    shared prefix + document + turn tokens

or, where the mix sets ``history: true`` (a conversation that resends
itself),

    shared prefix + document + (turn tokens + served answer) for every
    earlier turn + turn tokens

A single-request mix is a session of one turn. The loop decides when
sessions start: ``open`` starts them at Poisson arrivals of
``rate_per_s`` (exponential gaps), ``closed`` runs
``workers_per_slot * max_batch * chips`` workers, each starting its next
session when the last one ends. Within a session each turn is sent when the
previous answer has arrived, after the user's think time where the mix
sets a ``think_s`` distribution (seconds).

Every seed gets the same sizes and gaps in the same order: each
distribution is cut into ``strata`` equal-probability bins, and every run
of ``strata`` consecutive draws takes each bin's midpoint quantile once,
in one fixed shuffled order. A window holds a few tens of requests at
most, so an order drawn from the seed would change which sizes fall in
it, and with them the work measured. The seed draws the token ids,
uniform over the vocabulary, per session and turn, so a request's
contents do not depend on timing.

Distribution entries: {"kind": "lognormal", "median", "sigma", "min",
"max"}, {"kind": "uniform", "min", "max"} (integers, both ends
included) and {"kind": "uniform_real", "min", "max"}.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional, Sequence

import numpy as np

_TAGS = {"gap": 1, "document": 2, "turns": 3, "turn_tokens": 4,
         "output_tokens": 5, "think_s": 6}
_IDS = {"prefix": 11, "document": 12, "turn": 13}
# the gaps of an open loop: exponential of mean 1, scaled by the rate
_GAP = {"kind": "exponential"}


def quantile(dist: Dict, u: float) -> float:
    kind = dist["kind"]
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return float(min(hi, lo + math.floor(u * (hi - lo + 1))))
    if kind == "uniform_real":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return float(min(max(v, dist["min"]), dist["max"]))
    if dist is _GAP:
        return -math.log1p(-u)
    raise ValueError(f"unknown distribution kind {kind!r}")


def upper(dist: Optional[Dict]) -> float:
    """The largest value a distribution can give (0 for none)."""
    return 0.0 if dist is None else float(dist["max"])


@dataclasses.dataclass
class Turn:
    tokens: int            # new prompt tokens of this turn
    output: int            # tokens the request generates
    think_s: float = 0.0   # wait after the previous answer (turn 0: none)


@dataclasses.dataclass
class Session:
    index: int
    document: int
    turns: List[Turn]


class Traffic:
    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = seed
        self.vocab = vocab
        self.loop = spec["loop"]
        self.history = bool(spec.get("history", False))
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed, not {self.loop!r}")
        self.k = int(spec.get("strata", 32))
        self._base = {}
        for tag in _TAGS:
            dist = self._dist(tag)
            if dist is not None:
                self._base[tag] = np.array(
                    [quantile(dist, (i + 0.5) / self.k) for i in range(self.k)])
        if "gap" in self._base:       # midpoints miss the tail: keep the rate
            self._base["gap"] /= self._base["gap"].mean()
        self.prefix = self._ids(_IDS["prefix"], 0, 0,
                                int(spec.get("shared_prefix_tokens", 0)))
        self._perm_cache: Dict = {}
        self._starts: List[float] = []

    def _dist(self, tag: str) -> Optional[Dict]:
        if tag == "gap":
            return _GAP if self.loop == "open" else None
        return self.spec.get(tag)

    def draw(self, tag: str, i: int) -> float:
        """Draw number ``i`` of a stratified stream."""
        block, pos = divmod(i, self.k)
        key = (tag, block)
        perm = self._perm_cache.get(key)
        if perm is None:
            rng = np.random.default_rng(
                np.random.SeedSequence([_TAGS[tag], block]))
            perm = self._perm_cache[key] = rng.permutation(self.k)
        return float(self._base[tag][perm[pos]])

    def _ids(self, tag: int, a: int, b: int, n: int) -> List[int]:
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, tag, a, b]))
        return rng.integers(0, self.vocab, n).tolist()

    # ----------------------------------------------------------- sessions
    def session(self, i: int) -> Session:
        doc = int(self.draw("document", i)) if "document" in self._base else 0
        n_turns = int(self.draw("turns", i)) if "turns" in self._base else 1
        # request number j of the run: sessions' turns in session order
        # would make a request's draw depend on earlier sessions' turn
        # counts, so turn t of session i uses draw i * max_turns + t
        stride = int(upper(self._dist("turns"))) or 1
        think = "think_s" in self._base
        turns = [Turn(tokens=int(self.draw("turn_tokens", i * stride + t)),
                      output=int(self.draw("output_tokens", i * stride + t)),
                      think_s=(self.draw("think_s", i * stride + t)
                               if think and t else 0.0))
                 for t in range(n_turns)]
        return Session(index=i, document=doc, turns=turns)

    def start_s(self, i: int) -> float:
        """Open loop: start time of session ``i`` after the traffic begins,
        the sum of its stratified exponential gaps over the rate."""
        while len(self._starts) <= i:
            j = len(self._starts)
            prev = self._starts[-1] if self._starts else 0.0
            self._starts.append(prev + self.draw("gap", j))
        return self._starts[i] / float(self.spec["rate_per_s"])

    def _turn(self, session: Session, t: int) -> List[int]:
        return self._ids(_IDS["turn"], session.index, t,
                         session.turns[t].tokens)

    def prompt(self, session: Session, t: int,
               answers: Sequence[Sequence[int]] = ()) -> List[int]:
        """Prompt of turn ``t``: the shared prefix, the session's document,
        with ``history`` each earlier turn's tokens followed by
        ``answers[u]``, the tokens served for it, then the turn's own
        tokens."""
        doc = self._ids(_IDS["document"], session.index, 0, session.document)
        out = self.prefix + doc
        if self.history:
            if len(answers) < t:
                raise ValueError(f"turn {t} of a history mix needs the "
                                 f"{t} earlier answers, got {len(answers)}")
            for u in range(t):
                out += self._turn(session, u) + list(answers[u])
        return out + self._turn(session, t)

    def worst_case_tokens(self) -> float:
        """The most tokens one request can hold: prompt plus output, and
        with ``history`` every earlier turn and answer of the longest
        session."""
        turns = (upper(self.spec.get("turns")) or 1) if self.history else 1
        return (len(self.prefix) + upper(self.spec.get("document"))
                + turns * (upper(self.spec.get("turn_tokens"))
                           + upper(self.spec.get("output_tokens"))))
