"""Payoff bookkeeping + PFSP matchmaking for the league plane.

``PayoffMatrix`` is the ONE win-rate ledger of the repo: league
generation matches (league/learner.py), network battle matches
(runtime/battle.py, ``exec_network_match`` results incl. forfeits) and
ad-hoc head-to-heads (tools/head_to_head.py) all record into this shape,
so every consumer shares one win-points convention — win + draw/2 over
games, exactly ``runtime.evaluation.wp_func`` (the convention
tools/ablate_sampler.py reports deltas in).

Accounting rules (pinned by tests/test_battle_books.py /
tests/test_league.py):

* a finished match records one entry per ORDERED pair of distinct
  member names, pairwise from the per-seat scores: higher score = win,
  equal = draw — which makes multi-player placement outcomes
  (HungryGeese's {-1, -1/3, +1/3, +1} ranks) decompose into pairwise
  results with no extra convention;
* two seats held by the SAME member record nothing (self-pairs carry no
  information);
* a severed peer forfeits: the severed seat takes a LOSS against every
  surviving seat; survivor-vs-survivor pairs are NOT recorded (their
  game never finished — inventing a draw would bias the books toward
  0.5 exactly when a flaky peer is in the population).

``Matchmaker`` samples opponents for the league candidate by
prioritized fictitious self-play (AlphaStar): the frozen population is
weighted by a function of the candidate's current win rate p against
each member — 'var' p(1-p) (near-peers), 'hard' (1-p)² (hardest first),
'even' (uniform).  Unplayed members default to p = 0.5, which under both
non-uniform weightings is the maximum — new members get probed first.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

__all__ = ["PayoffMatrix", "Matchmaker", "pfsp_weights"]


class PayoffMatrix:
    """Win/draw/loss books per ordered (member, member) pair."""

    def __init__(self):
        # (a, b) -> [wins, draws, losses] from a's perspective
        self._books: Dict[Tuple[str, str], List[int]] = {}
        self.matches = 0          # finished/forfeited MATCHES recorded
        self.forfeits = 0

    # -- recording -----------------------------------------------------------

    def record_score(self, a: str, b: str, score_a: float, score_b: float,
                     n: int = 1) -> None:
        """``n`` pairwise results between ``a`` and ``b`` from final
        scores: higher score wins, equal draws.  Records BOTH ordered
        directions; self-pairs are ignored."""
        if a == b or n <= 0:
            return
        if score_a > score_b:
            i, j = 0, 2
        elif score_a < score_b:
            i, j = 2, 0
        else:
            i = j = 1
        self._books.setdefault((a, b), [0, 0, 0])[i] += n
        self._books.setdefault((b, a), [0, 0, 0])[j] += n

    def record_outcome(self, names: Mapping[Any, str],
                       outcome: Mapping[Any, float]) -> None:
        """One finished match: ``names`` maps seats to member names,
        ``outcome`` seats to final scores (an ``exec_match`` /
        ``exec_network_match`` outcome dict).  Every unordered seat pair
        with distinct names records pairwise."""
        seats = [s for s in names if s in outcome]
        for x in range(len(seats)):
            for y in range(x + 1, len(seats)):
                sa, sb = seats[x], seats[y]
                self.record_score(
                    names[sa], names[sb],
                    float(outcome[sa]), float(outcome[sb]),
                )
        self.matches += 1

    def record_forfeit(self, names: Mapping[Any, str], severed_seat) -> None:
        """A peer severed mid-match: its seat loses to every surviving
        seat; survivor pairs record nothing (their game never finished)."""
        loser = names[severed_seat]
        for seat, name in names.items():
            if seat == severed_seat:
                continue
            self.record_score(name, loser, 1.0, -1.0)
        self.matches += 1
        self.forfeits += 1

    def adopt(self, old: str, new: str) -> None:
        """Rename ``old``'s books to ``new`` (candidate -> frozen member
        at promotion).  Any pre-existing books under ``new`` are dropped
        first: a resurrected name must not inherit a dead member's
        record."""
        if old == new:
            return
        for pair in [p for p in self._books if new in p]:
            del self._books[pair]
        for (a, b) in list(self._books):
            if a == old:
                self._books[(new, b)] = self._books.pop((a, b))
            elif b == old:
                self._books[(a, new)] = self._books.pop((a, b))

    # -- reading ---------------------------------------------------------------

    def games(self, a: str, b: str) -> int:
        return sum(self._books.get((a, b), (0, 0, 0)))

    def win_points(self, a: str, b: str) -> Optional[float]:
        """(wins + draws/2) / games from ``a``'s perspective — the
        ``wp_func`` convention; None with no games on the books."""
        w, d, l = self._books.get((a, b), (0, 0, 0))
        n = w + d + l
        return None if n == 0 else (w + d / 2) / n

    def aggregate_win_points(self, a: str,
                             opponents: Sequence[str]) -> Optional[float]:
        """Pooled win points of ``a`` over every listed opponent (game-
        weighted, not mean-of-means — 3 games vs X must not outweigh 300
        vs Y)."""
        w = d = n = 0
        for b in opponents:
            bw, bd, bl = self._books.get((a, b), (0, 0, 0))
            w, d, n = w + bw, d + bd, n + bw + bd + bl
        return None if n == 0 else (w + d / 2) / n

    def members(self) -> List[str]:
        return sorted({a for a, _ in self._books})

    def coverage(self, a: str, opponents: Sequence[str],
                 min_games: int = 1) -> float:
        """Fraction of ``opponents`` against whom ``a`` has at least
        ``min_games`` on the books (1.0 over an empty pool: nothing is
        missing)."""
        if not opponents:
            return 1.0
        hit = sum(1 for b in opponents if self.games(a, b) >= min_games)
        return hit / len(opponents)

    def elo(self, members: Sequence[str],
            anchor: Optional[str] = None) -> Dict[str, float]:
        """Per-member Elo estimates from pooled win points against the
        listed members: r = 400·log10(p/(1-p)) with p clipped away from
        {0, 1} (a member yet to lose is 'at least +478', not infinity).
        Coarse by design — a population spread/ordering signal for the
        metrics, not a ladder rating; ``anchor`` (when listed)
        is shifted to exactly 0 so ratings are comparable across epochs."""
        ratings: Dict[str, float] = {}
        for m in members:
            p = self.aggregate_win_points(m, [x for x in members if x != m])
            if p is None:
                continue
            p = min(max(p, 0.06), 0.94)
            ratings[m] = 400.0 * math.log10(p / (1.0 - p))
        if anchor in ratings:
            shift = ratings[anchor]
            ratings = {m: r - shift for m, r in ratings.items()}
        return ratings

    # -- persistence -----------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return {
            "matches": self.matches,
            "forfeits": self.forfeits,
            "books": {f"{a}\x00{b}": wdl for (a, b), wdl in self._books.items()},
        }

    @classmethod
    def from_dict(cls, data: Mapping[str, Any]) -> "PayoffMatrix":
        out = cls()
        out.matches = int(data.get("matches", 0))
        out.forfeits = int(data.get("forfeits", 0))
        for key, wdl in dict(data.get("books", {})).items():
            a, _, b = key.partition("\x00")
            out._books[(a, b)] = [int(x) for x in wdl]
        return out


def pfsp_weights(win_rates: Sequence[Optional[float]],
                 weighting: str = "var") -> List[float]:
    """PFSP opponent weights from the candidate's win rate p per member
    (None = unplayed -> 0.5, the maximum of both non-uniform schemes, so
    fresh members get probed first).  Weights get a small floor so no
    member is ever starved entirely (a 'solved' member can un-solve as
    the candidate churns)."""
    out = []
    for p in win_rates:
        p = 0.5 if p is None else min(max(float(p), 0.0), 1.0)
        if weighting == "even":
            w = 1.0
        elif weighting == "hard":
            w = (1.0 - p) ** 2
        elif weighting == "var":
            w = p * (1.0 - p)
        else:
            raise ValueError(f"unknown pfsp weighting {weighting!r}")
        out.append(max(w, 1e-3))
    return out


class Matchmaker:
    """Samples the candidate's next opponent from the active population.

    Stateless beyond its RNG: the payoff ledger is the input, so local
    generation matches and network battle results steer the SAME
    sampling distribution the moment they are recorded.
    """

    def __init__(self, payoff: PayoffMatrix, weighting: str = "var",
                 seed: int = 0):
        self.payoff = payoff
        self.weighting = weighting
        self._rng = random.Random(seed ^ 0x1EA90E)

    def sample_opponent(self, candidate: str, pool: Sequence[str],
                        min_games: int = 0) -> Optional[str]:
        """PFSP draw over ``pool`` (member names); None on an empty pool.

        ``min_games > 0`` adds a PROBE QUOTA ahead of the PFSP draw:
        members with fewer than that many games against the candidate
        sample uniformly first.  Without it, one decisive first game
        pins p at 0 or 1, the 'var'/'hard' weight collapses to the
        floor, and that member starves — permanently blocking any
        coverage-gated promotion (the learner passes its
        ``promote_games`` here so the gate's requirement and the
        sampler's guarantee are the same number).  Win rates feed the
        weighting Laplace-smoothed toward 0.5 (prior weight 2) so small
        samples cannot pin the distribution either way."""
        if not pool:
            return None
        if min_games > 0:
            under = [b for b in pool if self.payoff.games(candidate, b) < min_games]
            if under:
                return self._rng.choice(under)
        rates = []
        for b in pool:
            p, n = self.payoff.win_points(candidate, b), self.payoff.games(candidate, b)
            rates.append(None if p is None else (p * n + 0.5 * 2) / (n + 2))
        weights = pfsp_weights(rates, self.weighting)
        return self._rng.choices(list(pool), weights=weights)[0]
