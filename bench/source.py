"""Seeded DBpedia-Live-like source: an initial dump and a changeset stream.

The benchmark's own copy of the generator the repository uses for its
examples, so that no change to the program can change the yardstick. It keeps
that generator's structure: a mixed-domain dump of soccer players (with
teams), places and other people or works, and changesets of random live
removals, new athletes and places (half with partial attribute sets), goal
updates of existing athletes (remove + add) and uninteresting bulk churn.

What differs is bookkeeping only: the live set is an indexable list with
swap-removal and the goal triples are indexed by athlete, so a changeset
costs time in its own size, not in the size of the dump. Every output is a
pure function of the seed.

Terms are encoded by :class:`Terms`, a dense append-only id table. The
program receives the term list (in id order) and the id arrays, never the
generator itself.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

Triple = Tuple[str, str, str]

P_TYPE = "rdf:type"
P_GOALS = "dbp:goals"
P_NAME = "foaf:name"
P_TEAM = "dbo:team"
P_LABEL = "rdfs:label"
P_LAT = "wgs:lat"
P_LONG = "wgs:long"
P_ABSTRACT = "dbo:abstract"
P_SUBJECT = "dcterms:subject"
P_HOMEPAGE = "foaf:homepage"
C_ATHLETE = "dbo:SoccerPlayer"
C_PLACE = "dbo:Place"
C_PERSON = "foaf:Person"
C_WORK = "dbo:Work"


class Terms:
    """Dense, append-only term <-> int32 id table."""

    def __init__(self):
        self.ids: Dict[str, int] = {}
        self.names: List[str] = []

    def id(self, term: str) -> int:
        tid = self.ids.get(term)
        if tid is None:
            tid = self.ids[term] = len(self.names)
            self.names.append(term)
        return tid

    def encode(self, rows: Sequence[Triple]) -> np.ndarray:
        """Rows as a lexicographically sorted, duplicate-free int32[N, 3]."""
        if not rows:
            return np.zeros((0, 3), np.int32)
        ids = self.ids
        flat = []
        for s, p, o in rows:
            for t in (s, p, o):
                tid = ids.get(t)
                if tid is None:
                    tid = self.id(t)
                flat.append(tid)
        arr = np.asarray(flat, np.int32).reshape(-1, 3)
        return np.unique(arr, axis=0)


@dataclasses.dataclass(frozen=True)
class SourceSize:
    n_athletes: int
    n_places: int
    n_other: int
    n_teams: int
    adds: int  # rows added per changeset (at least)
    removes: int  # random live rows removed per changeset
    athlete_fraction: float = 0.02
    place_fraction: float = 0.06


class DBpediaLive:
    """Initial dump, then ``<removed, added>`` changesets, from one seed."""

    def __init__(self, size: SourceSize, seed: int):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.terms = Terms()
        self._athletes = [f"dbr:Athlete_{i}" for i in range(size.n_athletes)]
        self._places = [f"dbr:Place_{i}" for i in range(size.n_places)]
        self._others = [f"dbr:Thing_{i}" for i in range(size.n_other)]
        self._teams = [f"dbr:Team_{i}" for i in range(size.n_teams)]
        self._next_id = 0
        self._live: List[Triple] = []  # indexable live set
        self._pos: Dict[Triple, int] = {}
        self._goals: Dict[str, set] = {}  # athlete -> its dbp:goals rows

    # -- live-set bookkeeping -------------------------------------------
    def _add(self, t: Triple) -> None:
        if t in self._pos:
            return
        self._pos[t] = len(self._live)
        self._live.append(t)
        if t[1] == P_GOALS:
            self._goals.setdefault(t[0], set()).add(t)

    def _remove(self, t: Triple) -> None:
        i = self._pos.pop(t)
        last = self._live.pop()
        if i < len(self._live):
            self._live[i] = last
            self._pos[last] = i
        if t[1] == P_GOALS:
            self._goals[t[0]].discard(t)

    # -- entity templates (as the repository's generator) -----------------
    def _athlete_triples(self, a: str, full: bool) -> List[Triple]:
        rng = self.rng
        team = self._teams[rng.integers(len(self._teams))]
        rows = [(a, P_TYPE, C_ATHLETE), (a, P_NAME, f'"{a}"'),
                (a, P_TEAM, team), (team, P_LABEL, f'"{team} FC"')]
        if full or rng.random() < 0.7:
            rows.append((a, P_GOALS, str(int(rng.integers(0, 300)))))
        if rng.random() < 0.3:
            rows.append((a, P_HOMEPAGE, f'"http://{a}.example.org"'))
        return rows

    def _place_triples(self, p: str, full: bool) -> List[Triple]:
        rng = self.rng
        rows = [
            (p, P_TYPE, C_PLACE),
            (p, P_LABEL, f'"{p}"'),
            (p, P_LAT, f"{rng.random() * 180 - 90:.4f}"),
            (p, P_LONG, f"{rng.random() * 360 - 180:.4f}"),
        ]
        if full or rng.random() < 0.8:
            rows.append((p, P_ABSTRACT, f'"Abstract of {p}"'))
        if rng.random() < 0.5:
            rows.append((p, P_SUBJECT, f"dbc:Category_{int(rng.integers(40))}"))
        return rows

    def _other_triples(self, o: str) -> List[Triple]:
        rng = self.rng
        cls = C_PERSON if rng.random() < 0.5 else C_WORK
        rows = [(o, P_TYPE, cls), (o, P_NAME, f'"{o}"')]
        for j in range(int(rng.integers(1, 5))):
            rows.append((o, f"dbp:prop{j}", str(int(rng.integers(1000)))))
        return rows

    # -- public ---------------------------------------------------------
    def initial_dump(self) -> np.ndarray:
        for a in self._athletes:
            for t in self._athlete_triples(a, full=True):
                self._add(t)
        for p in self._places:
            for t in self._place_triples(p, full=True):
                self._add(t)
        for o in self._others:
            for t in self._other_triples(o):
                self._add(t)
        return self.terms.encode(self._live)

    def slice_where(self, keep: Callable[[Triple], bool]) -> np.ndarray:
        """Initial replica: the live triples that pass a filter."""
        return self.terms.encode([t for t in self._live if keep(t)])

    def changeset(self) -> Tuple[np.ndarray, np.ndarray]:
        size, rng = self.size, self.rng
        adds: List[Triple] = []
        removes: List[Triple] = []
        k = min(size.removes, len(self._live))
        for i in rng.choice(len(self._live), size=k, replace=False):
            removes.append(self._live[i])

        n = size.adds
        n_ath = int(n * size.athlete_fraction)
        n_pl = int(n * size.place_fraction)
        for _ in range(max(1, n_ath // 4)):
            a = f"dbr:NewAthlete_{self._next_id}"
            self._next_id += 1
            adds += self._athlete_triples(a, full=rng.random() < 0.5)
        for _ in range(max(1, n_pl // 5)):
            p = f"dbr:NewPlace_{self._next_id}"
            self._next_id += 1
            adds += self._place_triples(p, full=rng.random() < 0.5)
        for _ in range(max(1, n_ath // 2)):  # goal updates
            a = self._athletes[rng.integers(len(self._athletes))]
            removes += sorted(self._goals.get(a, ()))
            adds.append((a, P_GOALS, str(int(rng.integers(0, 300)))))
        while len(adds) < n:  # uninteresting bulk churn
            o = f"dbr:NewThing_{self._next_id}"
            self._next_id += 1
            adds += self._other_triples(o)

        removed = sorted({t for t in removes if t in self._pos})
        gone = set(removed)
        added = sorted(set(adds) - gone)
        for t in removed:
            self._remove(t)
        for t in added:
            self._add(t)
        return self.terms.encode(removed), self.terms.encode(added)

    def stream(self, n: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        return [self.changeset() for _ in range(n)]
