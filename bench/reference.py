"""Plain reference for the broker's results: iRap semantics over Python sets.

It follows the paper's definitions directly and shares no code with the
program: its own interest compiler (root / edge / child patterns of the
query tree), one side evaluation (Defs 13-15: interesting, potential and
pulled triples), the combination into the new replica (Defs 16-18), and
sequential composition of pending changesets (Def 6). The evaluation is the
exhaustive set-and-loop formulation, with the target replica indexed by
pattern and binding so that probes cost a dictionary lookup, not a scan.

Everything is in the benchmark's own term ids (:class:`source.Terms`).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

Triple = Tuple[int, int, int]
WILD = -1


def is_var(term: str) -> bool:
    return term.startswith("?")


@dataclasses.dataclass(frozen=True)
class Plan:
    """Query tree of one interest: BGP patterns first, then OGP patterns."""

    patterns: Tuple[Tuple[int, int, int], ...]  # WILD where a variable
    n_bgp: int
    kinds: Tuple[str, ...]  # root / edge / child
    anchor: Tuple[int, ...]  # slot of the grouping variable
    cslot: Tuple[int, ...]  # edge: slot of its child variable
    cvar: Tuple[int, ...]  # edge / child: child variable index
    eq: Tuple[Optional[Tuple[int, int]], ...]  # variable repeated in a pattern
    n_children: int

    @property
    def n_total(self) -> int:
        return len(self.patterns)

    def matches(self, j: int, t: Triple) -> bool:
        p = self.patterns[j]
        for k in range(3):
            if p[k] != WILD and t[k] != p[k]:
                return False
        e = self.eq[j]
        return e is None or t[e[0]] == t[e[1]]


def compile_plan(bgp: Sequence[Sequence[str]], ogp: Sequence[Sequence[str]],
                 term_id) -> Plan:
    """The query tree: the root is the BGP's most-connected join variable
    (ties to the first name in sorted order); a pattern holding the root and
    one other join variable is an edge to that child, one holding only a
    child is a child star, and the rest are root stars."""
    pats = [tuple(p) for p in bgp] + [tuple(p) for p in ogp]
    n_bgp = len(bgp)
    occ: Dict[str, List[Tuple[int, int]]] = {}
    for j, p in enumerate(pats):
        for k, t in enumerate(p):
            if is_var(t):
                occ.setdefault(t, []).append((j, k))
    joins = {v for v, sites in occ.items() if len(sites) >= 2}
    if joins:
        root = max(sorted(joins),
                   key=lambda v: sum(1 for j, _ in occ[v] if j < n_bgp))
    else:
        root = pats[0][0] if is_var(pats[0][0]) else ""
    children: List[str] = []
    kinds, anchor, cslot, cvar, eq = [], [], [], [], []
    for j, p in enumerate(pats):
        pvars = [(t, k) for k, t in enumerate(p) if is_var(t)]
        e = None
        for v in {t for t, _ in pvars}:
            sites = [k for t, k in pvars if t == v]
            if len(sites) == 2:
                e = (sites[0], sites[1])
        eq.append(e)
        jv = [(t, k) for t, k in pvars if t in joins]
        at_root = [k for t, k in jv if t == root]
        other = [(t, k) for t, k in jv if t != root]
        if at_root and other:
            if other[0][0] not in children:
                children.append(other[0][0])
            kinds.append("edge")
            anchor.append(at_root[0])
            cslot.append(other[0][1])
            cvar.append(children.index(other[0][0]))
        elif at_root:
            kinds.append("root")
            anchor.append(at_root[0])
            cslot.append(-1)
            cvar.append(-1)
        elif other:
            if other[0][0] not in children:
                children.append(other[0][0])
            kinds.append("child")
            anchor.append(other[0][1])
            cslot.append(-1)
            cvar.append(children.index(other[0][0]))
        else:
            kinds.append("root")
            anchor.append(0)
            cslot.append(-1)
            cvar.append(-1)
    consts = tuple(
        tuple(WILD if is_var(t) else term_id(t) for t in p) for p in pats)
    return Plan(consts, n_bgp, tuple(kinds), tuple(anchor), tuple(cslot),
                tuple(cvar), tuple(eq), len(children))


class Probe:
    """A target replica with the rows that match pattern ``j`` keyed by the
    value in one slot, built lazily per (pattern, slot) and kept up to date
    as rows come and go, so a fire costs time in its changeset, not in the
    replica."""

    def __init__(self, plan: Plan, tgt: Set[Triple]):
        self.plan = plan
        self.tgt = set(tgt)
        self.by_pred: Dict[int, Set[Triple]] = {}
        for t in self.tgt:
            self.by_pred.setdefault(t[1], set()).add(t)
        self._idx: Dict[Tuple[int, int], Dict[int, Set[Triple]]] = {}

    def __call__(self, j: int, slot: int, val: int) -> Set[Triple]:
        idx = self._idx.get((j, slot))
        if idx is None:
            pred = self.plan.patterns[j][1]
            rows = self.tgt if pred == WILD else self.by_pred.get(pred, ())
            idx = {}
            for t in rows:
                if self.plan.matches(j, t):
                    idx.setdefault(t[slot], set()).add(t)
            self._idx[(j, slot)] = idx
        return idx.get(val, ())

    def update(self, gone: Set[Triple], new: Set[Triple]) -> None:
        """The replica becomes ``(tgt - gone) | new``."""
        for t in gone - new:
            if t not in self.tgt:
                continue
            self.tgt.discard(t)
            self.by_pred[t[1]].discard(t)
            for (j, slot), idx in self._idx.items():
                if self.plan.matches(j, t):
                    idx[t[slot]].discard(t)
        for t in new - self.tgt:
            self.tgt.add(t)
            self.by_pred.setdefault(t[1], set()).add(t)
            for (j, slot), idx in self._idx.items():
                if self.plan.matches(j, t):
                    idx.setdefault(t[slot], set()).add(t)


def evaluate_side(plan: Plan, m: Iterable[Triple], probe: Probe):
    """One side of a changeset against the replica: (interesting,
    potential, pulls) — Defs 13-15 over the query tree."""
    p = plan
    n = p.n_total
    m = sorted(m)
    roots = [j for j in range(n) if p.kinds[j] == "root"]
    edges = [j for j in range(n) if p.kinds[j] == "edge"]
    childs = [j for j in range(n) if p.kinds[j] == "child"]
    bgp_roots = [j for j in roots if j < p.n_bgp]
    bgp_edges = [j for j in edges if j < p.n_bgp]
    child_bgp = {c: [j for j in childs if p.cvar[j] == c and j < p.n_bgp]
                 for c in range(p.n_children)}
    child_all = {c: [j for j in childs if p.cvar[j] == c]
                 for c in range(p.n_children)}
    edges_of = {c: [e for e in edges if p.cvar[e] == c]
                for c in range(p.n_children)}
    bits = {t: [j for j in range(n) if p.matches(j, t)] for t in m}
    M = {j: [t for t in m if j in bits[t]] for j in range(n)}

    sat_gen: Set[Tuple[int, int]] = set()
    for j in roots + childs:
        for t in M[j]:
            sat_gen.add((t[p.anchor[j]], j))
    root_cand: Set[int] = set()
    for j in roots + edges:
        for t in M[j]:
            root_cand.add(t[p.anchor[j]])

    pool: Dict[int, List[Tuple[int, int, Triple, bool]]] = {e: [] for e in edges}
    for e in edges:
        for t in M[e]:
            pool[e].append((t[p.anchor[e]], t[p.cslot[e]], t, False))
        for j in child_all[p.cvar[e]]:  # upward probes from child bindings
            for t in M[j]:
                for row in probe(e, p.cslot[e], t[p.anchor[j]]):
                    pool[e].append((row[p.anchor[e]], row[p.cslot[e]], row, True))
                    root_cand.add(row[p.anchor[e]])
    for e in edges:  # downward probes from root candidates
        for b in sorted(root_cand):
            for row in probe(e, p.anchor[e], b):
                pool[e].append((row[p.anchor[e]], row[p.cslot[e]], row, True))

    child_cand: Dict[int, Set[int]] = {c: set() for c in range(p.n_children)}
    for c in range(p.n_children):
        for j in child_all[c]:
            for t in M[j]:
                child_cand[c].add(t[p.anchor[j]])
        for e in edges_of[c]:
            for _, cc, _, _ in pool[e]:
                child_cand[c].add(cc)

    sat_tgt: Set[Tuple[int, int]] = set()
    pull_entries = []
    for j in childs:
        for c in sorted(child_cand[p.cvar[j]]):
            rows = probe(j, p.anchor[j], c)
            if rows:
                sat_tgt.add((c, j))
            pull_entries.append(("child", j, p.cvar[j], c, rows))
    for j in roots:
        for b in sorted(root_cand):
            rows = probe(j, p.anchor[j], b)
            if rows:
                sat_tgt.add((b, j))
            pull_entries.append(("root", j, -1, b, rows))

    by_b = {e: {} for e in edges}
    by_c = {e: {} for e in edges}
    for e in edges:
        for b, c, _, _ in pool[e]:
            by_b[e].setdefault(b, set()).add(c)
            by_c[e].setdefault(c, set()).add(b)

    def sat(b, j):
        return (b, j) in sat_gen or (b, j) in sat_tgt

    def child_ok(cv, c):
        return all(sat(c, j) for j in child_bgp[cv])

    full_memo: Dict[int, bool] = {}

    def full(b):
        if b not in full_memo:
            full_memo[b] = bool(bgp_roots or bgp_edges) and all(
                sat(b, j) for j in bgp_roots) and all(
                any(child_ok(p.cvar[e], c) for c in by_b[e].get(b, ()))
                for e in bgp_edges)
        return full_memo[b]

    def linked_full(cv, c):
        return any(full(b) for e in edges_of[cv] for b in by_c[e].get(c, ()))

    interesting: Set[Triple] = set()
    potential: Set[Triple] = set()
    for t in m:
        inter = False
        for j in bits[t]:
            if p.kinds[j] == "root":
                inter |= full(t[p.anchor[j]])
            elif p.kinds[j] == "edge":
                inter |= full(t[p.anchor[j]]) and child_ok(p.cvar[j], t[p.cslot[j]])
            else:
                c = t[p.anchor[j]]
                inter |= child_ok(p.cvar[j], c) and linked_full(p.cvar[j], c)
        if inter:
            interesting.add(t)
        elif bits[t]:
            potential.add(t)

    pulls: Set[Triple] = set()
    for kind, j, cv, b, rows in pull_entries:
        if (b, j) in sat_gen:
            continue  # only missing patterns are pulled (Def 12)
        gate = full(b) if kind == "root" else (
            child_ok(cv, b) and linked_full(cv, b))
        if gate:
            pulls.update(rows)
    for e in edges:
        for b, c, row, is_pull in pool[e]:
            if is_pull and full(b) and child_ok(p.cvar[e], c):
                pulls.add(row)
    return interesting, potential, pulls


OUT_FIELDS = ("r", "r_i", "r_prime", "a", "a_i")


def step(plan: Plan, d: Set[Triple], a: Set[Triple], tau: Probe,
         rho: Set[Triple]):
    """One composed changeset against one replica (Defs 13-18): returns
    the five delivered sets and the new ρ, and turns ``tau`` into the new
    τ."""
    r, r_i, r_prime = evaluate_side(plan, d, tau)
    a_int, a_i, a_pulls = evaluate_side(plan, a | rho, tau)
    a_out = a_int | a_pulls
    tau.update(r | r_prime, a_out)
    rho1 = ((rho - r_i) | a_i | r_prime) - a_out
    outs = {"r": r, "r_i": r_i, "r_prime": r_prime, "a": a_out, "a_i": a_i}
    return outs, rho1


def compose(changesets: Sequence[Tuple[Set[Triple], Set[Triple]]]):
    """Def 6: applying <D1, A1> then <D2, A2> equals applying
    <D1 ∪ D2, (A1 \\ D2) ∪ A2>."""
    d: Set[Triple] = set()
    a: Set[Triple] = set()
    for d2, a2 in changesets:
        a = (a - d2) | a2
        d = d | d2
    return d, a


def rows(arr: np.ndarray) -> Set[Triple]:
    return {(int(s), int(p), int(o)) for s, p, o in np.asarray(arr)}


class Replica:
    """One subscriber's reference replica, driven fire by fire."""

    def __init__(self, plan: Plan, initial: Set[Triple]):
        self.plan = plan
        self.index = Probe(plan, initial)
        self.rho: Set[Triple] = set()

    @property
    def tau(self) -> Set[Triple]:
        return self.index.tgt

    def fire(self, window: Sequence[Tuple[Set[Triple], Set[Triple]]]):
        d, a = compose(window)
        outs, self.rho = step(self.plan, d, a, self.index, self.rho)
        return outs


def star_slice(plan: Plan, dump: np.ndarray) -> np.ndarray:
    """Initial replica of a subject-star interest (every pattern a root
    star on the subject): the dump rows of the subjects that match every
    BGP pattern, for every pattern they match. Vectorised over the dump."""
    if any(k != "root" or a != 0 for k, a in zip(plan.kinds, plan.anchor)):
        raise ValueError("star_slice takes subject-star interests only")
    masks = []
    for j in range(plan.n_total):
        mk = np.ones(len(dump), bool)
        for k in range(3):
            if plan.patterns[j][k] != WILD:
                mk &= dump[:, k] == plan.patterns[j][k]
        e = plan.eq[j]
        if e is not None:
            mk &= dump[:, e[0]] == dump[:, e[1]]
        masks.append(mk)
    subjects = None
    for j in range(plan.n_bgp):
        s = np.unique(dump[masks[j], 0])
        subjects = s if subjects is None else np.intersect1d(subjects, s)
    keep = np.isin(dump[:, 0], subjects) & np.any(masks, axis=0)
    return dump[keep]
