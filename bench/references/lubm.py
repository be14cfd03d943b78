"""Plain reference for the LUBM deployment, independent of the program.

The semantics a served answer must have: each conjunctive query of the
configuration evaluated over the RDFS entailment of the store at one
version (subclass, subproperty, domain and range rules of the LUBM
ontology), with set semantics.  The store is a sorted array of packed
triple keys; entailment is one vectorised expansion; a query is a chain
of hash joins done by sorting.  Nothing here imports the program.

Answers come back as sorted, distinct packed row keys (21 bits per
column), the form `pack_rows` gives any (n, w) array of ids.
"""
from __future__ import annotations

import numpy as np

from bench.generators import lubm as G

BITS = 21
MASK = np.uint64((1 << BITS) - 1)


def pack_rows(rows) -> np.ndarray:
    """(n, w) ids, w <= 3, each below 2**21 -> (n,) uint64 keys."""
    rows = np.asarray(rows, np.int64).reshape(len(rows), -1)
    key = np.zeros(len(rows), np.uint64)
    for c in range(rows.shape[1]):
        key = (key << np.uint64(BITS)) | rows[:, c].astype(np.uint64)
    return key


def _unpack3(keys: np.ndarray):
    return ((keys >> np.uint64(2 * BITS)).astype(np.int64),
            ((keys >> np.uint64(BITS)) & MASK).astype(np.int64),
            (keys & MASK).astype(np.int64))


def _closure(edges) -> dict[str, set[str]]:
    """name -> every name above it, itself included."""
    up: dict[str, set[str]] = {}
    for child, parent in edges:
        up.setdefault(child, set()).add(parent)
    out: dict[str, set[str]] = {}

    def visit(x: str) -> set[str]:
        if x not in out:
            acc = {x}
            for y in up.get(x, ()):
                acc |= visit(y)
            out[x] = acc
        return out[x]

    for x in set(up) | {p for _, p in edges}:
        visit(x)
    return out


class Reference:
    """The store at one version, and the answers of the configuration's
    queries over its entailment."""

    def __init__(self, triples, queries: list[dict]):
        self.keys = np.unique(pack_rows(triples))
        self.queries = {q["name"]: q for q in queries}
        self.V = G.vocabulary()
        self._sat = None
        sup_c = _closure(G.SUBCLASS)
        sup_p = _closure(G.SUBPROP)
        V = self.V
        self.sup_class = {V[c]: sorted(V[x] for x in sup_c.get(c, {c}))
                          for c in G.CLASSES}
        # per property: (superproperties, subject classes, object classes)
        self.prop_rules = {}
        for p in G.PROPS:
            sups = sorted(sup_p.get(p, {p}))
            s_cls, o_cls = set(), set()
            for q in sups:
                dom, rng = G.PROPS[q]
                if dom is not None:
                    s_cls |= sup_c.get(dom, {dom})
                if rng is not None:
                    o_cls |= sup_c.get(rng, {rng})
            self.prop_rules[V[p]] = ([V[q] for q in sups if q != p],
                                     sorted(V[c] for c in s_cls),
                                     sorted(V[c] for c in o_cls))

    # -- versions ------------------------------------------------------
    def apply(self, inserts, deletes) -> None:
        """TT' = (TT \\ deletes) | inserts."""
        keys = self.keys
        if len(deletes):
            dk = np.unique(pack_rows(deletes))
            pos = np.searchsorted(keys, dk)
            hit = pos < len(keys)
            hit[hit] = keys[pos[hit]] == dk[hit]
            keys = np.delete(keys, pos[hit])
        if len(inserts):
            keys = np.union1d(keys, pack_rows(inserts))
        self.keys = keys
        self._sat = None

    # -- entailment ----------------------------------------------------
    def entailed(self):
        """(s, p, o) columns of the RDFS closure, distinct."""
        if self._sat is None:
            s, p, o = _unpack3(self.keys)
            T = self.V["rdf:type"]
            out = [self.keys]

            def emit(ss, pp, oo):
                out.append(pack_rows(np.stack(
                    [ss, np.broadcast_to(pp, ss.shape),
                     np.broadcast_to(oo, ss.shape)], axis=1)))

            is_t = p == T
            for c, sups in self.sup_class.items():
                m = is_t & (o == c)
                if m.any():
                    for sc in sups:
                        emit(s[m], T, sc)
            for pid, (sup_props, s_cls, o_cls) in self.prop_rules.items():
                m = p == pid
                if not m.any():
                    continue
                for q in sup_props:
                    emit(s[m], q, o[m])
                for c in s_cls:
                    emit(s[m], T, c)
                for c in o_cls:
                    emit(o[m], T, c)
            keys = np.unique(np.concatenate(out))
            self._sat = _unpack3(keys)
        return self._sat

    # -- queries -------------------------------------------------------
    def _atom(self, atom, cols):
        """Bindings of one triple pattern: {var: column}."""
        s, p, o = cols
        m = np.ones(len(s), bool)
        vars_: dict[str, np.ndarray] = {}
        for term, col in zip(atom, (s, p, o)):
            if term.startswith("?"):
                continue
            m &= col == self.V[term]
        for term, col in zip(atom, (s, p, o)):
            if term.startswith("?"):
                if term in vars_:
                    m &= vars_[term] == col
                else:
                    vars_[term] = col
        return {v: c[m] for v, c in vars_.items()}

    @staticmethod
    def _join(left: dict, right: dict) -> dict:
        shared = [v for v in left if v in right]
        if not shared:
            raise ValueError("query atoms must be connected")
        lk = pack_rows(np.stack([left[v] for v in shared], axis=1))
        rk = pack_rows(np.stack([right[v] for v in shared], axis=1))
        order = np.argsort(rk, kind="stable")
        rk = rk[order]
        lo = np.searchsorted(rk, lk, side="left")
        hi = np.searchsorted(rk, lk, side="right")
        cnt = hi - lo
        li = np.repeat(np.arange(len(lk)), cnt)
        starts = np.repeat(lo, cnt)
        offs = np.arange(len(li)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        ri = order[starts + offs]
        out = {v: c[li] for v, c in left.items()}
        for v, c in right.items():
            if v not in out:
                out[v] = c[ri]
        return out

    def answer(self, name: str) -> np.ndarray:
        """Sorted distinct packed head rows of query `name`."""
        q = self.queries[name]
        cols = self.entailed()
        atoms = [self._atom(a, cols) for a in q["atoms"]]
        rel, rest = atoms[0], atoms[1:]
        while rest:
            nxt = next((i for i, a in enumerate(rest)
                        if set(a) & set(rel)), None)
            if nxt is None:
                raise ValueError(f"{name}: atoms are not connected")
            rel = self._join(rel, rest.pop(nxt))
        head = np.stack([rel[v] for v in q["head"]], axis=1)
        return np.unique(pack_rows(head))
