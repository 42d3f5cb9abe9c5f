"""Plain sequential reference of the acyclic conflict graph.

The paper's sequential specification, written out in plain Python with
nothing imported from the system under test: a vertex set, an edge set
and successor/predecessor lists.  An acyclic edge insert is accepted iff
the graph holds no path from its target back to its source, a
reachability read asks for a path of one edge or more, and removals
take effect at once.  Replaying a served trace in commit order through
this class gives every answer the served path has to give.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Set, Tuple


class SeqGraph:
    def __init__(self) -> None:
        self.vertices: Set[int] = set()
        self.succ: Dict[int, Set[int]] = {}
        self.pred: Dict[int, Set[int]] = {}
        self.n_edges = 0

    # ----------------------------------------------------------- edges

    def _link(self, u: int, v: int) -> None:
        s = self.succ.setdefault(u, set())
        if v not in s:
            s.add(v)
            self.pred.setdefault(v, set()).add(u)
            self.n_edges += 1

    def _unlink(self, u: int, v: int) -> None:
        s = self.succ.get(u)
        if s is not None and v in s:
            s.discard(v)
            self.pred[v].discard(u)
            self.n_edges -= 1

    def has_edge(self, u: int, v: int) -> bool:
        return v in self.succ.get(u, ())

    def edges(self) -> Iterable[Tuple[int, int]]:
        for u, vs in self.succ.items():
            for v in vs:
                yield u, v

    # -------------------------------------------------------- mutators

    def add_vertex(self, u: int) -> bool:
        self.vertices.add(u)
        return True

    def remove_vertex(self, u: int) -> bool:
        if u not in self.vertices:
            return False
        self.vertices.discard(u)
        for v in list(self.succ.get(u, ())):
            self._unlink(u, v)
        for a in list(self.pred.get(u, ())):
            self._unlink(a, u)
        return True

    def add_edge_unchecked(self, u: int, v: int) -> bool:
        """Insert without the cycle check (a preload known to be acyclic)."""
        if u not in self.vertices or v not in self.vertices:
            return False
        self._link(u, v)
        return True

    def acyclic_add_edge(self, u: int, v: int) -> bool:
        if u not in self.vertices or v not in self.vertices:
            return False
        if self.has_edge(u, v):
            return True
        if u == v or self.reaches(v, u):
            return False
        self._link(u, v)
        return True

    def remove_edge(self, u: int, v: int) -> bool:
        if u not in self.vertices or v not in self.vertices:
            return False
        self._unlink(u, v)
        return True

    # ----------------------------------------------------------- reads

    def reaches(self, u: int, v: int) -> bool:
        """True iff a path of one edge or more goes from u to v."""
        succ = self.succ
        stack = list(succ.get(u, ()))
        seen = set(stack)
        while stack:
            x = stack.pop()
            if x == v:
                return True
            for y in succ.get(x, ()):
                if y not in seen:
                    seen.add(y)
                    stack.append(y)
        return False

    def path_exists(self, u: int, v: int) -> bool:
        if u not in self.vertices or v not in self.vertices:
            return False
        return self.reaches(u, v)

    def descendants(self, u: int, limit: int) -> List[int]:
        """Up to ``limit`` vertices reachable from u, nearest first."""
        return self._nearest(self.succ, u, limit)

    def ancestors(self, u: int, limit: int) -> List[int]:
        """Up to ``limit`` vertices that reach u, nearest first."""
        return self._nearest(self.pred, u, limit)

    @staticmethod
    def _nearest(step: Dict[int, Set[int]], u: int, limit: int) -> List[int]:
        out: List[int] = []
        seen = {u}
        frontier = [u]
        while frontier and len(out) < limit:
            nxt = []
            for x in frontier:
                for y in step.get(x, ()):
                    if y not in seen:
                        seen.add(y)
                        nxt.append(y)
                        out.append(y)
            frontier = nxt
        return out[:limit]

    # ----------------------------------------------------------- replay

    def apply(self, kind: str, a: int, b: int) -> bool:
        """One request of a served trace; returns the answer it must get."""
        if kind == "add_vertex":
            return self.add_vertex(a)
        if kind == "remove_vertex":
            return self.remove_vertex(a)
        if kind == "add_edge":
            return self.acyclic_add_edge(a, b)
        if kind == "remove_edge":
            return self.remove_edge(a, b)
        if kind == "reachable":
            return self.path_exists(a, b)
        raise ValueError(f"unknown request kind {kind!r}")


def build(keys, edges) -> SeqGraph:
    """The reference after an acyclic preload (vertices, then edges)."""
    g = SeqGraph()
    for k in keys:
        g.add_vertex(int(k))
    for u, v in edges:
        g.add_edge_unchecked(int(u), int(v))
    return g
