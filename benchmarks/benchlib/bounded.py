"""The bounded route's counters, as the four ``bounded.*`` metrics read
them: the program's ``solver_dispatch_rounds`` histogram and
``solver_dispatches`` counter under their ``grid`` label, ``narrow`` or
``wide`` on the bounded per-goal route (``fused`` on the whole-chain
route, which these metrics leave out), for the move and swap megasteps.

A speculative dispatch (the pump's one-ahead successor of a pass's last
dispatch) runs no round, so the rounds summed here are the rounds the
passes searched. A program whose series carry no ``grid`` label gives
nothing to read: ``labelled`` says so, and every reader returns None."""

from __future__ import annotations

from .sut import series_total

GRIDS = ("narrow", "wide")
KINDS = ("move", "swap")
ROUNDS = "solver_dispatch_rounds_sum"
DISPATCHES = "solver_dispatches_total"


def labelled(series: dict) -> bool:
    """Whether the program labels its dispatch series by grid."""
    return any(n == ROUNDS and 'grid="' in labels
               for n, labels in series)


def total(series: dict, name: str, grids=GRIDS) -> float:
    return sum(series_total(series, name, grid=g, kind=k)
               for g in grids for k in KINDS)


def moved(before: dict, after: dict, name: str, grids=GRIDS) -> float:
    """How far the bounded route's series moved between two readings."""
    return total(after, name, grids) - total(before, name, grids)
