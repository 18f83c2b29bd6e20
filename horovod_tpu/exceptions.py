"""Exception hierarchy for elastic (fault-tolerant) training.

Mirrors upstream Elastic Horovod's split (horovod/common/exceptions.py in
the post-0.19 line):

* :class:`HorovodShutdownError` — a collective failed because the world
  broke underneath it: a peer died mid-negotiation, the engine was torn
  down by the coordinated-shutdown flag, or a rendezvous wait timed out.
  ``elastic.run`` treats it as *recoverable*: roll state back to the last
  commit, re-rendezvous, resume (upstream: HorovodInternalError).
* :class:`WorkersAvailableException` — the launcher re-minted the
  rendezvous epoch (failed rank respawned, or the world shrank/grew)
  while this rank was between collectives.  Also recoverable; raised at
  commit boundaries so ranks notice membership changes promptly
  (upstream: HostsUpdatedInterrupt).
* :class:`RankDroppedError` — the launcher shrank the world past this
  rank (it was presumed dead and its slot was dropped for good).  NOT
  recoverable: there is no world for this rank to rejoin, so
  ``elastic.run`` lets it propagate instead of burning the retry budget.

All subclass ``RuntimeError`` so pre-elastic call sites that assert on
``RuntimeError`` keep working unchanged.

This module is a true leaf ON PURPOSE: the engine (runtime layer), the
checkpoint layer, and the elastic user API all import from it, and any
heavier import here would both create cycles and drag the launcher
stack into every ``import horovod_tpu``.  ``elastic.exceptions``
re-exports these names for API symmetry, but runtime-layer code should
import from here so it never executes ``elastic/__init__``.
"""

from __future__ import annotations

__all__ = [
    "HorovodShutdownError",
    "RankDroppedError",
    "WorkersAvailableException",
]


class HorovodShutdownError(RuntimeError):
    """A collective or rendezvous failed because the world broke: peer
    death, coordinated engine shutdown, or a stalled wait.  Recoverable
    under ``elastic.run`` (rollback to last commit + re-rendezvous)."""


class RankDroppedError(HorovodShutdownError):
    """This rank is no longer a member of the current world — the
    launcher shrank past it.  Not recoverable: ``elastic.run`` re-raises
    instead of retrying a rendezvous that can never succeed."""


class WorkersAvailableException(RuntimeError):
    """The launcher advanced the rendezvous epoch (a failed rank was
    respawned or the world was re-formed); the current world is stale.
    Recoverable under ``elastic.run`` (re-rendezvous + state sync)."""
