"""Elastic exception hierarchy — re-exported from the package-level leaf.

The classes live in :mod:`horovod_tpu.exceptions` so the runtime layer
(engine, checkpoint) can raise them without importing the elastic
package — ``from ..elastic.exceptions import ...`` would execute
``elastic/__init__`` and drag the whole launcher stack (runner,
rendezvous HTTP server, cloudpickle) into every ``import horovod_tpu``.
This module keeps the user-facing spelling
``horovod_tpu.elastic.exceptions`` working.
"""

from __future__ import annotations

from ..exceptions import (  # noqa: F401
    HorovodShutdownError,
    RankDroppedError,
    WorkersAvailableException,
)

__all__ = [
    "HorovodShutdownError",
    "RankDroppedError",
    "WorkersAvailableException",
]
