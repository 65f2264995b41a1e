"""The memory a streamed push is summed in.

An iteration's host accumulator is a float32 array per tensor name, seeded
by the first contributor's gradient and added to by the others.  Seeded
with ``np.array(g)`` that is a store's worth of new address space every
round (far above malloc's mmap threshold, every 4 KB of it a page fault,
which cost more than the copy that fills it), dropped the moment the close
has swept it.  So the core keeps the buffers of the accumulator it closed
last, and the next iteration's seeds are copied over them.

Only when nobody reads them any more, by the rule the ring's frame pool,
the serve cache and ``CloseBuffers`` follow (``utils/buffers.exported``):
whoever keeps a sum, or a slice of one, keeps its buffer, and the seed
takes a new one in its place (``ps.fold.fresh_bytes``).  The bootstrap
close, whose aggregate BECOMES the store, a relay or a replication hook
that holds a mean: none is a special case.
"""

from __future__ import annotations

import numpy as np

from ..obs import stats as obs_stats
from ..utils.buffers import float32_over

# Bytes of accumulator that went to memory the core had to allocate
# (beside rpc.wire.fresh_bytes and ps.close.fresh_bytes): a store's first
# round, a second iteration folding while the first closes, and every
# round after somebody kept a sum.
_obs_fresh_bytes = obs_stats.counter("ps.fold.fresh_bytes")


def _buffer_of(acc) -> bytearray | None:
    """The bytearray ``acc`` is a view of (:meth:`FoldBuffers.take` made
    it), None for any other array."""
    while isinstance(acc, np.ndarray):
        acc = acc.base
    if isinstance(acc, memoryview):
        acc = acc.obj
    return acc if isinstance(acc, bytearray) else None


class FoldBuffers:
    """One spare generation: per tensor name, the buffer behind the sum
    of the accumulator closed last.  :meth:`take` and :meth:`give_back`
    are single dict operations on it, so folds of several iterations on
    several threads need no lock: a buffer has one taker."""

    def __init__(self):
        self._spare: dict[str, bytearray] = {}

    def take(self, name: str, shape: tuple) -> np.ndarray:
        """A float32 array of ``shape`` to seed ``name``'s sum in: over
        the spare buffer of that name when it has the size and no view
        of it is alive, over a new one otherwise.  Its values mean
        nothing until written."""
        return float32_over(self._spare.pop(name, None), shape,
                            _obs_fresh_bytes)[1]

    def give_back(self, sums: dict) -> None:
        """The close has read ``sums``: the buffers behind them are the
        spare generation now, and the one before goes (a close that
        brings none, an empty or a device aggregate, leaves it).  The
        caller may still hold the arrays, as may anybody else:
        :meth:`take` asks."""
        spare = {}
        for name, acc in sums.items():
            buf = _buffer_of(acc)
            if buf is not None:
                spare[name] = buf
        if spare:
            self._spare = spare
