"""The memory the barrier close writes the next store into.

The parameter server never writes into an array it has served ("the server
swaps new arrays in"): a close reads the store it retires and writes a
store's worth of NEW parameters.  Into fresh arrays that is new address
space every round, and at a model's size (far above malloc's mmap
threshold) every 4 KB of it a page fault, which cost more than the sweep
that fills it.  So the core keeps two generations of buffers, the one
behind the store it serves and the one behind the store that one retired,
and a close writes over the older: the store retired TWO closes ago.

Only when nobody reads it any more.  A caller of ``get_parameters()``, a
puller of an old version, the delta chain's base, a checkpoint writer, a
replication snapshot may each hold an array of a retired store (or a slice
of one, or a device transfer still reading it) for as long as they like.
The buffers are ``bytearray``s and the arrays ``np.frombuffer`` views of
them, so the interpreter itself counts who is left
(``utils/buffers.exported``, the rule the ring's frame pool and the
serve cache follow): a buffer with any view alive is its holder's, and the
close takes a new one in its place (``ps.close.fresh_bytes``).
"""

from __future__ import annotations

import numpy as np

from ..obs import stats as obs_stats
from ..utils.buffers import float32_over

# Bytes of close output that went to memory the core had to allocate
# (beside rpc.wire.fresh_bytes): the first two closes of a store, and
# every close whose buffer of two versions ago somebody still reads.
_obs_fresh_bytes = obs_stats.counter("ps.close.fresh_bytes")


class CloseBuffers:
    """Per tensor name, the buffer behind the store last published and
    the one behind the store it retired.  One close at a time (the
    caller's apply serialization): :meth:`take` the output arrays, then
    :meth:`publish` once the store holding them is swapped in, or not
    when it is not (a failed sweep, an ``initialize_parameters`` that
    landed first): what was taken is then simply taken again."""

    def __init__(self):
        self._live: dict[str, bytearray] = {}
        self._spare: dict[str, bytearray] = {}
        self._taken: dict[str, bytearray] = {}

    def take(self, shapes: dict[str, tuple]) -> dict[str, np.ndarray]:
        """A float32 array of its shape for every name to write the new
        values into: over the spare buffer of that name when it has the
        size and no view of it is alive, over a new one otherwise."""
        self._taken = {}
        out = {}
        for name, shape in shapes.items():
            buf, out[name] = float32_over(self._spare.get(name), shape,
                                          _obs_fresh_bytes)
            if buf is not None:
                self._taken[name] = buf
        return out

    def publish(self) -> None:
        """The arrays of the last :meth:`take` are the served store's
        now: their buffers become the live generation, the live one the
        spare, and the old spare goes (to whoever still holds it)."""
        self._spare, self._live, self._taken = self._live, self._taken, {}
