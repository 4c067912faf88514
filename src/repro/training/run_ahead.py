"""How far a trainer's host may run ahead of its device.

A step that reads nothing from the device returns as soon as it is enqueued,
so the host would queue step after step, each holding its own buffers on the
device, and the memory peak would grow with the queue.  ``RunAhead`` keeps at
most two steps in flight: after step t is dispatched it waits for step t-1
(never for step t, and it reads no value), so the next batch's copy and
dispatch still overlap the device's step.

This wait is the one host sync of a trainer step with tracing off; it lives
alone in this module so the reviewed ``no-host-sync`` suppression names it.
"""
from __future__ import annotations

from typing import Any

import jax


class RunAhead:
    def __init__(self):
        self._last: Any = None  # outputs of the last dispatched step

    def dispatched(self, out: Any) -> None:
        """Record step t's outputs; wait until step t-1's are ready."""
        last, self._last = self._last, out
        if last is not None:
            jax.block_until_ready(last)
