"""What the chip holds, from the runtime's own counters. Nothing is added
across moments and nothing is cut off at a limit.

``device.memory_stats()`` keeps two books (read on the v5e in PR 23, PERF.md
section 6): ``bytes_in_use``, the buffers (arguments and results), and
``bytes_reserved``, the scratch of the programs that are loaded, which stays
reserved between their executions. Each has a peak of its own, and the two
peaks come at different moments (set-up's reference check holds the most
buffers; the cell's program reserves the most scratch), so their sum is no
reading at all. Two readings are each of one moment:

- ``peak_bytes_in_use``: the allocator's own peak of buffers, which leaves
  out whatever scratch was reserved just then;
- ``bytes_in_use + bytes_reserved`` of one ``memory_stats()`` call, taken
  where a driver calls :meth:`MemoryWatch.sample` (the window's two edges,
  and the run's end). It misses a buffer that lives only inside a call, as
  the serve engine's second copy of its cache does.

The chip held at least the larger of the two at some moment, and that is
``memory_peak_bytes``. Both are printed beside it, with the limit.
"""

from __future__ import annotations


class MemoryWatch:
    def __init__(self, devices):
        self.devices = list(devices)
        self.held = [0] * len(self.devices)   # most of in_use + reserved

    def sample(self) -> None:
        for i, dev in enumerate(self.devices):
            stats = dev.memory_stats() or {}
            self.held[i] = max(self.held[i],
                               int(stats.get("bytes_in_use", 0))
                               + int(stats.get("bytes_reserved", 0)))

    def report(self) -> dict | None:
        """The fullest chip's readings; ``None`` where the runtime keeps no
        such counters (a CPU)."""
        self.sample()
        rows = []
        for dev, held in zip(self.devices, self.held):
            stats = dev.memory_stats() or {}
            if "peak_bytes_in_use" not in stats:
                return None
            buffers = int(stats["peak_bytes_in_use"])
            rows.append({
                "memory_peak_bytes": max(buffers, held),
                "memory_peak_buffers_bytes": buffers,
                "memory_held_bytes": held,
                "memory_limit_bytes": int(stats.get("bytes_limit", 0))})
        return max(rows, key=lambda r: r["memory_peak_bytes"])
