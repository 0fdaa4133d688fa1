"""CPython's cycle collector and the window.

The benchmark keeps what the oracle needs — every round's requests and
answers, every op's future — until the window has closed, which no
deployment does. A full (generation-2) collection walks all of it: 14 a
window and up to 255 ms each, on whichever thread allocated last (my
chip runs, PR 26). ``settle()`` is what a driver calls as its log grows:
it collects the young generations, so the program's own garbage still
goes, and then freezes the survivors, so that what is kept for the
oracle leaves the collector's sight. The harness freezes set-up's
objects the same way before the window and unfreezes after it.

``GcWatch`` counts, from ``gc.callbacks``, the collections of each
generation between ``start()`` and ``stop()`` and the seconds they
took: the ``samples`` line says how quiet the window was.
"""

from __future__ import annotations

import gc
import time


def settle() -> None:
    gc.collect(1)
    gc.freeze()


class GcWatch:
    def __init__(self):
        self.count = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self._t0 = None

    def _note(self, phase: str, info: dict) -> None:
        # collections do not nest: one runs at a time, under the GIL
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            g = info["generation"]
            self.count[g] += 1
            self.pause_s[g] += time.perf_counter() - self._t0
            self._t0 = None

    def start(self) -> None:
        gc.callbacks.append(self._note)

    def stop(self) -> dict:
        """What the window saw; safe to call twice."""
        if self._note in gc.callbacks:
            gc.callbacks.remove(self._note)
        return {"gc_collections_in_window": list(self.count),
                "gc_gen2_in_window": self.count[2],
                "gc_gen2_pause_ms": self.pause_s[2] * 1e3,
                "gc_pause_ms": sum(self.pause_s) * 1e3}
