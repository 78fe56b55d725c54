"""The process's long-lived heap, out of the collector's sight.

A served engine keeps a million and more gc-tracked objects that never
die: the package's modules, the tokenizer, the config tree, every traced
and lowered program's jaxpr, jax's caches. A full (generation 2)
collection walks all of them to find nothing, and under load one comes
every few seconds: a third of a second in which the server dispatches
nothing (PERF.md section 7, "Pauses"). `gc.freeze()` moves what is alive
to the collector's permanent generation, which no collection walks.

The program freezes where it knows that what it has just built lives as
long as the server, and nowhere else; no flag or environment variable
steers it:

    engine_stands()   where `LLMEngine.__init__` marks the boot complete:
                      unfreeze -> collect -> freeze. What an earlier
                      engine of this process froze is thawed first (the
                      process's singletons have let go of that engine by
                      now), so a dropped engine's cycles are collected
                      and the permanent generation holds what is alive
                      now, not what ever was; and the boot's own cyclic
                      garbage is freed, not pinned
    first_call_done() behind every first call of a program bucket
                      (`CompileTracker.call`, a miss): collect -> freeze.
                      A server's 20-30 first calls come AFTER the boot
                      (probes, warm set, ramp) and their jaxprs are the
                      largest part of the heap. The collection walks only
                      what was made since the last freeze, so it is short
                      beside the compile it follows

Objects frozen while alive are still freed by reference count when they
die; only a frozen CYCLE stays until the next `engine_stands` (bounded:
the requests in flight at a first call).

A `gc.callbacks` hook (`watch()`, from the top of `LLMEngine.__init__`;
two `perf_counter` reads a collection, no lock: a collection never
overlaps another) keeps seconds and counts by generation.
`engine/metrics.py` exports them as
`intellillm_gc_pause_seconds_total{generation}`,
`intellillm_gc_collections_total{generation}` and
`intellillm_gc_frozen_objects`; `/health/detail` has them under `heap`.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

GENERATIONS = (0, 1, 2)


class Heap:

    def __init__(self) -> None:
        self._lock = threading.Lock()       # the freeze points; not the hook
        self._t0 = 0.0
        self.pause_s: List[float] = [0.0, 0.0, 0.0]
        self.collections: List[int] = [0, 0, 0]
        self._exported: Tuple[List[float], List[int]] = ([0.0] * 3, [0] * 3)
        self.frozen_objects = 0
        self.freezes = 0
        self.last_freeze: Optional[Dict[str, Any]] = None

    # --- the hook ----------------------------------------------------------

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            generation = info["generation"]
            self.pause_s[generation] += time.perf_counter() - self._t0
            self.collections[generation] += 1

    def watch(self) -> None:
        with self._lock:
            if self._on_gc not in gc.callbacks:
                gc.callbacks.append(self._on_gc)

    # --- the freeze points -------------------------------------------------

    def engine_stands(self) -> None:
        self._freeze("engine_stands", thaw=True)

    def first_call_done(self) -> None:
        self._freeze("first_call", thaw=False)

    def _freeze(self, at: str, thaw: bool) -> None:
        with self._lock:
            t0 = time.perf_counter()
            if thaw:
                gc.unfreeze()
                self.frozen_objects = 0
            unreachable = gc.collect()
            # Counted as they go in (`gc.get_freeze_count()` walks the
            # whole permanent generation: 0.1 s at a million and a half
            # objects, behind every first call). One that dies later by
            # reference count stays counted until the next thaw.
            self.frozen_objects += len(gc.get_objects())
            gc.freeze()
            self.freezes += 1
            self.last_freeze = {
                "at": at, "seconds": time.perf_counter() - t0,
                "unreachable": unreachable,
                "frozen_objects": self.frozen_objects,
                # the collector's totals up to here: behind a server's last
                # first call, what its set-up cost
                **self._totals(),
            }

    # --- reading -----------------------------------------------------------

    def drain(self) -> List[Tuple[int, float, int]]:
        """(generation, seconds, collections) since the last drain, for
        the exporter's counters; a generation with no collection since is
        left out."""
        out = []
        seconds, counts = self._exported
        for g in GENERATIONS:
            s, n = self.pause_s[g], self.collections[g]
            if n != counts[g] or s != seconds[g]:
                out.append((g, s - seconds[g], n - counts[g]))
                seconds[g], counts[g] = s, n
        return out

    def _totals(self) -> Dict[str, Dict[str, float]]:
        return {
            "gc_pause_s": {str(g): self.pause_s[g] for g in GENERATIONS},
            "gc_collections": {str(g): self.collections[g]
                               for g in GENERATIONS},
        }

    def snapshot(self) -> Dict[str, Any]:
        return {"frozen_objects": self.frozen_objects,
                "freezes": self.freezes, "last_freeze": self.last_freeze,
                **self._totals()}


_HEAP: Optional[Heap] = None
_HEAP_LOCK = threading.Lock()


def get_heap() -> Heap:
    global _HEAP
    if _HEAP is None:
        with _HEAP_LOCK:
            if _HEAP is None:
                _HEAP = Heap()
    return _HEAP
