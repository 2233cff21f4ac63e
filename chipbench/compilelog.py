"""Compile seconds and persistent-cache hits from JAX's monitoring events,
and the peak device memory. Kept with the benchmark so that the yardstick
does not move when the program does."""
from __future__ import annotations

from typing import Any, Sequence


class CompileLog:
    """Backend compiles with their seconds, and persistent-cache hits and
    misses."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def count(self) -> int:
        """Programs compiled or loaded from the persistent cache so far."""
        return self.compiles + self.hits

    def line(self) -> str:
        return (f"so far: {self.compiles} compiles, {self.seconds:.1f} s; persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def memory_peak(devices: Sequence[Any]) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where the backend
    reports no memory statistics, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))
