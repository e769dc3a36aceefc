"""Helpers the systems share: seeds, the profiler switch, the window."""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, Optional


def seed_key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size (64-bit seeds fold in their high
    word), one independent key per ``stream``."""
    import jax
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


class Spans:
    """Host spans written into the profiler's own trace (one clock with
    the device events) when a traced run is on; free otherwise."""

    def __init__(self, enabled: bool):
        self.enabled = enabled

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """What a measured window leaves for the metrics and the check:
    its edges on the host clock, a snapshot of the counters at each edge,
    and whatever the system records (``extra``)."""
    t0: float = 0.0
    t1: float = 0.0
    start: Dict[str, Any] = dataclasses.field(default_factory=dict)
    end: Dict[str, Any] = dataclasses.field(default_factory=dict)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, name: str) -> float:
        return self.end[name] - self.start[name]


def sleep_until(t: float, stop: Optional[threading.Event] = None) -> None:
    """Sleep until ``perf_counter() >= t`` (early out when ``stop`` is set)."""
    while True:
        left = t - time.perf_counter()
        if left <= 0 or (stop is not None and stop.is_set()):
            return
        time.sleep(min(left, 0.05))
