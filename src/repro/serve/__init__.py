"""Serving: continuous-batching LM engine over a fixed (max_batch, max_len)
KV budget (legacy static drain scheduler as baseline; engine.Engine /
EXPERIMENTS.md §Serving) with contiguous or paged KV backing — the paged
layout pools fixed-size pages with hash-based prefix reuse
(arena.BlockPool / EXPERIMENTS.md §Paged-KV) — plus the CNN microbatching
engine that admits queued image requests into batched CompiledPlan rounds
(cnn.CNNEngine / EXPERIMENTS.md §Throughput). Both engines degrade
instead of dying under faults — every request ends in a terminal status
(ok | timeout | error | shed), with load shedding raising QueueFullError
under shed_policy="reject" (repro.faults / EXPERIMENTS.md §Resilience)."""
from .cnn import CNNEngine, CNNServeConfig, ImageRequest
from .engine import (BlockPool, Engine, QueueFullError, Request,
                     ServeConfig)

__all__ = ["BlockPool", "Engine", "QueueFullError", "Request",
           "ServeConfig", "CNNEngine", "CNNServeConfig", "ImageRequest"]
