"""The program's own spans in a profiler trace, beside the harness's.

``trace_reduce`` keeps the harness's host spans (``bench.*``). The program
writes its own (``engine.*``, through ``repro.obs.trace``) into the same
trace, as ``jax.profiler.TraceAnnotation``s on the device's clock. This
module reads both, each with the host thread it ran on, and adds to
``trace_reduce``'s reduction, whose keys it leaves as they are apart from
the names of the idle gaps:

- ``breakdown.idle_gaps`` name the innermost span at the gap's midpoint
  among ``bench.*`` and ``engine.*`` (``trace_reduce``'s own rule, given
  the program's spans too);
- ``host_idle``: span name -> device-idle seconds inside ``bench.window``
  during which that span is the innermost open span on the thread that
  holds ``bench.drain`` (:data:`OUTSIDE` when none is open), each idle
  interval split across the spans it overlaps; the values sum to
  ``window_s - busy_s``;
- ``innermost_s``: the same split of the whole window, idle or busy (the
  drain's self time is ``innermost_s["engine.drain"]``);
- ``spans``: per span name, the runs that end inside the window and their
  seconds clipped to it;
- ``long_gaps``: every idle gap of at least ``long_gap_s``, in window
  order, as [name at its midpoint, seconds, start from the window's
  start], with the innermost spans it overlaps on the drain thread.

Nothing in the harness calls this yet. ``python3 -m bench.host_spans``
runs one cell with ``--trace 1`` through ``bench.harness.run`` and prints
the result line with these keys and :func:`readings` under
``"host_spans"``, for use while the harness's reduction reads
``bench.*`` alone. That run also marks each full (generation 2) garbage
collection of the interpreter as a ``python.gc`` span, on the thread
that ran it, so a long idle gap can be told apart from a collection.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce

PREFIXES = ("bench.", "engine.")
GC_SPAN = "python.gc"
OUTSIDE = "outside spans"
DRAIN = ("bench.drain", "engine.drain")   # the thread that holds either

Segment = Tuple[float, float, str]


def read_events(pd, prefixes=PREFIXES) -> dict:
    """``trace_reduce.read_events`` with host spans of every prefix in
    ``prefixes``, and under ``"host"`` the same spans as (name, start,
    end, thread), a thread being (host plane name, line index)."""
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                          (plane.name, k)) for ev in line.events
                         if ev.name.startswith(prefixes)]
    return {"spans": [h[:3] for h in host], "host": host,
            "devices": trace_reduce.read_events(pd)["devices"]}


def innermost(spans) -> List[Segment]:
    """Sorted, disjoint (start, end, name) pieces of the time covered by
    ``spans`` (name, start, end) of one thread, each named after the
    innermost span open over it."""
    segs: List[Segment] = []
    stack: List[Tuple[str, float]] = []    # (name, end), innermost last
    t: Optional[float] = None

    def upto(x):
        nonlocal t
        if t is not None and x > t and stack:
            segs.append((t, x, stack[-1][0]))
        t = x if t is None else max(t, x)

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append((name, e))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    return segs


def split(intervals, segs: List[Segment]) -> Dict[str, float]:
    """Length of sorted, disjoint ``intervals`` under each segment's name,
    :data:`OUTSIDE` for the part no segment covers."""
    out: Dict[str, float] = {}
    j = 0
    for s, e in intervals:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < e:
            ov = min(e, segs[k][1]) - max(s, segs[k][0])
            if ov > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + ov
                covered += ov
            k += 1
        if e - s > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (e - s - covered)
    return out


def idle_intervals(ops, lo: float, hi: float):
    """Sorted intervals of [lo, hi] in which none of ``ops`` runs."""
    busy = trace_reduce.union([c for _, s, e, _ in ops
                               if (c := trace_reduce.clip((s, e), lo, hi))])
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def reduce_events(events: dict, top: int = 10,
                  long_gap_s: float = 0.05) -> dict:
    """``trace_reduce.reduce_events`` over every kept span, plus
    ``host_idle``, ``innermost_s``, ``spans`` and ``long_gaps``."""
    out = trace_reduce.reduce_events(
        {"spans": events["spans"], "devices": events["devices"]}, top)
    host = events["host"]
    win = [h for h in host if h[0] == "bench.window"]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        allops = [o for d in events["devices"].values() for o in d["ops"]]
        lo, hi = min(o[1] for o in allops), max(o[2] for o in allops)
    thread = next((h[3] for name in DRAIN for h in host if h[0] == name),
                  None)
    segs = innermost([(n, s, e) for n, s, e, th in host
                      if th == thread and n != "bench.window"])
    segs = [(c[0], c[1], n) for s, e, n in segs
            if (c := trace_reduce.clip((s, e), lo, hi))]
    n_dev = len(events["devices"])
    host_idle: Dict[str, float] = {}
    gaps = []
    for dev in events["devices"].values():
        idle = idle_intervals(dev["ops"], lo, hi)
        gaps += idle
        for name, t in split(idle, segs).items():
            host_idle[name] = host_idle.get(name, 0.0) + t * 1e-9 / n_dev
    spans: Dict[str, Dict[str, float]] = {}
    for n, s, e, th in host:
        c = trace_reduce.clip((s, e), lo, hi)
        if c is None or n == "bench.window":
            continue
        d = spans.setdefault(n, {"runs": 0, "seconds": 0.0})
        d["runs"] += e <= hi
        d["seconds"] += (c[1] - c[0]) * 1e-9
    long_gaps = []
    for s, e in sorted(g for g in gaps if (g[1] - g[0]) * 1e-9 >= long_gap_s):
        under = split([(s, e)], segs)
        long_gaps.append([gap_name(events["spans"], s, e), (e - s) * 1e-9,
                          (s - lo) * 1e-9,
                          {k: v * 1e-9 for k, v in sorted(
                              under.items(), key=lambda kv: -kv[1])}])
    out["host_idle"] = host_idle
    out["innermost_s"] = {k: v * 1e-9 for k, v in
                          split([(lo, hi)], segs).items()}
    out["spans"] = spans
    out["long_gaps"] = long_gaps
    return out


def gap_name(spans, s: float, e: float) -> str:
    """The innermost span at the gap's midpoint, by ``trace_reduce``'s rule
    (the shortest span holding it, the window excepted)."""
    t = (s + e) / 2
    inner = [sp for sp in spans if sp[0] != "bench.window"
             and sp[1] <= t <= sp[2]]
    return (min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner
            else "outside bench spans")


def readings(red: dict) -> Dict[str, Optional[float]]:
    """Host time per decode round by phase, in ms (span seconds inside the
    window over the decode rounds that ended in it), and each phase's
    share of the window's device-idle time, in percent of the window."""
    spans, win = red["spans"], red["window_s"]
    rounds = spans.get("engine.decode_round", {}).get("runs", 0)
    out: Dict[str, Optional[float]] = {}
    for phase in ("admit", "upload", "decode_round", "sample"):
        s = spans.get(f"engine.{phase}")
        out[f"{phase}_ms"] = (s["seconds"] * 1e3 / rounds
                              if s and rounds else None)
    for name, t in red["host_idle"].items():
        out[f"idle_share.{name}"] = 100.0 * t / win if win else None
    drain = red["innermost_s"].get("engine.drain")
    out["drain_self_share"] = (100.0 * drain / win
                               if drain is not None and win else None)
    return out


@contextlib.contextmanager
def gc_spans():
    """Inside, each full (generation 2) garbage collection is a
    :data:`GC_SPAN` profiler annotation on the thread that ran it."""
    from jax.profiler import TraceAnnotation
    open_: list = []

    def mark(phase, info):
        if info["generation"] < 2:
            return
        if phase == "start":
            open_.append(TraceAnnotation(GC_SPAN))
            open_[-1].__enter__()
        elif open_:
            open_.pop().__exit__(None, None, None)

    gc.callbacks.append(mark)
    try:
        yield
    finally:
        gc.callbacks.remove(mark)


def main(argv=None) -> int:
    """Run one cell traced; print its result line with ``"host_spans"``."""
    import argparse
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    from bench import harness
    kept: dict = {}

    def reduce_and_keep(trace_dir):
        """The harness's reduction, from one read of the trace."""
        ev = read_events(ProfileData.from_file(
            trace_reduce.find_trace(trace_dir)), PREFIXES + (GC_SPAN,))
        kept.update(reduce_events(ev))
        return trace_reduce.reduce_events(
            {"spans": [s for s in ev["spans"] if s[0].startswith("bench.")],
             "devices": ev["devices"]})

    trace_reduce.reduce_dir = reduce_and_keep
    try:
        with gc_spans():
            res = harness.run(args.workload, args.seed, args.seconds, True,
                              t_start=t_start)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    res["host_spans"] = {k: kept[k] for k in (
        "host_idle", "innermost_s", "spans", "long_gaps")}
    res["host_spans"]["idle_gaps"] = kept["breakdown"]["idle_gaps"]
    res["host_spans"]["readings"] = readings(kept)
    print(json.dumps(harness.finite(res), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
