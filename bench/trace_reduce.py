"""From a profiler trace to the numbers the per-layer metrics read.

A trace (``*.xplane.pb``, written by ``jax.profiler``) holds one plane per
chip (``/device:TPU:<n>``) whose "XLA Ops" line has an event per device
operation and whose "XLA Modules" line has an event per program run, and
host planes whose lines carry the harness's own spans (``bench.*``, from
``jax.profiler.TraceAnnotation``) on the same clock.

Everything is clipped to the ``bench.window`` span. Busy time is the union
of the operations' intervals, so overlapping or nested events never count
twice and busy can never exceed the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
Interval = Tuple[float, float]


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
CUSTOM = re.compile(r"^%?([\w.-]+) = (.*?) custom-call\((.*?)\)(?:,|$)")


def shapes(text: str) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    return tuple((dt, tuple(int(d) for d in dims.split(",") if d))
                 for dt, dims in SHAPE.findall(text))


def op_info(text: str):
    """(short name, kernel) of one "XLA Ops" event, whose name is the HLO
    instruction. ``kernel`` is None for XLA's own ops; for a custom call
    (a Pallas kernel) it is (wrapper name, output shapes, operand shapes),
    the wrapper name being the instruction's without its ".N" suffix."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%")
    first = SHAPE.search(rest)
    short = f"{name} {first.group(0)}" if first else name
    m = CUSTOM.match(text)
    if not m:
        return short, None
    return short, (re.sub(r"\.\d+$", "", m.group(1)), shapes(m.group(2)),
                   shapes(m.group(3)))


def read_events(pd) -> dict:
    """Plain tuples out of a ``jax.profiler.ProfileData``: host spans
    named ``bench.*``, and per device plane its ops (short name, start,
    end, kernel) and program runs."""
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops_line = lines.get("XLA Ops")
            if ops_line is None:
                continue
            ops = []
            for ev in ops_line.events:
                short, kernel = op_info(ev.name)
                ops.append((short, ev.start_ns, ev.start_ns + ev.duration_ns,
                            kernel))
            mods = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else [])]
            devices[plane.name] = {"ops": ops, "modules": mods}
    return {"spans": spans, "devices": devices}


def program_name(module: str) -> str:
    """A program run's event name without its run id: "jit_f(12)" -> "jit_f"."""
    return re.sub(r"\(\d+\)$", "", module).strip()


def reduce_events(events: dict, top: int = 10) -> dict:
    """Busy and idle time, time per program and per Pallas kernel call
    shape, the top device ops and the longest idle gaps, all inside the
    ``bench.window`` span."""
    spans = events["spans"]
    win = [s for s in spans if s[0] == "bench.window"]
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        allops = [o for d in devices.values() for o in d["ops"]]
        lo, hi = min(o[1] for o in allops), max(o[2] for o in allops)
    window_s = (hi - lo) * 1e-9
    busy_total = 0.0
    programs: Dict[str, Dict[str, float]] = {}
    kernels: Dict[tuple, Dict[str, float]] = {}
    ops_time: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices.values():
        ops = [(n, *c, cc) for n, s, e, cc in dev["ops"]
               if (c := clip((s, e), lo, hi))]
        busy = union([(s, e) for _, s, e, _ in ops])
        busy_total += sum(e - s for s, e in busy)
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for n, s, e, kernel in ops:
            ops_time[n] = ops_time.get(n, 0.0) + (e - s)
            if kernel:
                k = kernels.setdefault(kernel, {"calls": 0, "seconds": 0.0})
                k["calls"] += 1
                k["seconds"] += (e - s) * 1e-9
        mods = sorted((program_name(n), *c) for n, s, e in dev["modules"]
                      if (c := clip((s, e), lo, hi)))
        starts = [m[1] for m in mods]
        for name, s, e in mods:
            p = programs.setdefault(name, {"runs": 0, "seconds": 0.0,
                                           "custom_calls": 0,
                                           "custom_call_seconds": 0.0})
            p["runs"] += 1
            p["seconds"] += (e - s) * 1e-9
        for n, s, e, cc in ops:
            if not cc:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mods[i][2] >= e:
                p = programs[mods[i][0]]
                p["custom_calls"] += 1
                p["custom_call_seconds"] += (e - s) * 1e-9
    n_dev = len(devices)
    busy_s = busy_total * 1e-9 / n_dev

    def host_at(t: float) -> str:
        inner = [sp for sp in spans if sp[0] != "bench.window"
                 and sp[1] <= t <= sp[2]]
        return min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner \
            else "outside bench spans"

    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[host_at((s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:top]]
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s, "busy_s": busy_s, "programs": programs,
        "kernels": [{"kernel": k[0], "out": k[1], "ins": k[2], **v}
                    for k, v in kernels.items()],
        "breakdown": {
            "device_ops": [[n, t * 1e-9 / n_dev] for n, t in top_ops],
            "idle_gaps": idle,
        },
    }


def reduce_dir(trace_dir) -> dict:
    from jax.profiler import ProfileData
    return reduce_events(read_events(ProfileData.from_file(
        find_trace(trace_dir))))
