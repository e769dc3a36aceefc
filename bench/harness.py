"""One benchmark run: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the configuration as it is run; its
  ``"system"`` names ``systems/<system>.py``, the code that builds and
  drives that kind of system.
- ``configs/<reference>.py``, named by the configuration's
  ``"reference"``: seeded weights and the plain reference.
- ``traffic/<mix>.json``: the parameters ``gen.py`` reads.
- ``e2e_metrics/<metric>.py`` and ``layer_metrics/<metric>.py``: a
  ``read(ctx)`` that returns the metric's value, or None when the run has
  nothing to read it from.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


# ------------------------------------------------------------- discovery --

def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(kind: str, name: str, ext: str, bench: Path = BENCH) -> Path:
    """``<bench>/<kind>/<name><ext>``; a name that has no file is an error."""
    path = bench / kind / f"{name}{ext}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return path


def load_module(kind: str, name: str, bench: Path = BENCH):
    path = find(kind, name, ".py", bench)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str, bench: Path = BENCH) -> dict:
    with open(find(kind, name, ".json", bench)) as f:
        return json.load(f)


def merge(base: dict, over: Optional[dict]) -> dict:
    """``base`` with ``over`` merged in, nested dicts key by key."""
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def cell_metrics(bench: dict, workload: str, section: str) -> list:
    """Entries of ``section`` ("end_to_end" or "per_layer") reported in
    ``workload``: those without a ``workloads`` key, and those listing it."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------- device --

def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of JAX's devices; raises :class:`NoChip`
    off the TPU (never falls back), with fewer chips than the cell needs,
    or when Pallas would run in interpret mode."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not require_tpu:
        return info
    if info["platform"] != "tpu":
        raise NoChip(f"JAX's default platform is {info['platform']!r}, not "
                     "'tpu': the benchmark measures the chip only")
    if info["count"] < chips:
        raise NoChip(f"{info['count']} chip(s) found, the cell needs {chips}")
    from repro.kernels.common import use_interpret
    if use_interpret():
        raise NoChip("Pallas kernels would run in interpret mode")
    return info


def enable_cache():
    """The program's persistent compilation cache (inside the checkout
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), keeping every program
    however fast it compiled, so a second run loads all of them."""
    import jax
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts JAX's trace, compile and cache-load events between ``arm``
    and ``disarm``: a window that compiles shows a non-zero count."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


# ------------------------------------------------------------------- run --

def say(**fields):
    print(json.dumps(fields), file=sys.stderr, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, control: bool = False,
        overrides: Optional[dict] = None, root: Path = ROOT,
        bench: Optional[dict] = None) -> dict:
    """Run one cell and return its result line (as a dict).

    ``overrides`` merges into the loaded pieces (``{"config": {...},
    "traffic": {...}}``); ``control`` judges the control, the reference
    one precision lower put in the program's place, whose run must fail
    the check (the program's own numbers go under ``program_checks``);
    ``bench`` replaces ``<root>/BENCHMARK.json``.
    """
    bench = load_benchmark(root) if bench is None else bench
    where = root / "bench"
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    overrides = overrides or {}
    conf = merge(load_json("configs", cell["config"], where),
                 overrides.get("config"))
    conf["name"] = cell["config"]
    mix = merge(load_json("traffic", cell["traffic"], where),
                overrides.get("traffic"))
    say(phase="setup", workload=workload, seed=seed, seconds=seconds,
        trace=int(trace), control=int(control))

    sys.path.insert(0, str(root / "src"))
    cache = enable_cache() if require_tpu else None
    device = device_info(cell["chips"], require_tpu)
    say(phase="device", compile_cache=cache, **device)

    from bench.common import Spans
    import jax
    spans = Spans(trace)
    system_mod = load_module("systems", conf["system"], where)
    ref = load_module("configs", conf["reference"], where)
    system = system_mod.System(conf, ref, mix, seed, seconds, spans)
    system.build()
    system.warm()
    compiles = CompileCounter()
    trace_dir = TRACE_DIR / f"{workload}.{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only: bench.*
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles.armed = True
    try:
        win = system.run_window()
    finally:
        compiles.armed = False
        if trace:
            jax.profiler.stop_trace()
    setup_s = win.t0 - t_start
    say(phase="window", seconds=win.seconds, compiles_in_window=compiles.count,
        queued_at_start=win.start.get("queued"),
        queued_at_end=win.end.get("queued"),
        **{k: v for k, v in win.extra.items() if k.startswith("lag")})
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")

    reduced = None
    if trace:
        from bench import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    from bench import peaks
    ctx = {"window": win, "system": system, "conf": conf, "mix": mix,
           "setup_s": setup_s, "trace": reduced,
           "peaks": peaks.peaks_for(device["kind"]) if trace else None}
    section, kind = (("per_layer", "layer_metrics") if trace
                     else ("end_to_end", "e2e_metrics"))
    metrics = {}
    for m in cell_metrics(bench, workload, section):
        value = load_module(kind, m["name"], where).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = system.tally(win)

    system.free()
    t = time.perf_counter()
    checks = system.check(win, control=control)
    program = system.check(win) if control else None
    say(phase="check", seconds=time.perf_counter() - t)
    correct = all(lim is not None and v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    if program is not None:
        result["program_checks"] = {k: {"value": v, "limit": lim}
                                    for k, (v, lim) in program.items()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control (the reference one precision "
                         "lower in the program's place; its check must "
                         "fail); not a benchmark run")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}: run from the root "
              "of a checkout", file=sys.stderr)
        return 2
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start,
                     control=bool(args.control))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(finite(result), allow_nan=False), flush=True)
    return 0


def finite(x):
    """``x`` with every infinite float as the largest finite one (a tail
    with misses in it) and NaN as None, so the line is plain JSON."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None if math.isnan(x) else math.copysign(sys.float_info.max, x)
    return x
