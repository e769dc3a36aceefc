"""A later PR adds a configuration, a traffic mix and a metric by adding
files and BENCHMARK.json entries: the harness finds them by name, and no
file that is already there changes."""
import hashlib
import json
import os
import shutil
from pathlib import Path

from bench import harness
from bench.tests import tiny

NEW_METRIC = '''"""Prompt tokens a request brought, on average over the window."""


def read(ctx):
    reqs = ctx["system"].window_requests(ctx["window"])
    return sum(len(r.prompt) for r in reqs) / len(reqs) if reqs else None
'''


def _digest(tree: Path):
    return {str(p.relative_to(tree)): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in sorted(tree.rglob("*")) if p.is_file()}


def test_new_config_mix_and_metric_are_found(tmp_path):
    root = Path(harness.ROOT)
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(root / "src", tmp_path / "src")
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    conf = harness.merge(json.loads(
        (b / "configs" / "qwen2-0.5b-bf16.json").read_text()),
        tiny.LM["config"])
    conf["check"]["limit"]["max_logit_gap"] = 1.0
    (b / "configs" / "tiny-lm.json").write_text(json.dumps(conf))
    mix = harness.merge(json.loads(
        (b / "traffic" / "decode-backlog.json").read_text()),
        tiny.LM["traffic"])
    (b / "traffic" / "tiny-backlog.json").write_text(json.dumps(mix))
    (b / "layer_metrics" / "prompt_tokens.tiny.py").write_text(NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-lm", "source": "test",
                             "file": "bench/configs/tiny-lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-lm.backlog", "config": "tiny-lm",
                               "traffic": "tiny-backlog", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("tiny-lm.backlog")
    bench["per_layer"].append({"name": "prompt_tokens.tiny", "unit": "tokens",
                               "better": "lower", "source": "program_counter",
                               "layer": "serving", "moves": "tokens_per_s",
                               "workloads": ["tiny-lm.backlog"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    added = {"configs/tiny-lm.json",
             "traffic/tiny-backlog.json", "layer_metrics/prompt_tokens.tiny.py"}
    after = _digest(tmp_path / "bench")
    assert set(after) - set(before) == added
    assert {k: after[k] for k in before} == before

    import time
    res = harness.run("tiny-lm.backlog", 3, 2.0, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      root=tmp_path)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    per_layer = [m["name"] for m in harness.cell_metrics(
        bench, "tiny-lm.backlog", "per_layer")]
    assert per_layer == ["prompt_tokens.tiny"]
    mod = harness.load_module("layer_metrics", "prompt_tokens.tiny", b)
    assert callable(mod.read)
