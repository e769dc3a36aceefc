"""The harness end to end on the CPU at tiny sizes, past the chip check:
a sound run is correct, and each fault a serving cell can have, planted
where the answer is produced, turns ``correct`` false."""
from bench.tests import tiny

LM_CELL = "qwen2-0.5b-bf16.decode-backlog"
CHAT_CELL = "qwen2-0.5b-bf16.chat-prefix"


def _limits(res):
    return {k: c["limit"] for k, c in res["checks"].items()}


def test_lm_backlog_sound_run_is_correct():
    res = tiny.run(LM_CELL, tiny.LM)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_lm_token_altered_where_produced_fails(monkeypatch):
    from repro.serve.engine import Engine
    pick = Engine._pick
    calls = {"n": 0}

    def wrong(self, logits_row, req):
        calls["n"] += 1
        t = pick(self, logits_row, req)
        return (t + 1) % logits_row.shape[-1] if calls["n"] % 5 == 0 else t

    monkeypatch.setattr(Engine, "_pick", wrong)
    res = tiny.run(LM_CELL, tiny.LM)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_chat_sound_run_and_tails():
    res = tiny.run(CHAT_CELL, tiny.CHAT, seconds=3.0)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert set(m) == {"tokens_per_s", "ttft_p95_ms", "tpot_p95_ms",
                      "setup_s"}
    assert 0 < m["ttft_p95_ms"]["value"] < 1e6


def test_lm_control_fails():
    """The control: the reference in int8, rounded to nearest, in the
    program's place misses the limit at this size; the program does not."""
    sound = tiny.run(LM_CELL, tiny.CONTROL, seconds=3.0)
    assert sound["correct"], sound["checks"]
    res = tiny.run(LM_CELL, tiny.CONTROL, seconds=3.0, control=True)
    assert not res["correct"]
    mean = res["checks"]["mean_logit_gap"]
    assert mean["value"] > mean["limit"]
    # the program's own numbers ride along, as in its sound run
    assert res["program_checks"]["mean_logit_gap"]["value"] < mean["limit"]
