"""Tails over every request (failures are misses), and the window edges
the metric readers use."""
import math
import types

import numpy as np

from bench import harness, stats
from bench.common import Window


def test_percentile_nearest_rank_and_misses():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    # five misses in a hundred: the 95th percentile still reads a sample...
    assert stats.percentile(xs[:95] + [math.inf] * 5, 95) == 95
    # ...six make it a miss
    assert stats.percentile(xs[:94] + [math.inf] * 6, 95) == math.inf


def _req(uid, first, finish, admit, n, status="ok"):
    return types.SimpleNamespace(uid=uid, first_token_t=first,
                                 finish_t=finish, admit_t=admit,
                                 out_tokens=[0] * n, status=status)


class _Sys:
    def window_requests(self, win):
        return [r for r, d in zip(win.extra["reqs"], win.extra["due_abs"])
                if win.t0 <= d < win.t1]


def _ctx():
    # due at 0.5 (before), 1.0 .. 10.0 (inside [1, 11)), 11.0 (after)
    due = [0.5] + [1.0 + i for i in range(10)] + [11.0]
    reqs = [_req(i, d + 0.1, d + 1.1, d + 0.05, 11) for i, d in enumerate(due)]
    reqs[3].status = "error"                 # a failure inside the window
    reqs[0].first_token_t = 100.0            # a slow one outside it
    win = Window(t0=1.0, t1=11.0, start={"tokens_out": 5.0},
                 end={"tokens_out": 105.0},
                 extra={"reqs": reqs, "due_abs": np.array(due),
                        "open_loop": True})
    return {"window": win, "system": _Sys()}


def test_ttft_tail_counts_failures_and_only_the_window():
    read = harness.load_module("e2e_metrics", "ttft_p95_ms").read
    ctx = _ctx()
    # 10 requests due in the window, one failed: p95 over 10 is the max,
    # which is the miss; the slow request due before the window is not in it
    assert read(ctx) == math.inf
    ctx["window"].extra["reqs"][3].status = "ok"
    assert math.isclose(read(ctx), 100.0)


def test_tpot_and_rate_use_the_window_edges():
    ctx = _ctx()
    ctx["window"].extra["reqs"][3].status = "ok"
    tpot = harness.load_module("e2e_metrics", "tpot_p95_ms").read(ctx)
    assert math.isclose(tpot, 100.0)         # (1.1 - 0.1) s / 10 tokens
    rate = harness.load_module("e2e_metrics", "tokens_per_s").read(ctx)
    assert math.isclose(rate, 100.0 / 10.0)
    qw = harness.load_module("layer_metrics", "queue_wait_p95_ms.chat").read
    assert math.isclose(qw(ctx), 50.0)


def test_compile_counter_counts_only_while_armed():
    import jax
    import jax.numpy as jnp
    c = harness.CompileCounter()
    jax.jit(lambda x: x * 5 - 1)(jnp.ones(3)).block_until_ready()
    assert c.count == 0
    c.armed = True
    jax.jit(lambda x: x * 7 + 2)(jnp.ones(3)).block_until_ready()
    assert c.count > 0
