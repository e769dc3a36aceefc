"""The LM serving engine (``repro.serve.Engine``) under a traffic mix.

The window drives ``Engine.run_until_drained`` from the main thread, the
way a serving loop would: whenever requests are queued it drains them.
One clock thread keeps time: it submits an open loop's requests at
their due times, reads the counters at the window's edges and, once the
window is over, ends the requests still running by giving them a deadline
that has passed (the engine retires them at its next round boundary).

A closed loop submits its whole backlog before the window, so the queue
never runs dry; the window opens once the first decode round has run (the
batch is full) and the run fails if fewer than ``queue_depth`` requests
are still queued when it closes.
"""
from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from bench import gen
from bench.common import Spans, Window, seed_key, sleep_until


class BacklogRanDry(RuntimeError):
    """The closed loop's queue fell below its depth inside the window."""


def model_config(conf: dict):
    """The program's ``ModelConfig`` for the sizes in ``conf``."""
    from repro.configs.base import ModelConfig
    m = conf["model"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        qkv_bias=conf["attention_bias"],
        tied_embeddings=conf["tie_word_embeddings"],
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        act=conf["hidden_act"], param_dtype=m["param_dtype"],
        compute_dtype=m["compute_dtype"])


class System:
    def __init__(self, conf: dict, ref, mix: dict, seed: int,
                 seconds: float, spans: Spans):
        self.conf, self.ref, self.mix = conf, ref, mix
        self.seed, self.seconds, self.spans = seed, seconds, spans
        self.scfg_kw = dict(conf["serve"])
        self.dims = ref.dims(conf)

    # ---------------------------------------------------------- set-up --

    def build(self):
        from repro.serve import Engine, ServeConfig
        import jax
        self.mcfg = model_config(self.conf)
        if self.mcfg.head_dim != self.conf["model"]["head_dim"]:
            raise ValueError(f"head_dim {self.mcfg.head_dim} != "
                             f"{self.conf['model']['head_dim']}")
        self.weights = self.ref.make_weights(self.conf, seed_key(self.seed))
        jax.block_until_ready(self.weights)
        self.engine = Engine(self.mcfg, self.weights,
                             ServeConfig(**self.scfg_kw))
        self.items = gen.lm_items(self.mix, self.seed, self.dims["vocab"],
                                  self.seconds)

    def _shape_classes(self):
        """Prompt lengths that reach every program shape the traffic uses:
        one per (prefill bucket, pages written) on a prefix miss, and one
        per (suffix bucket, fresh pages) on a shared-prefix hit."""
        s = self.engine.scfg
        bs = s.kv_block_size

        def bucket(n):
            b = max(s.prefill_bucket, 1)
            while b < n:
                b *= 2
            return min(b, s.max_len)

        miss, hit = {}, {}
        for it in self.items:
            p = len(it.prompt)
            miss.setdefault((bucket(p), -(-p // bs)), p)
            if it.prefix >= 0 and s.prefix_cache:
                pre = self.mix["shared_prefix"]["len"]
                n_hit = min(pre // bs, (p - 1) // bs)
                sfx = p - n_hit * bs
                hit.setdefault((bucket(sfx), -(-p // bs) - n_hit), p)
        return sorted(miss.values()), sorted(hit.values())

    def warm(self):
        """Compile (or load from the cache) every program the window will
        run: one request per shape class, prefix donor first."""
        from repro.serve import Request
        rng = gen.rng_for(self.seed, 7)
        V = self.dims["vocab"]
        miss, hit = self._shape_classes()
        reqs: List = []
        if hit:
            pre = rng.integers(0, V, self.mix["shared_prefix"]["len"],
                               dtype=np.int32)
            for p in [hit[0]] + hit:
                body = rng.integers(0, V, p - len(pre), dtype=np.int32)
                reqs.append(np.concatenate([pre, body]))
        reqs += [rng.integers(0, V, p, dtype=np.int32) for p in miss]
        for i, prompt in enumerate(reqs):
            self.engine.submit(Request(uid=-1 - i, prompt=prompt,
                                       max_new_tokens=2))
        done = self.engine.run_until_drained()
        bad = [r.status for r in done if r.status != "ok"]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")
        self.warm_requests = len(reqs)

    # ---------------------------------------------------------- window --

    def run_window(self) -> Window:
        from repro.serve import Request
        eng = self.engine
        reqs = [Request(uid=it.uid, prompt=it.prompt,
                        max_new_tokens=it.out_len) for it in self.items]
        m = eng.metrics
        counters = {k: m.counter(f"serve.{k}") for k in
                    ("tokens_out", "decode_steps", "decode_time_s",
                     "prefills", "requests_done")}
        lock = threading.Lock()
        pool = {"hits": 0, "lookups": 0, "counted": None}

        def pool_cum():
            with lock:
                p = eng.pool
                live = p is not None and p is not pool["counted"]
                return (pool["hits"] + (p.hits if live else 0),
                        pool["lookups"] + (p.lookups if live else 0))

        def snapshot():
            snap = {k: c.value for k, c in counters.items()}
            snap["t"] = time.perf_counter()
            snap["pool_hits"], snap["pool_lookups"] = pool_cum()
            snap["out_lens"] = [len(r.out_tokens) for r in reqs]
            snap["queued"] = eng.queue.qsize()
            return snap

        win = Window()
        stop = threading.Event()
        failure: List[BaseException] = []
        open_loop = self.mix["loop"] == "open"
        due_abs = np.zeros(len(reqs))

        def cancel():
            for r in reqs:
                if not r.done:
                    r.deadline_s = 0.0

        def closed_clock():
            first = counters["decode_steps"].value
            while counters["decode_steps"].value == first \
                    and not stop.is_set():
                time.sleep(0.0005)
            win.start = snapshot()
            win.t0 = win.start["t"]
            with self.spans.span("bench.window"):
                sleep_until(win.t0 + self.seconds, stop)
                win.end = snapshot()
            win.t1 = win.end["t"]
            cancel()

        def open_clock():
            tail = self.mix.get("tail_s", 60.0)
            t_sched = time.perf_counter() + 0.01
            pre = self.mix.get("preroll_s", 0.0)
            for i, it in enumerate(self.items):
                due_abs[i] = t_sched + pre + it.due_s
            t0, t1 = t_sched + pre, t_sched + pre + self.seconds
            in_win = [r for r, d in zip(reqs, due_abs) if t0 <= d < t1]
            ann, i, n = None, 0, len(reqs)
            while not stop.is_set():
                now = time.perf_counter()
                if not win.start and now >= t0:
                    win.start, win.t0 = snapshot(), t0
                    ann = self.spans.span("bench.window")
                    ann.__enter__()
                if win.start and not win.end and now >= t1:
                    win.end, win.t1 = snapshot(), t1
                    ann.__exit__(None, None, None)
                if i < n and due_abs[i] <= now:
                    with self.spans.span("bench.submit"):
                        eng.submit(reqs[i])
                    i += 1
                    continue
                if win.end and (all(r.done for r in in_win)
                                or now > t1 + tail):
                    break
                nxt = min(due_abs[i] if i < n else now + 0.005,
                          now + 0.005)
                if not win.start:
                    nxt = min(nxt, t0)
                elif not win.end:
                    nxt = min(nxt, t1)
                sleep_until(nxt)
            cancel()
            lag = [r.submit_t - d for r, d in zip(reqs[:i], due_abs)]
            win.extra["lag_max_ms"] = 1e3 * max(lag, default=0.0)

        def clock():
            try:
                (open_clock if open_loop else closed_clock)()
            except BaseException as e:          # reported by the main loop
                failure.append(e)
                cancel()
            finally:
                stop.set()

        if not open_loop:
            for r in reqs:
                eng.submit(r)
        th = threading.Thread(target=clock, name="bench-clock",
                              daemon=True)
        th.start()
        try:
            while True:
                if eng.queue.empty():
                    if stop.is_set():
                        break
                    time.sleep(0.0002)
                    continue
                with self.spans.span("bench.drain"):
                    eng.run_until_drained()
                with lock:
                    p = eng.pool
                    if p is not None and p is not pool["counted"]:
                        pool["hits"] += p.hits
                        pool["lookups"] += p.lookups
                        pool["counted"] = p
        finally:
            stop.set()
            th.join()
        if failure:
            raise failure[0]
        if not open_loop and win.end["queued"] < self.mix["queue_depth"]:
            raise BacklogRanDry(
                f"{win.end['queued']} requests queued at the window's end, "
                f"fewer than queue_depth={self.mix['queue_depth']}: raise "
                f"the mix's 'requests'")
        win.extra.update(reqs=reqs, due_abs=due_abs if open_loop else None,
                         open_loop=open_loop)
        return win

    def free(self):
        """Drop the engine (its quantized weight copy and compiled state);
        the float weights stay for the reference."""
        self.engine = None

    # ----------------------------------------------------------- check --

    def window_requests(self, win: Window):
        """The requests the window is judged on: due in it (open loop), or
        finished in it (closed loop)."""
        reqs = win.extra["reqs"]
        if win.extra["open_loop"]:
            return [r for r, d in zip(reqs, win.extra["due_abs"])
                    if win.t0 <= d < win.t1]
        return [r for r in reqs
                if r.status == "ok" and win.t0 <= r.finish_t <= win.t1]

    def tally(self, win: Window):
        """(attempted, failed) of the window."""
        judged = self.window_requests(win)
        if win.extra["open_loop"]:
            return len(judged), sum(r.status != "ok" for r in judged)
        return len(judged), sum(r.status in ("error", "shed")
                                for r in win.extra["reqs"])

    def check(self, win: Window, control: bool = False) -> dict:
        """The numbers compared, each as (value, limit), over a sample of
        the window's finished requests drawn from the seed, the longest
        among them, each served token scored by the reference in the
        context the server had when it produced it:

        - ``max_logit_gap``: the widest gap by which a served token's
          reference logit lies below the reference's best;
        - ``mean_logit_gap``: the mean of that gap over every served token
          (0 where it is the reference's argmax): how often and how far a
          loss of precision moves the served tokens off the reference's;
        - ``missing``: requests that failed or never finished.

        ``control`` reads the first two of the int8 control in the
        program's place: at the same prompts and served tokens, the token
        the control puts first stands for the served one."""
        judged = self.window_requests(win)
        missing = self.tally(win)[1]
        ok = [r for r in judged if r.status == "ok"]
        k = self.conf["check"]["requests"]
        limit = self.conf["check"]["limit"]
        gap = mean = float("inf")
        if ok:
            longest = max(ok, key=lambda r: (len(r.out_tokens),
                                              len(r.prompt), -r.uid))
            rest = [r for r in ok if r is not longest]
            rng = gen.rng_for(self.seed, 9)
            pick = [longest] + [rest[i] for i in rng.permutation(
                len(rest))[:max(k - 1, 0)]]
            pad = -(-self.scfg_kw["max_len"] // 256) * 256
            gaps = []
            for r in pick:
                args = (self.conf, self.weights, r.prompt,
                        np.asarray(r.out_tokens, np.int32), pad)
                targets = None
                if control:
                    targets = self.ref.served_stats(*args, quant=True)["argmax"]
                gaps.append(self.ref.served_stats(*args,
                                                  targets=targets)["gap"])
            gaps = np.concatenate(gaps)
            gap = float(np.max(gaps))
            mean = float(np.mean(gaps))
        return {"max_logit_gap": (gap, limit["max_logit_gap"]),
                "mean_logit_gap": (mean, limit["mean_logit_gap"]),
                "missing": (float(missing), 0.0)}
