"""The LM serving engine on a pattern stack (LFM2: gated short convolutions
beside attention layers, dropless sigmoid-routed experts) under a traffic
mix: ``lm.System``'s window and check, with the program's ``ModelConfig``
built from the LFM2 keys of the configuration file, and the engine's
expert counters (``serve.moe_experts_hit``, ``serve.moe_assignments``)
read at the window's edges.
"""
from __future__ import annotations

import contextlib
import math

from bench import gen
from bench.common import seed_key
from bench.systems import lm

COUNTERS = ("moe_experts_hit", "moe_assignments")


def model_config(conf: dict):
    """The program's ``ModelConfig`` for the sizes in ``conf``."""
    from repro.configs.base import ModelConfig, MoEConfig
    m = conf["model"]
    return ModelConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        tied_embeddings=conf["tie_word_embeddings"],
        rope_theta=conf["rope_theta"], norm_eps=conf["norm_eps"],
        layer_types=tuple(conf["layer_types"]),
        conv_kernel=conf["conv_L_cache"],
        n_dense_layers=conf["num_dense_layers"], qk_norm=True,
        moe=MoEConfig(num_experts=conf["num_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_ff=conf["moe_intermediate_size"], router="sigmoid",
                      expert_bias=conf["use_expert_bias"]),
        param_dtype=m["param_dtype"], compute_dtype=m["compute_dtype"])


class _EdgeSpans:
    """The harness's spans, with the expert counters read as the window's
    span opens and closes: right after the window's own snapshots."""

    def __init__(self, spans, read):
        self.enabled, self._spans, self._read = spans.enabled, spans, read
        self.edges = []

    @contextlib.contextmanager
    def _edge(self, name):
        with self._spans.span(name):
            self.edges.append(self._read())
            try:
                yield
            finally:
                self.edges.append(self._read())

    def span(self, name: str):
        if name == "bench.window":
            return self._edge(name)
        return self._spans.span(name)


class System(lm.System):
    def __init__(self, conf, ref, mix, seed, seconds, spans):
        super().__init__(conf, ref, mix, seed, seconds, spans)
        self.spans = _EdgeSpans(spans, self._counters)

    def _counters(self) -> dict:
        m = self.engine.metrics
        return {k: m.counter(f"serve.{k}").value for k in COUNTERS}

    def build(self):
        from repro.serve import Engine, ServeConfig
        import jax
        self.mcfg = model_config(self.conf)
        if self.mcfg.head_dim != self.conf["model"]["head_dim"]:
            raise ValueError(f"head_dim {self.mcfg.head_dim} != "
                             f"{self.conf['model']['head_dim']}")
        if self.conf["routed_scaling_factor"] != 1:
            raise ValueError("the program's sigmoid routing scales the "
                             "normalised weights by 1")
        self.weights = self.ref.make_weights(self.conf, seed_key(self.seed))
        jax.block_until_ready(self.weights)
        self.engine = Engine(self.mcfg, self.weights,
                             ServeConfig(**self.scfg_kw))
        self.items = gen.lm_items(self.mix, self.seed, self.dims["vocab"],
                                  self.seconds)

    def check(self, win, control: bool = False) -> dict:
        """``lm.System``'s check without ``max_logit_gap``: here one
        near-tied expert pick that bfloat16 rounding flips moves a logit by
        about one, in the program and in the int8 control alike, so the
        widest gap separates neither; ``mean_logit_gap`` separates them
        (PERF.md, section 2). ``lm.System.check`` reads a limit for every
        number it returns, so it is handed an unbounded one to drop."""
        conf = self.conf
        limit = dict(conf["check"]["limit"], max_logit_gap=math.inf)
        self.conf = dict(conf, check=dict(conf["check"], limit=limit))
        try:
            out = super().check(win, control)
        finally:
            self.conf = conf
        del out["max_logit_gap"]
        return out

    def run_window(self):
        win = super().run_window()
        start, end = self.spans.edges[0], self.spans.edges[-1]
        win.start.update(start)
        win.end.update(end)
        return win
