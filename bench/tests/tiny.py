"""Sizes small enough for a CPU test run of the harness (Pallas kernels in
interpret mode); widths are cut here only, never in a cell."""
import os
import time

from bench import harness

LM = {"config": {"num_hidden_layers": 2, "hidden_size": 64,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "intermediate_size": 128, "vocab_size": 512,
                 "model": {"head_dim": 16},
                 "serve": {"max_batch": 4, "max_len": 256},
                 "check": {"requests": 3}},
      "traffic": {"requests": 4096, "queue_depth": 4,
                  "prompt_len": {"median": 40, "sigma": 0.5, "min": 16,
                                 "max": 100},
                  "output_len": {"median": 8, "sigma": 0.5, "min": 4,
                                 "max": 20}}}

# the control (the reference in int8 in the program's place) shows its error
# against the limit at the published widths: two layers of them
CONTROL = {"config": {"num_hidden_layers": 2,
                      "serve": {"max_batch": 4, "max_len": 256},
                      "check": {"requests": 6}},
           "traffic": dict(LM["traffic"],
                           output_len={"median": 24, "sigma": 0.5,
                                       "min": 8, "max": 48})}

CHAT = {"config": LM["config"],
        "traffic": {"rate": 3.0, "preroll_s": 0.5, "tail_s": 20,
                    "shared_prefix": {"count": 3, "len": 64, "zipf": 1.1},
                    "burst": {"every_s": 2, "length_s": 0.5, "factor": 3},
                    "prompt_len": {"median": 20, "sigma": 0.5, "min": 8,
                                   "max": 60},
                    "output_len": {"median": 8, "sigma": 0.5, "min": 4,
                                   "max": 20}}}

def run(workload, overrides, seconds=2.0, seed=2 ** 33 + 7, **kw):
    """One harness run on the CPU: no chip check, no compilation cache."""
    os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
    return harness.run(workload, seed, seconds, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       overrides=overrides, **kw)
