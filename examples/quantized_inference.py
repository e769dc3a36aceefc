"""The paper's quantization scheme (Eq. 4 / Algorithm 1) applied at LM
scale: quantize a small transformer's matmul weights to int8 with
power-of-two scales, run the shift-requantized integer matmuls via the
Pallas matmul_q8 kernel path, and compare next-token agreement vs float.

Run:  PYTHONPATH=src python examples/quantized_inference.py
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core.quantize import frac_bits_for, quantize
from repro.kernels.ops import matmul
from repro.models import api

enable_compile_cache()
cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2, d_model=128,
                          n_heads=4, n_kv_heads=2, d_ff=256, vocab=512)
key = jax.random.PRNGKey(0)
params = api.init_params(cfg, key)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 24), 0, cfg.vocab)

# float reference: final hidden states + logits
from repro.models.transformer import forward_hidden, unembed
h = forward_hidden(params, toks, cfg, remat="none")
logits_f = unembed(params, h, cfg)

# int8 path for the biggest matmul: the unembedding (d_model x vocab)
w = params["embed"].T                                  # tied unembed
wq = quantize(w)
hq = quantize(h)
acc_fb = hq.frac_bits + wq.frac_bits
out_fb = frac_bits_for(logits_f)
q_logits = matmul(hq.q.reshape(-1, hq.q.shape[-1]), wq.q,
                  requant_shift=acc_fb - out_fb, method="pallas")
q_logits = q_logits.reshape(logits_f.shape).astype(jnp.float32) * 2.0 ** -out_fb

top1_f = jnp.argmax(logits_f[:, -1], -1)
top1_q = jnp.argmax(q_logits[:, -1], -1)
agree = float(jnp.mean((top1_f == top1_q).astype(jnp.float32)))
rel = float(jnp.mean(jnp.abs(q_logits - logits_f)) /
            jnp.mean(jnp.abs(logits_f)))
print(f"int8 pow2 unembed: top-1 agreement {agree:.2f}, rel L1 {rel:.3f}")
print("(full-layer integer inference is exercised in examples/train_cnn.py"
      " --primitive ... via quantize_cnn)")
