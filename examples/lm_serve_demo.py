"""Serve a (reduced) LM from the assigned-architecture zoo with batched
requests, continuous batching, and prefix-grouped admission.

  PYTHONPATH=src python examples/lm_serve_demo.py --arch gemma2-2b
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.models.lm import LM
from repro.serve.engine import Request, ServeEngine

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="smollm-135m")
args = ap.parse_args()

cfg = reduced(get_config(args.arch))
model = LM(cfg, backend="jnp", remat="none")
params = model.init(jax.random.key(0))
engine = ServeEngine(model, params, batch_slots=4, max_len=48)

rng = np.random.default_rng(0)
shared = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
reqs = []
for i in range(6):
    prompt = shared.copy() if i < 3 else rng.integers(
        0, cfg.vocab_size, 6).astype(np.int32)
    prompt[-1] = i
    reqs.append(Request(rid=i, prompt=prompt, max_new=6))

done = engine.run(reqs, max_steps=64)
for rid in sorted(done):
    print(f"req {rid}: generated {done[rid]}")
print(f"arch={cfg.name} (reduced) served {len(done)} requests")
