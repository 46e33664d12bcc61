"""Batched serving with the slot-cache decode path + STAR-style hot swap:
a newer committed checkpoint replaces the serving params mid-stream via the
Thomas-rule tid check (stale loads are rejected).

    PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serve.engine import ServeEngine

compile_cache.enable()

cfg = get_arch("starcoder2-7b", smoke=True)
params_v1 = tf.init_params(cfg, jax.random.key(0))
eng = ServeEngine(cfg, params_v1, max_len=96)

prompts = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size,
                             dtype=jnp.int32)
out1 = eng.generate(prompts, 16)
print("v1 tokens:", out1[0].tolist())

# a newer training epoch commits; swap in (tid = committed step)
params_v2 = tf.init_params(cfg, jax.random.key(7))
assert eng.load_params(params_v2, tid=100)
assert not eng.load_params(params_v1, tid=50)       # stale: rejected
out2 = eng.generate(prompts, 16)
print("v2 tokens:", out2[0].tolist())
print(f"stats: {eng.stats}")
