"""End-to-end driver: serve a small real model with batched requests through
the disaggregated cluster — prefill engines, NetKV routing, kv_pack transfer,
continuous-batching decode.  Token output is exact (tested against a
monolithic forward).  This runs qwen3-14b's float32 smoke config, sized for
a CPU; ``python -m repro.launch.serve --real`` serves a full-width model.

    PYTHONPATH=src python examples/serve_netkv.py
"""
import dataclasses, os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.configs import get_spec
from repro.serving import DisaggregatedCluster, ServeRequest

cfg = dataclasses.replace(get_spec("qwen3-14b").smoke, compute_dtype=jnp.float32)
cluster = DisaggregatedCluster(cfg, scheduler="netkv-full", n_prefill=2,
                               n_decode=4, cache_len=64, background=0.2)
rng = np.random.default_rng(0)
shared_prefix = rng.integers(0, cfg.vocab_size, size=16)

reqs = []
for i in range(8):
    # half the requests share a 16-token prefix (prefix-cache hits kick in)
    if i % 2 == 0:
        prompt = np.concatenate([shared_prefix, rng.integers(0, cfg.vocab_size, 8)])
    else:
        prompt = rng.integers(0, cfg.vocab_size, size=24)
    reqs.append(ServeRequest(i, prompt, max_new=8, arrival=i * 0.05))

print(f"serving {len(reqs)} requests on a {len(cluster.decode)}-decode cluster")
for r in cluster.serve(reqs):
    print(f"req{r.request_id}: decode@{r.decode_instance} tier{r.tier} "
          f"xfer={r.transfer_bytes/1e3:6.0f}KB t_xfer={r.transfer_time*1e3:5.1f}ms "
          f"ttft={r.ttft*1e3:4.0f}ms tokens={r.tokens}")
print("note: even-numbered requests re-hitting a warm instance ship fewer KV bytes")
