"""Serving launcher: disaggregated cluster simulation at paper scale, or the
real-model executable cluster.

    python -m repro.launch.serve --profile rag --scheduler netkv-full
    python -m repro.launch.serve --real --arch smollm-135m --requests 8
    python -m repro.launch.serve --real --smoke --arch qwen3-14b   # CPU-sized

``--real`` serves the architecture at its full width in bf16 (random
weights from ``--seed``, 1024-token prompts, a 2048-token cache); one TPU
v5e holds smollm-135m or granite-moe-1b whole.  ``--smoke`` swaps in the
architecture's float32 smoke config at a 64-token cache, which the CPU
runs in seconds.
"""

from __future__ import annotations

import argparse


def main(argv: list[str] | None = None) -> int:
    from repro.core.jaxutil import use_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--scheduler", default="netkv-full")
    ap.add_argument("--profile", default="rag",
                    choices=["chatbot", "rag", "long_context"])
    ap.add_argument("--rate", type=float, default=1.0, help="fraction of capacity")
    ap.add_argument("--background", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default=None,
                    help="the KV-size model for the simulator (default "
                         "llama3-70b); the served model with --real "
                         "(default smollm-135m)")
    ap.add_argument("--real", action="store_true",
                    help="run the real model end to end at full width")
    ap.add_argument("--smoke", action="store_true",
                    help="with --real: the float32 smoke config instead")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--faults", action="store_true",
                    help="inject a decode-instance failure mid-run")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.real:
        import dataclasses

        import jax.numpy as jnp
        import numpy as np

        from repro.configs import get_spec
        from repro.serving import DisaggregatedCluster, ServeRequest

        spec = get_spec(args.arch or "smollm-135m")
        if args.smoke:
            cfg = dataclasses.replace(spec.smoke, compute_dtype=jnp.float32)
            cache_len, prompt_len = 64, 24
        else:
            cfg, cache_len, prompt_len = spec.model, 2048, 1024
        cluster = DisaggregatedCluster(cfg, scheduler=args.scheduler,
                                       cache_len=cache_len, seed=args.seed)
        rng = np.random.default_rng(args.seed)
        reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, size=prompt_len),
                             max_new=8, arrival=i * 0.02)
                for i in range(args.requests)]
        for r in cluster.serve(reqs):
            print(f"req{r.request_id}: decode@{r.decode_instance} tier{r.tier} "
                  f"xfer={r.transfer_bytes/1e3:.0f}KB ttft={r.ttft*1e3:.0f}ms "
                  f"tokens={r.tokens[:8]}")
        return 0

    from repro.configs import get_spec
    from repro.sim import FaultEvent, SimConfig, run_sim
    from repro.traces import generate_trace, profile_capacity

    arch = args.arch or "llama3-70b"
    kv = get_spec(arch).kv_spec()
    cap = profile_capacity(args.profile, kv_bytes_per_token=kv.kv_bytes_per_token or 1.0)
    trace = generate_trace(args.profile, duration=22.0,
                           target_rps=cap * args.rate, seed=args.seed)
    faults = [FaultEvent(time=8.0, kind="kill_decode", instance_id=5)] if args.faults else []
    cfg = SimConfig(scheduler=args.scheduler, seed=args.seed, kv_spec=kv,
                    background=args.background, faults=faults)
    m = run_sim(cfg, trace)
    print(f"{args.scheduler} on {args.profile} ({arch} KV) @ {args.rate:.0%}:")
    print(f"  TTFT mean={m.ttft_mean*1e3:.0f}ms p99={m.ttft_p99*1e3:.0f}ms")
    print(f"  TBT  mean={m.tbt_mean*1e3:.2f}ms  SLO={m.slo_attainment:.3f} "
          f"goodput={m.goodput_rps:.2f}rps")
    print(f"  transfer mean={m.xfer_mean*1e3:.0f}ms  tiers "
          f"2:{m.tier_fraction[2]:.2f} 3:{m.tier_fraction[3]:.2f}")
    if args.faults:
        print(f"  requeues after failure: {m.requeues}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
