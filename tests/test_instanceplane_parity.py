"""InstancePlane parity: columnar engine vs the retired per-object oracle.

Full ``Simulation`` runs (trace -> prefill -> scheduler -> FlowPlane ->
decode) are executed twice — ``instance_engine="plane"`` vs
``instance_engine="reference"`` — on seeded 64- and 256-GPU fat-trees, and
every per-request outcome must match *bit-for-bit*: prefill start/end,
scheduling time, chosen decode instance, tier, effective transfer bytes,
per-instance cache-hit tokens, transfer landing, admission, first token
(TTFT), TBT, finish time, token counts, rejections and requeues.  Finish
*order* (the (finish_time, request_id) sequence) and the per-instance cache
counters (hits/misses/evictions/bytes_used) must also be identical.

This exercises the cohort-stepped iteration clock, the RadixPlane broadcast
LCP + array LRU, epoch-batched admission (both engines share the epoch
path), the vectorised prefill ETA argmin, and the fault/requeue machinery.
Both of the plane's token-accounting paths are pinned explicitly: the
scalar per-row path (small cohorts) and the fused-array path
(``scalar_rows_max = -1`` forces it for every cohort).
"""

import numpy as np
import pytest

from hybrid_shape import HYBRID_KV
from repro.core.cost import LLAMA3_70B_KV
from repro.sim import FaultEvent, SimConfig, Simulation
from repro.traces import generate_trace, profile_capacity

TREE_64 = dict(n_pods=2, racks_per_pod=2, servers_per_rack=2, n_prefill=4)
TREE_256 = dict(n_pods=2, racks_per_pod=8, servers_per_rack=2, n_prefill=16)


def _trace(tree_kw, seed, duration=5.0):
    n_servers = 2 * tree_kw["n_pods"] * tree_kw["racks_per_pod"] * \
        tree_kw["servers_per_rack"]
    n_inst = n_servers * 8 // 4
    n_prefill = tree_kw["n_prefill"]
    cap = profile_capacity(
        "rag", n_prefill=n_prefill, n_decode=n_inst - n_prefill,
        tor_egress_bytes_per_s=8 * 50e9 / 8 * max(n_inst // 16, 1))
    return generate_trace("rag", duration=duration, target_rps=cap, seed=seed)


def _run(engine, tree_kw, sched, seed, faults=(), scalar_rows_max=None,
         kv_spec=LLAMA3_70B_KV):
    cfg = SimConfig(scheduler=sched, seed=seed, background=0.2,
                    warmup=1.0, measure=3.0, instance_engine=engine,
                    faults=faults, kv_spec=kv_spec, **tree_kw)
    sim = Simulation(cfg)
    if scalar_rows_max is not None and engine == "plane":
        sim.engine.scalar_rows_max = scalar_rows_max
    sim.run(_trace(tree_kw, seed), drain=40.0)
    return sim


def _outcomes(sim):
    recs = [
        (r.req.request_id, r.prefill_instance, r.prefill_start, r.prefill_end,
         r.sched_time, r.decode_instance, r.tier, r.s_eff, r.state_bytes,
         r.hit_tokens, r.transfer_end, r.admit_time, r.first_token, r.finish,
         r.tbt, r.tokens_out, r.rejected, r.requeues)
        for r in sim.records
    ]
    finish_order = sorted(
        (r.finish, r.req.request_id) for r in sim.records if r.finish >= 0
    )
    return recs, finish_order, sim.engine.cache_stats()


def _assert_parity(a, b):
    ra, fa, ca = _outcomes(a)
    rb, fb, cb = _outcomes(b)
    assert ra == rb          # every per-request field, bit-for-bit
    assert fa == fb          # finish order (time, id)
    assert ca == cb          # per-instance cache-hit counters


class TestBitExactParity:
    @pytest.mark.parametrize("kv", [LLAMA3_70B_KV, HYBRID_KV],
                             ids=["attn", "hybrid"])
    @pytest.mark.parametrize("seed", range(2))
    def test_netkv_full_64(self, seed, kv):
        _assert_parity(_run("plane", TREE_64, "netkv-full", seed, kv_spec=kv),
                       _run("reference", TREE_64, "netkv-full", seed, kv_spec=kv))

    def test_cla_64(self):
        _assert_parity(_run("plane", TREE_64, "cla", 0),
                       _run("reference", TREE_64, "cla", 0))

    def test_netkv_full_256(self):
        _assert_parity(_run("plane", TREE_256, "netkv-full", 0),
                       _run("reference", TREE_256, "netkv-full", 0))

    def test_vector_row_path_64(self):
        """scalar_rows_max = -1 forces the fused-array accounting path for
        every cohort — it must agree with the reference (and hence with the
        scalar path) exactly."""
        _assert_parity(_run("plane", TREE_64, "netkv-full", 3,
                            scalar_rows_max=-1),
                       _run("reference", TREE_64, "netkv-full", 3))


class TestBatchWindowParity:
    def test_netkv_batch_64(self):
        """Window-batched scheduling: the dispatch burst goes through the
        FlowPlane arrival epoch (one union rate recompute) on both arms."""
        _assert_parity(_run("plane", TREE_64, "netkv-batch", 0),
                       _run("reference", TREE_64, "netkv-batch", 0))


class TestFaultParity:
    FAULTS = (
        FaultEvent(time=1.6, kind="kill_decode", instance_id=5,
                   detection_delay=0.3),
        FaultEvent(time=2.1, kind="slowdown", instance_id=7, factor=3.0),
        FaultEvent(time=2.5, kind="add_decode"),
    )

    def test_kill_slowdown_join_64(self):
        """Failure (victims + bounced dispatches + requeues), straggler
        scaling and elastic join must all replay identically."""
        a = _run("plane", TREE_64, "netkv-full", 0, faults=self.FAULTS)
        b = _run("reference", TREE_64, "netkv-full", 0, faults=self.FAULTS)
        _assert_parity(a, b)
        assert sum(r.requeues for r in a.records) > 0  # fault path exercised
        sa = next(d for d in a.decode if d.instance_id == 7)
        assert sa.iter_scale_est > 1.0                 # straggler EWMA moved


class TestThroughputSanity:
    def test_plane_not_slower_at_scale(self):
        """The cohort clock must step a large synchronized pool much faster
        than per-instance heap events (the decode_throughput benchmark gates
        the full 10x at 1024; this is a fast in-suite canary at 256)."""
        import time

        from repro.core.cost import H100_TP4_ITER, H100_TP4_PREFILL, LLAMA3_70B_KV
        from repro.core.view import ClusterView
        from repro.sim import (
            EventLoop, InstancePlane, ReferenceInstanceEngine, RequestState,
        )
        from repro.traces.mooncake import Request

        class Meta:
            def __init__(self, iid, srv):
                self.instance_id, self.server = iid, srv

        def build(kind, D=256, B=32):
            loop = EventLoop()
            view = ClusterView(capacity=D)
            dec = [Meta(i, (0, 0, i)) for i in range(D)]
            cls = InstancePlane if kind == "plane" else ReferenceInstanceEngine
            eng = cls([], dec, view=view, loop=loop, iter_model=H100_TP4_ITER,
                      prefill_model=H100_TP4_PREFILL, beta_max=64,
                      kv_spec=LLAMA3_70B_KV, kv_budget=1e18)
            eng.set_decode_callbacks(None, None)
            rid = 0
            for i in range(D):
                for _ in range(B):
                    req = Request(request_id=rid, arrival=0.0, input_len=256,
                                  output_len=10**9,
                                  block_hashes=((rid, 0), (rid, 1)),
                                  share_group=-1, slo=5.0)
                    eng.enqueue(i, RequestState(req=req, kv_bytes=1e6), 0.0)
                    rid += 1
            eng.kick(range(D), 0.0)
            return loop, eng

        times = {}
        for kind in ("plane", "reference"):
            loop, eng = build(kind)
            horizon = 10 * H100_TP4_ITER(32) * 1.001
            t0 = time.perf_counter()
            loop.run(until=horizon)
            times[kind] = time.perf_counter() - t0
            assert eng.total_iterations == 256 * 10
        assert times["plane"] < times["reference"]
