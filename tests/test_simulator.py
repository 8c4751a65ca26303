"""End-to-end simulator behaviour: paper-property reproduction at test scale,
fault tolerance, elastic scaling, straggler mitigation, oracle staleness."""

import numpy as np
import pytest

from repro.sim import FaultEvent, SimConfig, Simulation, run_sim
from repro.sim.instances import RequestState
from repro.sim.kvcache import BlockCache
from repro.sim.trace import FORENSICS_COLUMNS
from repro.traces import generate_trace, profile_capacity
from repro.traces.mooncake import Request

from hybrid_shape import HYBRID_KV, STATE, plain_bytes


def _trace(profile="rag", dur=12.0, frac=1.0, seed=0, **kw):
    cap = profile_capacity(profile)
    return generate_trace(profile, duration=dur, target_rps=cap * frac, seed=seed, **kw)


def _cfg(sched, seed=0, **kw):
    kw.setdefault("warmup", 2.0)
    kw.setdefault("measure", 8.0)
    kw.setdefault("background", 0.2)
    return SimConfig(scheduler=sched, seed=seed, **kw)


TRACE = _trace()


class TestSchedulerOrdering:
    """The paper's headline ordering at 100% RAG load."""

    def test_netkv_beats_rr_and_cla(self):
        ms = {s: run_sim(_cfg(s), TRACE) for s in ("rr", "cla", "netkv-full")}
        assert ms["netkv-full"].ttft_mean < ms["cla"].ttft_mean
        assert ms["netkv-full"].ttft_mean < ms["rr"].ttft_mean
        assert ms["netkv-full"].xfer_mean < ms["rr"].xfer_mean

    def test_tbt_overhead_below_half_ms(self):
        """§VI-J: NetKV's TBT cost vs CLA* stays under 0.5 ms."""
        cla = run_sim(_cfg("cla"), TRACE)
        nk = run_sim(_cfg("netkv-full"), TRACE)
        assert abs(nk.tbt_mean - cla.tbt_mean) < 0.5e-3

    def test_tier_shifting(self):
        """Table VI: NetKV shifts transfers toward tier 2."""
        rr = run_sim(_cfg("rr"), TRACE)
        nk = run_sim(_cfg("netkv-full"), TRACE)
        assert nk.tier_fraction[2] > rr.tier_fraction[2]
        assert nk.tier_fraction[3] < rr.tier_fraction[3]
        # pack placement: tiers 0/1 unreached
        assert rr.tier_fraction[0] == 0 and rr.tier_fraction[1] == 0

    def test_ablation_ladder_order(self):
        """Table IV: every rung is at least as good as the previous (with
        tolerance — dynamic congestion may add a small residual either way)."""
        cla = run_sim(_cfg("cla"), TRACE)
        topo = run_sim(_cfg("netkv-topo"), TRACE)
        static = run_sim(_cfg("netkv-static"), TRACE)
        assert topo.ttft_mean < cla.ttft_mean  # static tier signal dominates
        assert static.ttft_mean < cla.ttft_mean


class TestOracleStaleness:
    def test_minute_refresh_harmless(self):
        """Exp 4: 100 ms vs 60 s refresh changes TTFT by < 10%."""
        fast = run_sim(_cfg("netkv-full", oracle_refresh=0.1), TRACE)
        slow = run_sim(_cfg("netkv-full", oracle_refresh=60.0), TRACE)
        assert abs(fast.ttft_mean - slow.ttft_mean) / fast.ttft_mean < 0.10


class TestFaultTolerance:
    def test_decode_failure_requeues_and_completes(self):
        faults = [FaultEvent(time=4.0, kind="kill_decode", instance_id=5)]
        m = run_sim(_cfg("netkv-full", faults=faults), TRACE)
        assert m.requeues > 0                    # victims re-ran
        assert m.n_unfinished == 0               # and completed
        assert m.slo_attainment > 0.3            # cluster survived

    def test_elastic_scale_up(self):
        faults = [FaultEvent(time=3.0, kind="add_decode", instance_id=0)]
        m = run_sim(_cfg("netkv-full", faults=faults), TRACE)
        assert m.n_unfinished == 0

    def test_elastic_join_lands_on_least_populated_server(self):
        """add_decode places the new instance on the decode-hosting server
        with the fewest healthy resident decode instances — after a kill,
        that is the dead instance's server — and it becomes schedulable."""
        faults = [
            FaultEvent(time=1.0, kind="kill_decode", instance_id=5),
            FaultEvent(time=3.0, kind="add_decode"),
        ]
        cfg = _cfg("netkv-full", faults=faults)
        sim = Simulation(cfg)
        dead_server = sim._decode_by_id(5).server
        sim.run(TRACE)
        new = sim.decode[-1]
        assert new.instance_id == max(sim._server_of)
        assert new.server == dead_server          # thinnest decode population
        assert bool(sim.view.healthy[new.slot])   # scheduler-visible
        assert new.iterations > 0                 # actually received work

    def test_elastic_join_spreads_across_servers(self):
        """With all servers equally populated, consecutive joins never stack
        on the server a previous join already thickened."""
        faults = [FaultEvent(time=2.0 + i, kind="add_decode") for i in range(2)]
        cfg = _cfg("netkv-full", faults=faults)
        sim = Simulation(cfg)
        sim.run(TRACE)
        joined = sim.decode[-2:]
        assert joined[0].server != joined[1].server

    def test_straggler_detected_and_avoided(self):
        """A 4x-slowed instance should receive fewer requests under LA-aware
        policies once the EWMA detector converges."""
        faults = [FaultEvent(time=0.0, kind="slowdown", instance_id=5, factor=4.0)]
        cfg = _cfg("netkv-full", faults=faults)
        sim = Simulation(cfg)
        m = sim.run(_trace(dur=10.0))
        slow = next(d for d in sim.decode if d.instance_id == 5)
        others = [d for d in sim.decode if d.instance_id != 5]
        assert slow.iter_scale_est > 2.0         # detector converged
        mean_iters = np.mean([d.iterations for d in others])
        # the slow instance ran fewer iterations per unit time by construction;
        # scheduling kept its queue from exploding
        assert slow.queued <= max(d.queued for d in others) + 2

    def test_dead_prefill_rejects_cleanly(self):
        cfg = _cfg("netkv-full")
        sim = Simulation(cfg)
        for p in sim.prefill:
            p.healthy = False
        m = sim.run(TRACE)
        assert m.n_rejected == len(TRACE)


class TestDetectionDelay:
    """Health flips scheduler-visible only after the detection delay; in the
    window, dispatches to the dead instance bounce and requeue."""

    def test_visibility_lags_by_detection_delay(self):
        faults = [FaultEvent(time=0.5, kind="kill_decode", instance_id=5,
                             detection_delay=0.25)]
        sim = Simulation(_cfg("netkv-full", faults=faults))
        sim.load_trace([])
        dec = sim._decode_by_id(5)
        sim.loop.run(until=0.6)
        assert dec.healthy is False                       # engine truth: dead
        assert bool(sim.view.healthy[dec.slot]) is True   # not yet detected
        sim.loop.run(until=0.8)
        assert bool(sim.view.healthy[dec.slot]) is False  # visible after delay

    def test_window_dispatch_bounces_and_requeues(self):
        """Single-decode cluster: a request scheduled inside the detection
        window is dispatched to the dead instance, bounces at transfer-landing
        time, and requeues — it is NOT rejected up front."""
        cfg = SimConfig(scheduler="netkv-full", n_pods=1, racks_per_pod=1,
                        servers_per_rack=1, gpus_per_server=8, tp=4,
                        n_prefill=1, warmup=0.0, measure=5.0, background=0.0,
                        faults=[FaultEvent(time=0.5, kind="kill_decode",
                                           instance_id=-1,
                                           detection_delay=1.0)])
        sim = Simulation(cfg)
        assert len(sim.decode) == 1
        cfg.faults[0].instance_id = sim.decode[0].instance_id
        # Short prompt: prefill lands well inside the (0.5, 1.5) window.
        req = Request(request_id=0, arrival=0.55, input_len=128, output_len=4,
                      block_hashes=tuple(("t", i) for i in range(8)),
                      share_group=-1, slo=2.0)
        sim.load_trace([req])
        sim.loop.run(until=5.0)
        rs = sim.records[0]
        assert rs.requeues > 0      # dispatched to the dead instance, bounced
        assert rs.rejected          # only decode instance never recovers


class TestRequeueReset:
    def test_requeue_clears_per_attempt_fields(self):
        """Regression: a requeued request must not keep sched_time /
        first_token / admit_time / tier / s_eff / hit_tokens from the failed
        attempt — a stale first_token reports a phantom TTFT for a request
        that never decoded on the new attempt."""
        sim = Simulation(_cfg("netkv-full"))
        sim.load_trace([])
        rs = RequestState(req=TRACE[0], kv_bytes=1e6)
        rs.sched_time = 1.0
        rs.first_token = 2.0
        rs.admit_time = 1.5
        rs.tier = 3
        rs.s_eff = 5e5
        rs.hit_tokens = 128.0
        rs.decode_instance = 5
        rs.tokens_out = 7
        rs.transfer_end = 1.2
        sim._requeue(rs, 2.5)
        assert rs.sched_time == -1.0
        assert rs.first_token == -1.0
        assert rs.admit_time == -1.0
        assert rs.tier == -1
        assert rs.s_eff == 0.0
        assert rs.hit_tokens == 0.0
        assert rs.decode_instance == -1
        assert rs.tokens_out == 0
        assert rs.transfer_end == -1.0
        assert rs.requeues == 1 and not rs.rejected

    def test_no_phantom_ttft_after_fault(self):
        """Every record that reports a finite TTFT actually produced a first
        token after its last (re)scheduling."""
        faults = [FaultEvent(time=4.0, kind="kill_decode", instance_id=5)]
        cfg = _cfg("netkv-full", faults=faults)
        sim = Simulation(cfg)
        m = sim.run(TRACE)
        assert m.requeues > 0
        for rs in sim.records:
            if rs.first_token >= 0:
                assert rs.first_token >= rs.sched_time >= 0


class TestDeterminism:
    def test_same_seed_same_result(self):
        a = run_sim(_cfg("netkv-full", seed=7), TRACE)
        b = run_sim(_cfg("netkv-full", seed=7), TRACE)
        assert a.ttft_mean == b.ttft_mean
        assert a.tier_fraction == b.tier_fraction


class TestBlockCache:
    def test_lcp_semantics(self):
        c = BlockCache(budget_bytes=1e9, bytes_per_block=1e3)
        c.insert([("a", 0), ("a", 1), ("a", 3)])
        # LCP requires consecutiveness: block 2 missing stops the prefix at 2
        assert c.lcp_blocks([("a", 0), ("a", 1), ("a", 2), ("a", 3)]) == 2

    def test_lru_eviction(self):
        c = BlockCache(budget_bytes=3e3, bytes_per_block=1e3)
        c.insert([1, 2, 3])
        c.touch([1])          # 2 becomes LRU
        c.insert([4])
        assert 2 not in c and 1 in c and 4 in c

    def test_hit_clamped_to_input(self):
        c = BlockCache(budget_bytes=1e9, bytes_per_block=1e3)
        c.insert([("a", i) for i in range(10)])
        assert c.hit_tokens([("a", i) for i in range(10)], input_len=50) == 50


class TestBatchScheduler:
    def test_batch_mode_runs(self):
        m = run_sim(_cfg("netkv-batch"), TRACE)
        assert m.n_unfinished == 0
        assert np.isfinite(m.ttft_mean)


class TestKVGrowthAccounting:
    """Regression: decode-side KV growth (one token per iteration) can push
    pinned bytes past the budget.  The scheduler-visible free_memory must
    clamp at zero (no phantom negative capacity) and growth must keep
    evicting the LRU cache — on both instance engines."""

    def _grow_past_budget(self, engine):
        from repro.core.cost import B_TOK, H100_TP4_ITER, H100_TP4_PREFILL, \
            LLAMA3_70B_KV
        from repro.core.view import ClusterView
        from repro.sim import EventLoop, InstancePlane, \
            ReferenceInstanceEngine, RequestState

        class Meta:
            def __init__(self, iid, srv):
                self.instance_id, self.server = iid, srv

        kpt = LLAMA3_70B_KV.kv_bytes_per_token
        req = Request(request_id=0, arrival=0.0, input_len=128, output_len=64,
                      block_hashes=tuple(("k", i) for i in range(8)),
                      share_group=-1, slo=5.0)
        rs = RequestState(req=req, kv_bytes=float(LLAMA3_70B_KV.kv_bytes(128)))
        # Budget: the pinned prefix plus 3 cache blocks of headroom.  The 8
        # inserted prefix blocks don't all fit (insert evicts 5), and the 64
        # output tokens of decode growth (= 4 blocks of bytes) evict the
        # rest mid-decode and then overcommit the budget outright.
        budget = rs.kv_bytes + 3 * (kpt * B_TOK)
        loop = EventLoop()
        view = ClusterView(capacity=1)
        cls = InstancePlane if engine == "plane" else ReferenceInstanceEngine
        eng = cls([], [Meta(0, (0, 0, 0))], view=view, loop=loop,
                  iter_model=H100_TP4_ITER, prefill_model=H100_TP4_PREFILL,
                  beta_max=4, kv_spec=LLAMA3_70B_KV, kv_budget=budget)
        eng.set_decode_callbacks(None, None)
        eng.reserve(0, rs, 0.0)
        eng.enqueue(0, rs, 0.0)
        eng.kick([0], 0.0)
        min_free = float("inf")
        while not loop.empty():
            nt = loop.next_time()
            loop.run(until=nt)
            min_free = min(min_free, float(view.free_memory[0]))
        assert rs.finish > 0
        stats = eng.cache_stats()[0]
        return min_free, stats

    @pytest.mark.parametrize("engine", ["plane", "reference"])
    def test_free_memory_clamped_and_cache_evicted(self, engine):
        min_free, stats = self._grow_past_budget(engine)
        assert min_free == 0.0          # overcommitted, but never negative
        assert stats["evictions"] > 0   # growth evicted the resident blocks
        assert stats["bytes_used"] == 0.0

    def test_no_negative_free_memory_in_full_run(self):
        sim = Simulation(_cfg("netkv-full"))
        sim.run(TRACE)
        assert (sim.view.free_memory[: sim.view.n] >= 0.0).all()


class TestMeasuredTelemetry:
    """Satellite: oracle source='measured' aggregates FlowPlane link
    counters instead of reading the background model's ground truth."""

    def test_measured_matches_static_background_when_idle(self):
        from repro.cluster.network import BackgroundTraffic, FlowPlane
        from repro.cluster.topology import FatTree

        net = FlowPlane(FatTree(), BackgroundTraffic(0.3), seed=0)
        m = net.measured_tier_congestion(0.0)
        truth = net.tier_congestion(0.0)
        assert m[0] == 0.0  # tier 0 (NVLink) has no fabric links
        for t in (1, 2, 3):
            assert m[t] == pytest.approx(truth[t], abs=1e-9)

    def test_measured_sees_own_kv_traffic(self):
        from repro.cluster.network import BackgroundTraffic, FlowPlane
        from repro.cluster.topology import FatTree

        net = FlowPlane(FatTree(), BackgroundTraffic(0.2), seed=0)
        net.start_transfer((0, 0, 0), (1, 1, 1), 1e12, 0.0, lambda t, n: None)
        with_kv = net.measured_tier_congestion(0.0)
        without = net.measured_tier_congestion(0.0, include_kv=False)
        assert with_kv[3] > without[3]  # cross-pod flow shows in the counters
        for t in (1, 2, 3):
            assert without[t] == pytest.approx(0.2, abs=1e-9)

    def test_sim_runs_with_measured_source(self):
        m = run_sim(_cfg("netkv-full", telemetry_source="measured"),
                    TRACE[: len(TRACE) // 2])
        assert np.isfinite(m.ttft_mean)

    def test_unknown_source_rejected(self):
        with pytest.raises(ValueError):
            Simulation(_cfg("netkv-full", telemetry_source="sflow"))


class TestHybridStateBytes:
    """Eq. (2'): a hybrid model's transfers carry the KV its prefix hit
    leaves and the fixed Mamba-2 state whole, through every scoring
    backend, and the forensics rows name the state beside s_eff."""

    @pytest.mark.parametrize("backend", ["numpy", "pallas"])
    def test_every_transfer_is_eq2_prime(self, backend):
        tr = generate_trace("rag", duration=2.0, target_rps=25.0, seed=5)
        cfg = _cfg("netkv-full", seed=5, warmup=0.5, measure=1.5,
                   kv_spec=HYBRID_KV, trace=True,
                   scheduler_kwargs={"backend": backend})
        sim = Simulation(cfg)
        sim.run(tr, drain=40.0)
        sent: dict[int, float] = {}
        for kind, req, t0, t1, inst, tier, a, b in sim.trace.spans():
            if kind == "xfer_seg":
                sent[req] = sent.get(req, 0.0) + a
        served = [rs for rs in sim.records if rs.decode_instance >= 0]
        assert len(served) == len(sim.records) > 20
        assert any(rs.hit_tokens > 0 for rs in served)
        for rs in served:
            want = plain_bytes(rs.req.input_len, rs.hit_tokens)
            assert rs.s_eff == want and rs.state_bytes == STATE
            assert sent[rs.req.request_id] == want, rs.req.request_id
        rows = [dict(zip(FORENSICS_COLUMNS, r))
                for r in sim.trace.forensics_rows()]
        assert rows and all(r["state_win"] == STATE <= r["s_eff_win"]
                            for r in rows)
