"""ChunkPlane: chunk-interleaved prefill + streamed KV transfer.

Covers the PR-5 tentpole and its satellites:

* plane vs reference bit-exact parity in chunked mode (streaming off/on,
  with faults and mid-stream OCS rewires),
* chunk-duration conservation (the per-request compute telescopes to the
  monolithic ``c*l + d``) and byte conservation of streamed transfers,
* the serial ETA-fold shortcut audited at the queue-drain boundary,
* open-flow-counter parity after fault-driven aborts (the least-loaded
  NIC policy's signal),
* NaN-safe metrics rows for degenerate measurement windows,
* the streamed-overlap transfer-time column vs its scalar oracle twin.
"""

import math

import numpy as np
import pytest

from hybrid_shape import HYBRID_KV, STATE, plain_bytes
from hypothesis_compat import given, settings, st

from repro.core.cost import (
    H100_TP4_ITER,
    H100_TP4_PREFILL,
    LLAMA3_70B_KV,
    PrefillTimeModel,
    streamed_transfer_time,
)
from repro.core.oracle import OracleView, PAPER_TIER_BANDWIDTH, PAPER_TIER_LATENCY
from repro.core.schedulers import v_transfer_time
from repro.core.view import ClusterView
from repro.sim import (
    EventLoop,
    FaultEvent,
    InstancePlane,
    ReferenceInstanceEngine,
    RequestState,
    RewireEvent,
    SimConfig,
    Simulation,
)
from repro.sim.metrics import summarize
from repro.traces import generate_trace, profile_capacity
from repro.traces.mooncake import Request

TREE_64 = dict(n_pods=2, racks_per_pod=2, servers_per_rack=2, n_prefill=4)


def _trace(seed, duration=5.0):
    cap = profile_capacity("rag", n_prefill=4, n_decode=12)
    return generate_trace("rag", duration=duration, target_rps=cap, seed=seed)


def _run(engine, seed=0, duration=5.0, faults=(), rewires=(), **kw):
    kw.setdefault("background", 0.2)
    cfg = SimConfig(scheduler="netkv-full", seed=seed,
                    warmup=1.0, measure=3.0, instance_engine=engine,
                    faults=faults, rewires=rewires, **TREE_64, **kw)
    sim = Simulation(cfg)
    sim.run(_trace(seed, duration), drain=40.0)
    return sim


def _outcomes(sim):
    return [
        (r.req.request_id, r.prefill_instance, r.prefill_start, r.prefill_end,
         r.sched_time, r.decode_instance, r.tier, r.s_eff, r.state_bytes,
         r.hit_tokens, r.transfer_end, r.admit_time, r.first_token, r.finish,
         r.tbt, r.tokens_out, r.rejected, r.requeues, r.tokens_ready,
         r.streamed_bytes)
        for r in sim.records
    ]


class TestChunkedParity:
    """InstancePlane's ChunkPlane vs the scalar ChunkedPrefillSim oracle."""

    @pytest.mark.parametrize("chunk,budget", [(512, None), (768, 3072)])
    def test_chunked_bit_exact(self, chunk, budget):
        a = _run("plane", chunk_tokens=chunk, prefill_token_budget=budget)
        b = _run("reference", chunk_tokens=chunk, prefill_token_budget=budget)
        assert _outcomes(a) == _outcomes(b)
        assert a.engine.chunks.iterations > len(a.records)  # interleaved

    @pytest.mark.parametrize("kv", [LLAMA3_70B_KV, HYBRID_KV],
                             ids=["attn", "hybrid"])
    def test_streaming_bit_exact(self, kv):
        a = _run("plane", chunk_tokens=512, kv_streaming=True, kv_spec=kv)
        b = _run("reference", chunk_tokens=512, kv_streaming=True, kv_spec=kv)
        assert _outcomes(a) == _outcomes(b)
        streamed = [r for r in a.records if r.streamed_bytes > 0]
        assert streamed  # the streaming path actually ran

    def test_streaming_with_faults_bit_exact(self):
        faults = (FaultEvent(time=1.6, kind="kill_decode", instance_id=5,
                             detection_delay=0.3),
                  FaultEvent(time=2.2, kind="slowdown", instance_id=7,
                             factor=3.0))
        a = _run("plane", seed=1, chunk_tokens=512, kv_streaming=True,
                 faults=faults)
        b = _run("reference", seed=1, chunk_tokens=512, kv_streaming=True,
                 faults=faults)
        assert _outcomes(a) == _outcomes(b)
        assert sum(r.requeues for r in a.records) > 0  # fault path exercised

    def test_serial_mode_untouched(self):
        """chunk_tokens=None reproduces the serial model bit-for-bit (the
        full 64/256-GPU suites live in test_instanceplane_parity.py)."""
        a = _run("plane", duration=3.0)
        b = _run("reference", duration=3.0)
        assert _outcomes(a) == _outcomes(b)
        assert a.engine.chunks is None


class TestStreamedBytes:
    """Byte conservation of the streamed transfer path."""

    @pytest.mark.parametrize("kv", [LLAMA3_70B_KV, HYBRID_KV],
                             ids=["attn", "hybrid"])
    def test_streamed_bytes_telescope_to_s_eff(self, kv):
        sim = _run("plane", chunk_tokens=512, kv_streaming=True, kv_spec=kv)
        done = [r for r in sim.records if r.stream_last]
        assert done
        for r in done:
            assert r.streamed_bytes == pytest.approx(r.s_eff, rel=1e-12)
            assert r.state_bytes == kv.fixed_state_bytes

    @pytest.mark.parametrize("hit", [0, 2000, 4096, 8192])
    def test_state_rides_with_the_final_chunk(self, hit):
        """The KV streams pro rata as its chunks prefill; the fixed state
        exists only at the prompt's end and ships with the final chunk
        (as ``pack_transfer_chunk(final=True)`` does), also when a full
        prefix hit leaves no KV to stream."""
        sim = Simulation(SimConfig(scheduler="netkv-full", chunk_tokens=512,
                                   kv_streaming=True, kv_spec=HYBRID_KV,
                                   **TREE_64))
        sent = []
        sim.net.start_transfer = lambda src, dst, n, now, **kw: sent.append(n)
        sim._reschedule_net = lambda now: None
        l = 8192
        req = Request(request_id=0, arrival=0.0, input_len=l, output_len=4,
                      block_hashes=(), share_group=-1, slo=5.0)
        ids = sorted(sim._server_of)
        rs = RequestState(req=req, kv_bytes=float(HYBRID_KV.kv_bytes(l)),
                          prefill_instance=ids[0], decode_instance=ids[-1],
                          tier=2, s_eff=plain_bytes(l, hit),
                          state_bytes=float(STATE), hit_tokens=float(hit))
        per_chunk = []
        for ready in range(512, l + 1, 512):
            rs.tokens_ready = ready
            n0 = len(sent)
            sim._stream_chunks(rs, 0.0)
            per_chunk.append(sum(sent[n0:]))
        assert rs.stream_last and rs.streamed_bytes == rs.s_eff
        assert sum(sent) == pytest.approx(rs.s_eff, rel=1e-12)
        kv = rs.s_eff - STATE
        assert per_chunk[-1] == pytest.approx(kv * 512 / (l - hit) + STATE
                                              if hit < l else STATE, rel=1e-12)
        for k, ready in enumerate(range(512, l, 512)):
            want = kv * (min(max(ready - hit, 0), l - hit) / (l - hit)) \
                if hit < l else 0.0
            assert sum(per_chunk[:k + 1]) == pytest.approx(want, rel=1e-12)

    def test_conservation_across_midstream_rewires(self):
        """An OCS rewire mid-stream re-water-fills in-flight chunk flows;
        the per-request streamed byte total must still telescope to s_eff
        and both engines must agree bit-for-bit."""
        rewires = (RewireEvent(time=1.8, scale={2: 0.25, 3: 0.25}),
                   RewireEvent(time=2.8, scale={2: 4.0, 3: 4.0}))
        a = _run("plane", chunk_tokens=512, kv_streaming=True, rewires=rewires)
        b = _run("reference", chunk_tokens=512, kv_streaming=True,
                 rewires=rewires)
        assert _outcomes(a) == _outcomes(b)
        for r in a.records:
            if r.stream_last:
                assert r.streamed_bytes == pytest.approx(r.s_eff, rel=1e-12)

    def test_streaming_overlaps_and_cuts_ttft(self):
        """The whole point: transfer overlaps prefill, so mean TTFT drops
        vs the same chunked run without streaming."""
        base = _run("plane", chunk_tokens=1024, background=0.4)
        stream = _run("plane", chunk_tokens=1024, kv_streaming=True,
                      background=0.4)
        mb = summarize(base.records, window=(1.0, 4.0), scheduler="x")
        ms = summarize(stream.records, window=(1.0, 4.0), scheduler="x")
        assert ms.xfer_mean < mb.xfer_mean


class TestStreamingFaultEdges:
    """Regressions for the streamed-dispatch fault/rejection edges."""

    def test_requeue_cancels_stream_despite_stale_prefill_end(self):
        """A requeued request may carry a *stale* prefill_end from an
        earlier completed attempt while its current attempt is still
        mid-prefill; _requeue must cancel the live chunk stream anyway
        (and reset prefill_end), or the orphaned stream keeps firing
        chunk callbacks for a request being re-scheduled elsewhere."""
        cfg = SimConfig(scheduler="netkv-full", seed=0, warmup=1.0,
                        measure=3.0, chunk_tokens=512, kv_streaming=True,
                        **TREE_64)
        sim = Simulation(cfg)
        sim.load_trace([])
        rs = _req(0, 4096)
        sim.engine.pick_prefill(0.0).submit(rs, 0.0)
        assert int(sim.engine.chunks.backlog.sum()) > 0
        rs.prefill_end = 0.5          # stale value from a previous attempt
        sim._requeue(rs, 0.0)         # resubmits via _on_arrival
        # Old stream cancelled, exactly one fresh stream: the total chunk
        # backlog is one request's worth, not two.
        claimed = sum(
            take for infl in sim.engine.chunks.inflight if infl
            for st, take in infl if not st.cancelled
        )
        assert int(sim.engine.chunks.backlog.sum()) + claimed == 4096
        assert rs.prefill_end == -1.0

    def test_first_chunk_rejection_counted_once(self):
        """A request rejected at first-chunk scheduling must not be
        re-scheduled (or re-counted) when its prefill later completes."""
        cfg = SimConfig(scheduler="netkv-full", seed=0, warmup=1.0,
                        measure=3.0, background=0.2, chunk_tokens=512,
                        kv_streaming=True, m_min=1e18, **TREE_64)
        sim = Simulation(cfg)
        sim.run(_trace(0, duration=3.0), drain=30.0)
        n_arrived = sum(1 for r in sim.records if r.prefill_instance >= 0)
        assert n_arrived > 0
        assert all(r.rejected for r in sim.records)
        assert sim.rejected == len(sim.records)  # one count per request

    def test_streaming_refuses_batch_window(self):
        with pytest.raises(ValueError, match="netkv-batch"):
            Simulation(SimConfig(scheduler="netkv-batch", chunk_tokens=512,
                                 kv_streaming=True, **TREE_64))

    def test_kill_between_chunk_transfers_requeues_at_fault_time(self):
        """A streamed victim caught *between* chunk transfers (stream_open
        == 0, next chunk still prefilling) must be cancelled and requeued
        at fault time — not keep streaming KV to the dead instance until
        the last byte bounces."""
        # Fat pipes everywhere: each chunk's transfer drains well inside
        # the next chunk's prefill time, so stream_open dwells at 0.
        cfg = SimConfig(scheduler="netkv-full", seed=0, warmup=0.0,
                        measure=3.0, background=0.0, chunk_tokens=512,
                        kv_streaming=True,
                        tier_bandwidth={t: 1e12 for t in range(4)},
                        **TREE_64)
        sim = Simulation(cfg)
        req = Request(request_id=0, arrival=0.0, input_len=8192, output_len=4,
                      block_hashes=tuple((0, j) for j in range(8192 // 16)),
                      share_group=-1, slo=5.0)
        rs = RequestState(req=req, kv_bytes=float(cfg.kv_spec.kv_bytes(8192)))
        sim.records.append(rs)
        sim.loop.at(0.0, lambda now: sim._on_arrival(rs, now))
        # Run until the first chunk committed a decode target.
        while not rs.stream_scheduled and sim.loop.next_time() is not None:
            sim.loop.run(until=sim.loop.next_time())
        assert rs.stream_scheduled and rs.prefill_end < 0
        victim = rs.decode_instance
        # Step to an instant with no chunk transfer in flight (tier
        # transfers drain far faster than the next 512-token chunk
        # prefills), then kill the chosen decode instance.
        while rs.stream_open > 0:
            sim.loop.run(until=sim.loop.next_time())
        assert rs.stream_open == 0 and rs.prefill_end < 0
        t_fault = sim.loop.now + 1e-4
        sim.loop.at(t_fault, lambda now: sim._on_fault(
            FaultEvent(time=now, kind="kill_decode", instance_id=victim), now))
        sim.loop.run(until=t_fault)
        assert rs.requeues == 1          # requeued AT the fault instant
        assert not rs.stream_scheduled   # streaming state reset
        sim.loop.run()
        assert rs.finish >= 0 and rs.decode_instance != victim


class _Meta:
    def __init__(self, iid, srv):
        self.instance_id, self.server = iid, srv


def _mk_engines(chunk, budget, n_pre=2, model=H100_TP4_PREFILL):
    out = []
    for cls in (InstancePlane, ReferenceInstanceEngine):
        loop = EventLoop()
        view = ClusterView(capacity=1)
        pre = [_Meta(i, (0, 0, i)) for i in range(n_pre)]
        eng = cls(pre, [], view=view, loop=loop, iter_model=H100_TP4_ITER,
                  prefill_model=model, beta_max=64, kv_spec=LLAMA3_70B_KV,
                  kv_budget=1e18, chunk_tokens=chunk,
                  prefill_token_budget=budget)
        out.append((loop, eng))
    return out


def _req(rid, l):
    return RequestState(
        req=Request(request_id=rid, arrival=0.0, input_len=l, output_len=4,
                    block_hashes=((rid, 0),), share_group=-1, slo=5.0),
        kv_bytes=1.0,
    )


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_chunk_duration_conservation(data):
    """Per-request prefill compute telescopes to the monolithic c*l + d,
    and the instance makespan to c*suml + d*n (the fixed overhead rides
    with each request's first chunk)."""
    chunk = data.draw(st.integers(16, 2048), label="chunk")
    budget = data.draw(st.one_of(st.none(), st.integers(16, 8192)),
                       label="budget")
    lens = data.draw(st.lists(st.integers(1, 6000), min_size=1, max_size=6),
                     label="lens")
    model = H100_TP4_PREFILL
    (loop, eng), _ = _mk_engines(chunk, budget, n_pre=1)
    rss = [_req(i, l) for i, l in enumerate(lens)]
    got = []
    eng.on_prefill_done = lambda rs, now: got.append(rs)
    for rs in rss:
        eng.prefill[0].submit(rs, 0.0)
    loop.run()
    assert len(got) == len(rss)
    solo = len(rss) == 1
    for rs, l in zip(rss, lens):
        assert rs.prefill_end >= rs.prefill_start
        if solo:  # alone on the instance: end - start is exactly T_prefill(l)
            assert rs.prefill_end - rs.prefill_start == pytest.approx(
                model.c * l + model.d, rel=1e-9)
    makespan = max(rs.prefill_end for rs in rss)
    assert makespan == pytest.approx(
        model.c * sum(lens) + model.d * len(lens), rel=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_chunk_engine_event_parity(data):
    """Random chunk/budget/length mixes: the plane and the scalar oracle
    emit identical (request, tokens_ready, time) chunk-completion streams
    and identical prefill_start/end fields — bit-for-bit."""
    chunk = data.draw(st.integers(16, 1024), label="chunk")
    budget = data.draw(st.one_of(st.none(), st.integers(16, 4096)),
                       label="budget")
    lens = data.draw(st.lists(st.integers(1, 4000), min_size=1, max_size=8),
                     label="lens")
    n_pre = data.draw(st.integers(1, 3), label="n_pre")
    seqs = []
    for loop, eng in _mk_engines(chunk, budget, n_pre=n_pre):
        events = []
        eng.on_chunk_done = lambda rs, tok, now: events.append(
            ("chunk", rs.req.request_id, tok, now))
        eng.on_prefill_done = lambda rs, now: events.append(
            ("done", rs.req.request_id, now))
        rss = [_req(i, l) for i, l in enumerate(lens)]
        for rs in rss:
            eng.pick_prefill(0.0).submit(rs, 0.0)
        loop.run()
        seqs.append((events,
                     [(rs.prefill_instance, rs.prefill_start, rs.prefill_end)
                      for rs in rss]))
    assert seqs[0] == seqs[1]


class TestSerialEtaBoundary:
    """Satellite: the ``base = p_eta if len(q) > 1 else p_busy`` shortcut
    (sim/instances.py submit_prefill) vs the reference exact-fold walk at
    the queue-drain boundary."""

    def test_drain_and_resubmit_parity(self):
        model = H100_TP4_PREFILL
        engines = _mk_engines(None, None, n_pre=2)
        results = []
        for loop, eng in engines:
            done = []
            eng.on_prefill_done = lambda rs, now: done.append(rs)
            first = [_req(i, 1000 + 500 * i) for i in range(4)]
            for rs in first:
                eng.pick_prefill(0.0).submit(rs, 0.0)
            drain = max(model(1000), model(1500)) + model(2000) + model(2500)
            # Resubmit at the exact drain instant of the busier queue and
            # once more mid-event later; both must reproduce the reference
            # fold (max(busy, now) + sum T) bit-for-bit.
            second = [_req(10 + i, 3000 + i) for i in range(3)]

            def resub(now, eng=eng, rss=second):
                for rs in rss:
                    eng.pick_prefill(now).submit(rs, now)

            loop.at(drain, resub)
            loop.run()
            etas = [eng.prefill[s].eta(loop.now) for s in range(2)]
            results.append((
                [(rs.prefill_instance, rs.prefill_start, rs.prefill_end)
                 for rs in first + second],
                [rs.req.request_id for rs in done], etas,
            ))
        assert results[0] == results[1]

    def test_idle_resubmit_rebuilds_fold(self):
        """Queue fully drained, instance idle past busy_until: a fresh
        submit must base the fold on ``now``, not the stale busy column."""
        (loop, eng), (rloop, reng) = _mk_engines(None, None, n_pre=1)
        for l_, e in ((loop, eng), (rloop, reng)):
            rs = _req(0, 800)
            e.prefill[0].submit(rs, 0.0)
            l_.run()
            late = l_.now + 5.0
            l_.at(late, lambda now, e=e: e.prefill[0].submit(_req(1, 600), now))
            l_.run()
        assert eng.prefill[0].eta(loop.now) == reng.prefill[0].eta(rloop.now)
        assert eng.prefill[0].busy_until == reng.prefill[0].busy_until


class TestAbortCounterParity:
    """Satellite: per-link open-flow counters stay reconciled through
    fault-driven aborts in both network engines (the least-loaded NIC
    policy's signal)."""

    def _recount(self, fp):
        cnt = np.zeros(fp.tree.n_links, np.int64)
        for fv in fp.flows.values():
            for l in fv.path:
                cnt[l] += 1
        return cnt

    def test_direct_abort_counter_parity(self):
        from repro.cluster.network import BackgroundTraffic, FlowPlane
        from repro.cluster.reference import ReferenceFlowNetwork
        from repro.cluster.topology import FatTree

        bg = BackgroundTraffic(0.2)
        fp = FlowPlane(FatTree(2, 2, 2, 8, nics_per_server=4), bg, seed=0,
                       nic_policy="least-loaded")
        rf = ReferenceFlowNetwork(FatTree(2, 2, 2, 8, nics_per_server=4), bg,
                                  seed=0, nic_policy="least-loaded")
        srv = [(p, r, s) for p in range(2) for r in range(2) for s in range(2)]
        tps, trs = [], []
        for i in range(8):
            a, b = srv[i % 8], srv[(i + 3) % 8]
            tps.append(fp.start_transfer(a, b, 1e9, 0.0, lambda t, n: None))
            trs.append(rf.start_transfer(a, b, 1e9, 0.0, lambda t, n: None))
        for i in (1, 4, 6):
            fp.abort_transfer(tps[i], 0.01)
            rf.abort_transfer(trs[i], 0.01)
            assert tps[i].flows_open == 0 == trs[i].flows_open
        np.testing.assert_array_equal(fp.open_flow_counts(),
                                      rf.open_flow_counts())
        # The incremental counters also match a from-scratch recount of the
        # plane's own live flows (no leaked abort residue).
        np.testing.assert_array_equal(fp.open_flow_counts(), self._recount(fp))

    def test_fault_driven_abort_keeps_counters_consistent(self):
        """Full simulation with kills under the least-loaded policy at 4
        NICs + streaming (many in-flight chunk flows to abort): the
        FlowPlane's incremental counters must equal a live recount after
        the run, and both engines replay identically."""
        faults = (FaultEvent(time=1.5, kind="kill_decode", instance_id=4,
                             detection_delay=0.3),
                  FaultEvent(time=2.0, kind="kill_decode", instance_id=9,
                             detection_delay=0.3))
        kw = dict(chunk_tokens=512, kv_streaming=True, nics_per_server=4,
                  nic_policy="least-loaded", faults=faults)
        a = _run("plane", seed=2, **kw)
        b = _run("reference", seed=2, **kw)
        assert _outcomes(a) == _outcomes(b)
        np.testing.assert_array_equal(a.net.open_flow_counts(),
                                      self._recount(a.net))


class TestEmptyWindowMetrics:
    """Satellite: summarize must yield NaN-safe rows, never crash."""

    def test_empty_records(self):
        m = summarize([], window=(5.0, 5.0), scheduler="x")
        assert m.n_measured == 0
        assert math.isnan(m.ttft_mean) and math.isnan(m.ttft_p99)
        assert math.isnan(m.tbt_mean) and math.isnan(m.xfer_p95)
        assert math.isnan(m.slo_attainment) and math.isnan(m.hit_frac_mean)
        assert m.goodput_rps == 0.0
        m.row()  # the CSV path digests the NaNs too

    def test_window_with_no_completions(self):
        rs = _req(0, 1000)
        rs.req = Request(request_id=0, arrival=6.0, input_len=1000,
                         output_len=4, block_hashes=((0, 0),),
                         share_group=-1, slo=5.0)
        m = summarize([rs], window=(5.0, 10.0), scheduler="x")
        assert m.n_measured == 1 and m.n_unfinished == 1
        assert math.isnan(m.ttft_p50)
        assert m.slo_attainment == 0.0

    def test_done_without_valid_tbt(self):
        """A record with a first token but no valid TBT used to feed
        np.percentile an empty array and crash mid-sweep."""
        rs = _req(0, 1000)
        rs.first_token = 1.0
        rs.tbt = -1.0
        m = summarize([rs], window=(0.0, 10.0), scheduler="x")
        assert math.isnan(m.tbt_mean) and math.isnan(m.tbt_p95)
        assert np.isfinite(m.ttft_mean)

    def test_degenerate_window_in_full_sweep(self):
        """measure window entirely before any arrival: the whole summarize
        path (incl. aggregate_seeds) survives."""
        from repro.sim.metrics import aggregate_seeds

        cfg = SimConfig(scheduler="cla", seed=0, warmup=30.0, measure=1e-9,
                        background=0.2, **TREE_64)
        sim = Simulation(cfg)
        m = sim.run(_trace(0, duration=2.0), drain=10.0)
        agg = aggregate_seeds([m])
        assert math.isnan(agg["ttft_mean"])


class TestStreamedTransferTerm:
    """The ladder's overlap-aware T_xfer column vs its scalar oracle."""

    def _oracle(self):
        return OracleView(tier_of=lambda a, b: 2,
                          tier_bandwidth=dict(PAPER_TIER_BANDWIDTH),
                          tier_latency=dict(PAPER_TIER_LATENCY),
                          congestion={0: 0.0, 1: 0.2, 2: 0.3, 3: 0.5})

    def test_vector_matches_scalar(self):
        ov = self._oracle()
        s_eff = np.array([0.0, 1e9, 5e9, 2e8])
        tier_row = np.array([0, 1, 2, 3])
        nfl = {0: 0, 1: 1, 2: 0, 3: 2}
        for rem, tail in [(0.0, None), (0.4, 1e8), (2.0, 5e8), (0.1, 0.0)]:
            vec = v_transfer_time(s_eff, tier_row, ov.tier_bandwidth,
                                  ov.congestion, nfl, ov.tier_latency,
                                  prefill_remaining=rem, tail_bytes=tail)
            for i in range(len(s_eff)):
                t = int(tier_row[i])
                want = ov.est_transfer_time(
                    float(s_eff[i]), t, nfl[t],
                    prefill_remaining=rem, tail_bytes=tail)
                assert vec[i] == pytest.approx(want, rel=1e-12)

    def test_defaults_reproduce_serial(self):
        ov = self._oracle()
        s_eff = np.array([0.0, 1e9, 5e9])
        tier_row = np.array([1, 2, 3])
        nfl = {t: 0 for t in range(4)}
        a = v_transfer_time(s_eff, tier_row, ov.tier_bandwidth, ov.congestion,
                            nfl, ov.tier_latency)
        b = v_transfer_time(s_eff, tier_row, ov.tier_bandwidth, ov.congestion,
                            nfl, ov.tier_latency, prefill_remaining=0.0,
                            tail_bytes=None)
        np.testing.assert_array_equal(a, b)

    def test_overlap_credit(self):
        """The streamed estimate credits prefill/transfer overlap: it beats
        serial-after-prefill (prefill_remaining + T_xfer), never beats the
        pipe's own drain time, and degenerates to the tail when prefill
        dominates."""
        serial = streamed_transfer_time(1e9, 12.5e9, 0.0, 0, 1e-3)
        over = streamed_transfer_time(1e9, 12.5e9, 0.0, 0, 1e-3,
                                      prefill_remaining=0.05, tail_bytes=1e8)
        floor = streamed_transfer_time(1e9, 12.5e9, 0.0, 0, 1e-3,
                                       prefill_remaining=100.0, tail_bytes=1e8)
        assert over < 0.05 + serial       # beats transfer-after-prefill
        assert over >= serial             # the pipe still has to drain s_eff
        assert floor == pytest.approx(100.0 + 1e8 / 12.5e9 + 1e-3)
