"""Scheduler ladder unit/property tests + JAX scorer equivalence."""

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (
    CandidateState,
    H100_TP4_ITER,
    NetworkCostOracle,
    RequestInfo,
    SelfContentionTracker,
    make_scheduler,
)
from repro.core.netkv_jax import JaxNetKV, PoolArrays
from repro.core.batch_assign import NetKVBatch
from repro.core.oracle import OracleView, PAPER_TIER_BANDWIDTH, PAPER_TIER_LATENCY

from hybrid_shape import HYBRID_KV, STATE


def _view(congestion=None):
    tiers = {(0, 1): 2, (0, 2): 3, (0, 3): 3, (0, 4): 2}
    return OracleView(
        tier_of=lambda p, d: tiers.get((p, d), 3),
        tier_bandwidth=PAPER_TIER_BANDWIDTH,
        tier_latency=PAPER_TIER_LATENCY,
        congestion=congestion or {t: 0.0 for t in range(4)},
    )


def _cands(**over):
    base = [
        CandidateState(1, 2e11, 0, 4, 0.0),
        CandidateState(2, 2e11, 0, 4, 0.0),
        CandidateState(3, 2e11, 0, 4, 0.0),
        CandidateState(4, 2e11, 0, 4, 0.0),
    ]
    for idx, kw in over.items():
        for k, v in kw.items():
            setattr(base[idx], k, v)
    return base


REQ = RequestInfo(0, 8192, 8192 * 320 * 1024)
# The same request with a hybrid model's fixed state in its s_r (Eq. 2').
HYBRID_REQ = RequestInfo(0, 8192, float(HYBRID_KV.kv_bytes(8192)),
                         state_bytes=float(STATE))
REQS = pytest.mark.parametrize("req", [REQ, HYBRID_REQ], ids=["attn", "hybrid"])


def _mk(name, **kw):
    return make_scheduler(name, H100_TP4_ITER, 64, m_min=1e9, **kw)


class TestFeasibility:
    def test_memory_filter(self):
        s = _mk("netkv-full")
        cands = _cands()
        for c in cands:
            c.free_memory = 1e6  # below s_eff + m_min
        assert s.select(REQ, 0, cands, _view()) is None

    def test_unhealthy_filtered(self):
        s = _mk("netkv-full")
        cands = _cands()
        for c in cands[1:]:
            c.healthy = False
        d = s.select(REQ, 0, cands, _view())
        assert d.instance_id == 1

    def test_full_hit_always_feasible(self):
        """100% prefix hit -> s_eff = 0 -> only m_min required."""
        s = _mk("netkv-full")
        cands = _cands()
        for c in cands:
            c.free_memory = 2e9
            c.hit_tokens = REQ.input_len
        assert s.select(REQ, 0, cands, _view()) is not None


class TestNetKVDecisions:
    def test_prefers_same_pod_all_else_equal(self):
        s = _mk("netkv-full")
        d = s.select(REQ, 0, _cands(), _view())
        assert d.tier == 2  # candidates 1 and 4 are tier 2

    def test_cache_beats_tier_when_big_enough(self):
        """§III-D: warm cross-pod beats cold same-pod at 90% hit."""
        s = _mk("netkv-full")
        cands = _cands()
        cands[1].hit_tokens = 0.9 * REQ.input_len  # instance 2, tier 3
        d = s.select(REQ, 0, cands, _view())
        assert d.instance_id == 2

    def test_congestion_flips_decision(self):
        """§III-D: perturbing cross-pod congestion flips the verdict."""
        s = _mk("netkv-full")
        cands = _cands()
        cands[1].hit_tokens = 0.75 * REQ.input_len
        assert s.select(REQ, 0, cands, _view()).instance_id == 2
        cands = _cands()
        cands[1].hit_tokens = 0.75 * REQ.input_len
        d = s.select(REQ, 0, cands, _view({0: 0, 1: 0, 2: 0.0, 3: 0.72}))
        assert d.tier == 2

    def test_self_contention_spreads_load(self):
        s = _mk("netkv-static")
        infl = SelfContentionTracker()
        picks = []
        for _ in range(4):
            d = s.select(REQ, 0, _cands(), _view(), infl)
            picks.append(d.tier)
        # once tier 2 carries in-flight transfers, tier 3 gets picked
        assert 3 in picks and 2 in picks

    def test_topo_only_ignores_contention(self):
        s = _mk("netkv-topo")
        infl = SelfContentionTracker()
        for _ in range(4):
            d = s.select(REQ, 0, _cands(), _view(), infl)
            assert d.tier == 2  # never reacts
        assert infl.get(0, 2) == 0  # and never increments

    def test_inflight_cap(self):
        t = SelfContentionTracker(cap=3)
        for _ in range(10):
            t.incr(0, 2)
        assert t.get(0, 2) == 3


class TestLadderInformationOrder:
    def test_rr_cycles(self):
        s = _mk("rr")
        picks = [s.select(REQ, 0, _cands(), _view()).instance_id for _ in range(8)]
        assert picks[:4] == [1, 2, 3, 4] and picks[4:] == [1, 2, 3, 4]

    def test_la_prefers_empty(self):
        s = _mk("la")
        cands = _cands()
        cands[2].batch_size = 0
        for i, c in enumerate(cands):
            if i != 2:
                c.batch_size = 60
                c.queued = 20
        assert s.select(REQ, 0, cands, _view()).instance_id == 3

    def test_ca_prefers_warm(self):
        s = _mk("ca")
        cands = _cands()
        cands[3].hit_tokens = 4096
        assert s.select(REQ, 0, cands, _view()).instance_id == 4

    def test_cla_trades_off(self):
        s = _mk("cla", w_cache=1.0, w_load=1.0)
        cands = _cands()
        cands[0].hit_tokens = REQ.input_len  # warm but overloaded
        cands[0].queued = 500
        cands[0].batch_size = 64
        d = s.select(REQ, 0, cands, _view())
        assert d.instance_id != 1


class TestJaxScorerEquivalence:
    @REQS
    @given(data=st.data())
    @settings(max_examples=30, deadline=None)
    def test_matches_python_netkv(self, req, data):
        rng = np.random.default_rng(data.draw(st.integers(0, 10_000)))
        n = data.draw(st.integers(2, 24))
        cands = [
            CandidateState(
                instance_id=i,
                free_memory=float(rng.uniform(1e9, 4e11)),
                queued=int(rng.integers(0, 10)),
                batch_size=int(rng.integers(0, 64)),
                hit_tokens=float(rng.integers(0, req.input_len)),
                healthy=bool(rng.random() > 0.1),
                iter_scale=float(rng.uniform(1.0, 2.0)),
            )
            for i in range(n)
        ]
        tiers = rng.integers(0, 4, n)
        view = OracleView(
            tier_of=lambda p, d: int(tiers[d]),
            tier_bandwidth=PAPER_TIER_BANDWIDTH,
            tier_latency=PAPER_TIER_LATENCY,
            congestion={t: float(rng.uniform(0, 0.8)) for t in range(4)},
        )
        py = _mk("netkv-full")
        d_py = py.select(req, 0, cands, view, None)

        jx = JaxNetKV(H100_TP4_ITER, 64, m_min=1e9)
        pool = PoolArrays.from_candidates(cands, tiers)
        idx, costs = jx.select_arrays(pool, req.kv_bytes, req.input_len, view,
                                      [0, 0, 0, 0], req.state_bytes)
        if d_py is None:
            assert idx is None
        else:
            # same winner (cost ties broken identically by argmin order)
            assert cands[idx].instance_id == d_py.instance_id or \
                abs(float(costs[idx]) - d_py.cost) < 1e-5


class TestBatchAssignment:
    @REQS
    def test_window_of_one_equals_greedy(self, req):
        b = NetKVBatch(H100_TP4_ITER, 64, m_min=1e9)
        g = _mk("netkv-full")
        cands, cands_g = _cands(), _cands()
        for c in cands + cands_g:
            c.hit_tokens = 4096.0              # half the prompt on every one
        d_b = b.select_batch([(req, 0)], [cands], _view(), None)[0]
        d_g = g.select(req, 0, cands_g, _view(), None)
        # identical candidates tie; both must pick the same-cost (tier) choice
        assert d_b.tier == d_g.tier
        assert abs(d_b.cost - d_g.cost) < 1e-12
        # Eq. (2'): the hit halves the KV, never the fixed state.
        kv = req.kv_bytes - req.state_bytes
        assert d_b.s_eff == d_g.s_eff == kv * 0.5 + req.state_bytes

    def test_joint_window_spreads(self):
        """Two same-window requests should not both pile onto one instance
        when the marginal costs say otherwise."""
        b = NetKVBatch(H100_TP4_ITER, 64, m_min=1e9)
        infl = SelfContentionTracker()
        reqs = [(RequestInfo(i, 8192, 8192 * 320 * 1024), 0) for i in range(4)]
        cands = _cands()
        ds = b.select_batch(reqs, [cands] * 4, _view(), infl)
        assert all(d is not None for d in ds)
        tiers = [d.tier for d in ds]
        assert 3 in tiers  # contention pushed someone cross-pod
