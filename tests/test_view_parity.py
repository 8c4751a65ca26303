"""Scorer parity: vectorised ClusterView ladder vs the retired Python loop.

For every ladder policy the vectorised path must pick the same instance with
the same ``Decision`` cost/tier/s_eff/est_transfer_time as the per-candidate
reference loop (``repro.core.reference``), including deterministic
tie-breaking under fixed seeds, rejection behaviour, and the all-infeasible
-> ``None`` case.  The Pallas ``netkv_score`` backend (f32, interpret mode
on CPU) is parity-checked on the winner with a cost tolerance.
"""

import numpy as np
import pytest

from repro.core import (
    CandidateState,
    ClusterView,
    H100_TP4_ITER,
    RequestInfo,
    SelfContentionTracker,
    make_reference_scheduler,
    make_scheduler,
)
from repro.core.cost import IterTimeModel
from repro.core.oracle import OracleView, PAPER_TIER_BANDWIDTH, PAPER_TIER_LATENCY

from hybrid_shape import HYBRID_KV, STATE

LADDER = ["rr", "la", "ca", "cla", "netkv-topo", "netkv-static", "netkv-full",
          "netkv-pred"]
REQ = RequestInfo(0, 8192, 8192 * 320 * 1024)
# The same request with a hybrid model's fixed state in its s_r (Eq. 2').
HYBRID_REQ = RequestInfo(0, 8192, float(HYBRID_KV.kv_bytes(8192)),
                         state_bytes=float(STATE))
REQS = pytest.mark.parametrize("req", [REQ, HYBRID_REQ], ids=["attn", "hybrid"])
# A piecewise iter model exercises the v_iter_time segments too.
PIECEWISE_ITER = IterTimeModel(a=0.0124, b=1.6e-5, breaks=(32.0,), slopes=(4e-5,))


def _pool(rng, n, all_infeasible=False):
    return [
        CandidateState(
            instance_id=i + 1,
            free_memory=1e5 if all_infeasible else float(rng.uniform(1e9, 4e11)),
            queued=int(rng.integers(0, 10)),
            batch_size=int(rng.integers(0, 64)),
            hit_tokens=float(rng.integers(0, REQ.input_len)),
            healthy=bool(rng.random() > 0.15),
            iter_scale=float(rng.uniform(1.0, 2.0)),
        )
        for i in range(n)
    ]


def _oracle(rng, n):
    tiers = rng.integers(0, 4, n + 1)
    return OracleView(
        tier_of=lambda p, d: int(tiers[d % len(tiers)]),
        tier_bandwidth=PAPER_TIER_BANDWIDTH,
        tier_latency=PAPER_TIER_LATENCY,
        congestion={t: float(rng.uniform(0, 0.8)) for t in range(4)},
    )


def _assert_same(d_new, d_ref):
    if d_ref is None:
        assert d_new is None
        return
    assert d_new is not None
    assert d_new.instance_id == d_ref.instance_id
    assert d_new.cost == d_ref.cost
    assert d_new.tier == d_ref.tier
    assert d_new.s_eff == d_ref.s_eff
    assert d_new.est_transfer_time == d_ref.est_transfer_time


class TestLadderParity:
    @REQS
    @pytest.mark.parametrize("name", LADDER)
    @pytest.mark.parametrize("seed", range(8))
    def test_seed_sweep_bit_identical(self, name, seed, req):
        """Sequential decisions (shared contention state) match bit-for-bit."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 40))
        cands = _pool(rng, n)
        view = _oracle(rng, n)
        s_new = make_scheduler(name, H100_TP4_ITER, 64, m_min=1e9, seed=seed)
        s_ref = make_reference_scheduler(name, H100_TP4_ITER, 64, m_min=1e9, seed=seed)
        infl_new, infl_ref = SelfContentionTracker(), SelfContentionTracker()
        for _ in range(4):
            _assert_same(
                s_new.select(req, 0, cands, view, infl_new),
                s_ref.select(req, 0, cands, view, infl_ref),
            )
        assert infl_new._counts == infl_ref._counts

    @pytest.mark.parametrize("name", LADDER)
    def test_piecewise_iter_model(self, name):
        rng = np.random.default_rng(99)
        cands = _pool(rng, 24)
        view = _oracle(rng, 24)
        s_new = make_scheduler(name, PIECEWISE_ITER, 64, m_min=1e9)
        s_ref = make_reference_scheduler(name, PIECEWISE_ITER, 64, m_min=1e9)
        _assert_same(s_new.select(REQ, 0, cands, view, None),
                     s_ref.select(REQ, 0, cands, view, None))

    @pytest.mark.parametrize("name", LADDER)
    def test_all_infeasible_rejects(self, name):
        rng = np.random.default_rng(3)
        cands = _pool(rng, 12, all_infeasible=True)
        view = _oracle(rng, 12)
        assert make_scheduler(name, H100_TP4_ITER, 64, m_min=1e9).select(
            REQ, 0, cands, view, None) is None
        assert make_reference_scheduler(name, H100_TP4_ITER, 64, m_min=1e9).select(
            REQ, 0, cands, view, None) is None

    @pytest.mark.parametrize("name", LADDER)
    def test_exact_tie_breaking_deterministic(self, name):
        """Identical candidates: ties resolved by the shared RNG stream —
        same seed picks the same winner as the reference, twice over."""
        view = _oracle(np.random.default_rng(0), 8)
        for seed in range(5):
            cands = [CandidateState(i + 1, 2e11, 0, 4, 0.0) for i in range(8)]
            picks = []
            for mk in (make_scheduler, make_reference_scheduler,
                       make_scheduler, make_reference_scheduler):
                s = mk(name, H100_TP4_ITER, 64, m_min=1e9, seed=seed)
                picks.append(s.select(REQ, 0, cands, view, None).instance_id)
            assert len(set(picks)) == 1

    def test_view_and_candidate_list_agree(self):
        """select() over a maintained ClusterView == select() over the
        equivalent CandidateState list."""
        rng = np.random.default_rng(11)
        cands = _pool(rng, 16)
        view = _oracle(rng, 16)
        cv = ClusterView.from_candidates(cands, tier_fn=view.tier_of)
        a = make_scheduler("netkv-full", H100_TP4_ITER, 64, m_min=1e9)
        b = make_scheduler("netkv-full", H100_TP4_ITER, 64, m_min=1e9)
        _assert_same(a.select(REQ, 0, cv, view, None),
                     b.select(REQ, 0, cands, view, None))


class TestPallasBackendParity:
    @REQS
    @pytest.mark.parametrize("seed", range(4))
    def test_winner_matches_numpy(self, seed, req):
        rng = np.random.default_rng(seed + 100)
        n = int(rng.integers(4, 48))
        cands = _pool(rng, n)
        view = _oracle(rng, n)
        d_np = make_scheduler("netkv-full", H100_TP4_ITER, 64, m_min=1e9).select(
            req, 0, cands, view, None)
        d_pl = make_scheduler("netkv-full", H100_TP4_ITER, 64, m_min=1e9,
                              backend="pallas").select(req, 0, cands, view, None)
        if d_np is None:
            assert d_pl is None
            return
        # f32 scoring: same winner (or an equal-cost winner within f32 eps).
        assert d_pl.instance_id == d_np.instance_id or \
            abs(d_pl.cost - d_np.cost) < 1e-5 * max(abs(d_np.cost), 1e-9)
        assert d_pl.tier == d_np.tier or d_pl.instance_id != d_np.instance_id
        assert d_pl.s_eff == d_np.s_eff or d_pl.instance_id != d_np.instance_id

    def test_all_infeasible_rejects(self):
        rng = np.random.default_rng(0)
        cands = _pool(rng, 8, all_infeasible=True)
        view = _oracle(rng, 8)
        s = make_scheduler("netkv-full", H100_TP4_ITER, 64, m_min=1e9,
                           backend="pallas")
        assert s.select(REQ, 0, cands, view, None) is None

    def test_piecewise_model_rejected_at_construction(self):
        with pytest.raises(ValueError):
            make_scheduler("netkv-full", PIECEWISE_ITER, 64, backend="pallas")


class TestClusterViewMaintenance:
    def test_slot_map_and_growth(self):
        cv = ClusterView(capacity=2)
        slots = [cv.add_instance(10 * i, free_memory=float(i)) for i in range(9)]
        assert slots == list(range(9))
        assert cv.n == 9
        for i in range(9):
            assert cv.slot_of(10 * i) == i
            assert cv.free_memory[i] == float(i)
        with pytest.raises(ValueError):
            cv.add_instance(0)

    def test_tier_rows_cached_and_invalidated(self):
        calls = []

        def tier_fn(a, b):
            calls.append((a, b))
            return (a + b) % 4

        cv = ClusterView(tier_fn=tier_fn)
        cv.add_instance(1)
        cv.add_instance(2)
        row = cv.tier_row(0)
        assert list(row) == [1, 2]
        cv.tier_row(0)
        assert len(calls) == 2          # second lookup served from cache
        cv.add_instance(3)              # membership change invalidates rows
        assert list(cv.tier_row(0)) == [1, 2, 3]
