"""Multi-hop KV routing (beyond paper, §VII-D): staged-fetch planning."""

import pytest

from repro.core import CandidateState, H100_TP4_ITER, RequestInfo
from repro.core.multihop import NetKVMultiHop, StagingStore
from repro.core.oracle import OracleView, PAPER_TIER_BANDWIDTH, PAPER_TIER_LATENCY

from hybrid_shape import HYBRID_KV, STATE


def _view(cong=None):
    # prefill 0; decode 1 (tier 2), decode 2 (tier 3); store 100 near decode 2
    tiers = {(0, 1): 2, (0, 2): 3, (100, 1): 3, (100, 2): 1}
    return OracleView(
        tier_of=lambda a, b: tiers.get((a, b), 3),
        tier_bandwidth=PAPER_TIER_BANDWIDTH,
        tier_latency=PAPER_TIER_LATENCY,
        congestion=cong or {t: 0.0 for t in range(4)},
    )


REQ = RequestInfo(7, 8192, 8192 * 320 * 1024)
# A hybrid model's request: a store can stage its KV, never its fixed state.
HYBRID_REQ = RequestInfo(7, 8192, float(HYBRID_KV.kv_bytes(8192)),
                         state_bytes=float(STATE))
HASHES = tuple(("g", 0, j) for j in range(8192 // 16))


def _sched(stores):
    s = NetKVMultiHop(H100_TP4_ITER, 64, m_min=1e9, stores=stores)
    s.observe_request(HASHES)
    return s


def test_cold_store_behaves_like_netkv_full():
    s = _sched([StagingStore(100, capacity_bytes=1e12)])
    d = s.select(REQ, 0, [CandidateState(1, 4e11, 0, 4, 0.0),
                          CandidateState(2, 4e11, 0, 4, 0.0)], _view())
    assert s.plans[REQ.request_id].kind == "direct"
    assert d.tier == 2  # same-pod wins as in plain NetKV


def test_warm_store_enables_staged_fetch():
    store = StagingStore(100, capacity_bytes=1e12)
    store.insert(HASHES)  # full prefix resident near decode 2
    s = _sched([store])
    cands = [CandidateState(1, 4e11, 0, 4, 0.0), CandidateState(2, 4e11, 0, 4, 0.0)]
    d = s.select(REQ, 0, cands, _view())
    plan = s.plans[REQ.request_id]
    # decode 2 fetches the whole payload from the same-rack store (tier 1)
    assert plan.kind == "staged" and plan.store_id == 100
    assert d.instance_id == 2
    assert plan.staged_bytes > 0 and plan.direct_bytes == 0


@pytest.mark.parametrize("req", [REQ, HYBRID_REQ], ids=["attn", "hybrid"])
def test_partial_hit_splits_legs(req):
    store = StagingStore(100, capacity_bytes=1e12)
    store.insert(HASHES[: len(HASHES) // 2])
    s = _sched([store])
    cands = [CandidateState(2, 4e11, 0, 4, 0.0)]
    d = s.select(req, 0, cands, _view())
    plan = s.plans[req.request_id]
    assert plan.kind == "staged"
    assert plan.staged_bytes > 0 and plan.direct_bytes > 0
    assert abs(plan.staged_bytes + plan.direct_bytes - req.kv_bytes) < 1e-3 * req.kv_bytes
    # The store holds half the prompt's KV; the state rides the direct leg.
    assert plan.staged_bytes == pytest.approx((req.kv_bytes - req.state_bytes) / 2)


def test_dram_bandwidth_caps_staged_leg():
    fast = StagingStore(100, capacity_bytes=1e12, dram_bw=1e12)
    slow = StagingStore(100, capacity_bytes=1e12, dram_bw=1e8)  # 100 MB/s
    fast.insert(HASHES)
    slow.insert(HASHES)
    t_fast = _sched([fast]).select(REQ, 0, [CandidateState(2, 4e11, 0, 4, 0.0)], _view())
    t_slow = _sched([slow]).select(REQ, 0, [CandidateState(2, 4e11, 0, 4, 0.0)], _view())
    assert t_fast.est_transfer_time < t_slow.est_transfer_time


def test_store_lru_eviction():
    store = StagingStore(100, capacity_bytes=3 * store_bpb() if False else 3 * (16 * 320 * 1024 / 4))
    store.insert([1, 2, 3, 4])
    assert store.hit_blocks([1]) == 0  # 1 evicted (LRU)
    assert store.hit_blocks([2, 3, 4]) == 3


def store_bpb():
    return 16 * 320 * 1024 / 4
