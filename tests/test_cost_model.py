"""Unit + property tests for the cost model (Eqs. 1-7) and Propositions 1-2."""

import math

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (
    H100_TP4_ITER,
    LLAMA3_70B_KV,
    ModelKVSpec,
    Prop1Instance,
    effective_bandwidth,
    effective_transfer_bytes,
    first_decode_time,
    post_prefill_latency,
    prop1_condition,
    prop1_latencies,
    prop2_epsilon_bound,
    prop2_ordering_preserved,
    queue_time,
    transfer_time,
)


class TestEq1KVSize:
    def test_llama3_70b_paper_number(self):
        # §III-B: 320 KB/token aggregate for Llama-3-70B.
        assert LLAMA3_70B_KV.kv_bytes_per_token == 320 * 1024

    def test_worked_example_32k(self):
        # §III-D: 32K-token request => ~10 GB aggregate.
        s_r = LLAMA3_70B_KV.kv_bytes(32768)
        assert abs(s_r - 10.74e9) / 10.74e9 < 0.01

    def test_hybrid_fixed_state(self):
        spec = ModelKVSpec("hy", n_layers=32, n_kv_heads=8, d_head=128,
                           n_attn_layers=4, fixed_state_bytes=16_000_000)
        # fixed state present even at zero-length input
        assert spec.kv_bytes(0) == 16_000_000
        # per-token term counts only the attention layers
        assert spec.kv_bytes_per_token == 2 * 4 * 8 * 128 * 2


class TestWorkedExample:
    """§III-D full worked example, both congestion regimes."""

    def test_moderate_congestion(self):
        s_r = LLAMA3_70B_KV.kv_bytes(32768)
        t1 = transfer_time(effective_transfer_bytes(s_r, 16384, 32768),
                           50e9 / 8, 0.2, 1, 8e-6)
        t2 = transfer_time(effective_transfer_bytes(s_r, 0.9 * 32768, 32768),
                           25e9 / 8, 0.2, 0, 15e-6)
        assert abs(t1 - 2.0) < 0.2 and abs(t2 - 0.4) < 0.05
        assert t2 < t1  # warm cross-pod candidate wins

    def test_congestion_flips_gap(self):
        s_r = LLAMA3_70B_KV.kv_bytes(32768)
        t2_low = transfer_time(effective_transfer_bytes(s_r, 0.9 * 32768, 32768),
                               25e9 / 8, 0.2, 0, 15e-6)
        t2_high = transfer_time(effective_transfer_bytes(s_r, 0.9 * 32768, 32768),
                                25e9 / 8, 0.5, 0, 15e-6)
        assert t2_high > t2_low * 1.5  # the gap collapses from 5x to ~3x


@given(
    s_r=st.floats(1e6, 1e11),
    hit=st.floats(0, 1e6),
    l=st.integers(1, 10 ** 6),
)
def test_eq2_bounds(s_r, hit, l):
    s_eff = effective_transfer_bytes(s_r, hit, l)
    assert 0.0 <= s_eff <= s_r
    # full hit -> zero transfer
    assert effective_transfer_bytes(s_r, l, l) == 0.0
    # zero hit -> full transfer
    assert effective_transfer_bytes(s_r, 0, l) == s_r


# Eq. (2'): every copy of s_eff in the program, as f(s_r, hit, l, state).
def _scalar_s_eff(s_r, hit, l, state):
    return effective_transfer_bytes(s_r, hit, l, state)


def _vector_s_eff(s_r, hit, l, state):
    from repro.core.schedulers import v_s_eff

    return float(v_s_eff(s_r, np.array([float(hit)]), l, state)[0])


S_EFF_COPIES = [_scalar_s_eff, _vector_s_eff]


def _eq2_draws(seed, n=64):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        l = int(rng.integers(1, 1 << 17))
        yield (float(l) * float(rng.choice([6144, 196_608, 327_680])),
               float(rng.uniform(-0.1, 1.3)) * l, l)


@pytest.mark.parametrize("s_eff", S_EFF_COPIES)
def test_eq2_prime_never_discounts_the_state(s_eff):
    state = 49_930_240.0
    for kv, hit, l in _eq2_draws(0):
        got = s_eff(kv + state, hit, l, state)
        frac = min(max(hit, 0.0), float(l)) / float(l)
        assert got == pytest.approx(kv * (1.0 - frac) + state, rel=1e-15)
        assert state <= got <= kv + state
        # A full hit leaves exactly the state; no hit leaves all of s_r.
        assert s_eff(kv + state, l, l, state) == state
        assert s_eff(kv + state, 0.0, l, state) == kv + state


@pytest.mark.parametrize("s_eff", S_EFF_COPIES)
def test_eq2_prime_zero_state_is_eq2_bit_for_bit(s_eff):
    for kv, hit, l in _eq2_draws(1):
        frac = min(max(hit, 0.0), float(l)) / float(l)
        assert s_eff(kv, hit, l, 0.0) == kv * (1.0 - frac)
        assert s_eff(kv, hit, l, 0.0) == s_eff(kv, hit, l, 0)


@given(
    bw=st.floats(1e6, 1e12),
    c=st.floats(0, 0.99),
    n=st.integers(0, 64),
)
def test_eq4_monotonicity(bw, c, n):
    b = effective_bandwidth(bw, c, n)
    assert 0 < b <= bw
    # more congestion or contention never increases bandwidth
    assert effective_bandwidth(bw, min(c + 0.1, 0.99), n) <= b + 1e-9
    assert effective_bandwidth(bw, c, n + 1) < b + 1e-9


@given(
    q=st.integers(0, 200), beta=st.integers(0, 64),
)
def test_eq6_queue(q, beta):
    t = queue_time(q, beta, 64, H100_TP4_ITER)
    assert t >= 0
    # no wait while slots are free
    if q <= 64 - beta:
        assert t == 0


@given(
    s_r=st.floats(1e8, 1e11),
    rho1=st.floats(0, 0.99),
    rho2=st.floats(0, 0.99),
    k=st.floats(1, 16),
    c1=st.floats(0, 0.9),
    c3=st.floats(0, 0.9),
    q1=st.floats(0, 5),
    q2=st.floats(0, 5),
)
@settings(max_examples=300)
def test_prop1_condition_matches_latencies(s_r, rho1, rho2, k, c1, c3, q1, q2):
    """Eq. (8) must EXACTLY characterise when d1 beats d2."""
    inst = Prop1Instance(s_r=s_r, B1=12.5e9, k=k, c1=c1, c3=c3,
                         rho1=rho1, rho2=max(rho1, rho2),
                         t_queue_d1=q1, t_queue_d2=q2)
    t1, t2 = prop1_latencies(inst)
    if abs(t1 - t2) / max(t1, t2, 1e-12) < 1e-9:
        return  # boundary: numerically ambiguous
    assert prop1_condition(inst) == (t1 < t2)


def test_prop1_paper_example():
    inst = Prop1Instance(s_r=1e9, B1=4e9, k=4, c1=0, c3=0, rho1=0.0, rho2=0.5)
    assert prop1_condition(inst)  # 1 < 2: network-oblivious pick is 2x worse
    t1, t2 = prop1_latencies(inst)
    assert abs(t2 / t1 - 2.0) < 1e-9


def test_prop1_gap_widens_with_context():
    """The suboptimality factor grows with s_r (context length)."""
    gaps = []
    for s_r in [1e8, 1e9, 1e10]:
        inst = Prop1Instance(s_r=s_r, B1=4e9, k=4, c1=0, c3=0, rho1=0.0,
                             rho2=0.5, t_queue_d1=0.05, t_queue_d2=0.05)
        t1, t2 = prop1_latencies(inst)
        gaps.append(t2 - t1)
    assert gaps[0] < gaps[1] < gaps[2]


@given(
    b_hi=st.floats(1e8, 1e12), ratio=st.floats(0.01, 1.0),
    c_hi=st.floats(0, 0.95), c_lo=st.floats(0, 0.95),
    eps=st.floats(0, 0.5),
)
@settings(max_examples=300)
def test_prop2_bound_is_sufficient(b_hi, ratio, c_hi, c_lo, eps):
    """Any eps strictly below the Eq. (9) bound preserves the ordering."""
    b_lo = b_hi * ratio
    if b_hi * (1 - c_hi) <= b_lo * (1 - c_lo):
        return  # premise requires true ordering
    bound = prop2_epsilon_bound(b_hi, c_hi, b_lo, c_lo)
    if eps < bound:
        assert prop2_ordering_preserved(b_hi, c_hi, b_lo, c_lo, eps)


def test_prop2_paper_numbers():
    # 4:1 oversub, c*=0.3 both: bound = 0.42
    assert abs(prop2_epsilon_bound(4.0, 0.3, 1.0, 0.3) - 0.42) < 1e-9
    # near saturation the tolerance vanishes
    assert prop2_epsilon_bound(4.0, 0.999, 1.0, 0.0) < 0


def test_eq5_additive():
    total = post_prefill_latency(
        s_r=1e9, hit_tokens=0, input_len=1000, tier_bw=1e9, congestion=0.0,
        n_inflight=0, tier_latency=1e-5, q_d=0, beta_d=3, beta_max=64,
        iter_model=H100_TP4_ITER,
    )
    expect = 1e9 / 1e9 + 1e-5 + first_decode_time(3, H100_TP4_ITER)
    assert abs(total - expect) < 1e-12
