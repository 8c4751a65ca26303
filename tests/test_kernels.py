"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Every kernel is swept over shapes and dtypes; hypothesis drives randomized
block tables and pool states for kv_pack/unpack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

import repro.kernels.ops as ops
import repro.kernels.ref as ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


class TestFlashDecode:
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("b,h,kv,dh,s,block_s", [
        (1, 4, 4, 64, 512, 128),     # MHA
        (2, 8, 2, 64, 1024, 256),    # GQA 4:1
        (2, 16, 8, 128, 512, 256),   # GQA 2:1, d_head 128
        (1, 8, 1, 128, 2048, 512),   # MQA
    ])
    def test_allclose(self, dtype, tol, b, h, kv, dh, s, block_s):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(keys[0], (b, h, dh), dtype)
        k = _rand(keys[1], (b, s, kv, dh), dtype)
        v = _rand(keys[2], (b, s, kv, dh), dtype)
        pos = s - s // 3
        out = ops.flash_decode(q, k, v, pos, block_s=block_s)
        exp = ref.flash_decode_ref(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32), atol=tol, rtol=tol)

    def test_pos_boundaries(self):
        """pos exactly on block boundaries and pos=1."""
        key = jax.random.PRNGKey(1)
        q = _rand(key, (1, 4, 64), jnp.float32)
        k = _rand(key, (1, 512, 2, 64), jnp.float32)
        v = _rand(key, (1, 512, 2, 64), jnp.float32)
        for pos in [1, 128, 256, 512]:
            out = ops.flash_decode(q, k, v, pos, block_s=128)
            exp = ref.flash_decode_ref(q, k, v, pos)
            np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5)

    def test_matches_model_decode_attention(self):
        """Kernel == the model's XLA decode path (the serving substitution)."""
        from repro.models.attention import decode_attention

        key = jax.random.PRNGKey(2)
        q = _rand(key, (2, 8, 64), jnp.float32)
        k = _rand(key, (2, 256, 4, 64), jnp.float32)
        v = _rand(key, (2, 256, 4, 64), jnp.float32)
        out = ops.flash_decode(q, k, v, 200, block_s=128)
        exp = decode_attention(q[:, None], k, v, jnp.int32(200))[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5)


class TestKVPack:
    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_roundtrip(self, data):
        n_pages = data.draw(st.integers(4, 32))
        n_sel = data.draw(st.integers(1, n_pages))
        table = data.draw(st.permutations(range(n_pages)))[:n_sel]
        pool = jax.random.normal(jax.random.PRNGKey(0), (n_pages, 16, 2, 64))
        buf = ops.kv_pack(pool, jnp.asarray(table, jnp.int32))
        exp = ref.kv_pack_ref(pool, jnp.asarray(table, jnp.int32))
        np.testing.assert_array_equal(np.asarray(buf), np.asarray(exp))
        dst = jnp.zeros_like(pool)
        got = ops.kv_unpack(dst, buf, jnp.asarray(table, jnp.int32))
        exp2 = ref.kv_unpack_ref(jnp.zeros_like(pool), buf, jnp.asarray(table, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp2))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_dtypes(self, dtype):
        pool = _rand(jax.random.PRNGKey(0), (8, 16, 4, 128), dtype)
        table = jnp.asarray([7, 0, 3], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(ops.kv_pack(pool, table), np.float32),
            np.asarray(ref.kv_pack_ref(pool, table), np.float32))


class TestNetKVScoreKernel:
    @given(seed=st.integers(0, 1000), d=st.integers(1, 300))
    @settings(max_examples=25, deadline=None)
    def test_allclose_and_argmin(self, seed, d):
        rng = np.random.default_rng(seed)
        args = dict(
            free_mem=rng.uniform(1e9, 4e11, d),
            queued=rng.integers(0, 20, d).astype(np.float32),
            batch=rng.integers(0, 64, d).astype(np.float32),
            hit_tokens=rng.uniform(0, 9000, d),
            tier=rng.integers(0, 4, d),
            healthy=(rng.random(d) > 0.15).astype(np.float32),
            iter_scale=rng.uniform(1, 2, d),
            tier_bw=[4.5e11, 1.25e10, 6.25e9, 3.125e9],
            tier_lat=[1e-6, 3e-6, 8e-6, 1.5e-5],
            congestion=rng.uniform(0, 0.8, 4),
            n_inflight=rng.integers(0, 8, 4).astype(np.float32),
        )
        kw = dict(s_r=2.6e9, input_len=8192.0, iter_a=0.0124, iter_b=1.6e-5,
                  m_min=2e9, beta_max=64)
        c_k, b_k = ops.netkv_score(**args, **kw)
        c_r, b_r = ref.netkv_score_ref(**args, **kw)
        finite = np.asarray(c_r) < 1e38
        if finite.any():
            np.testing.assert_allclose(np.asarray(c_k)[finite],
                                       np.asarray(c_r)[finite], rtol=1e-5)
        assert int(b_k) == int(b_r)

    @pytest.mark.parametrize("force_reference", [False, True])
    @pytest.mark.parametrize("state", [0.0, 1.5e9])
    def test_matches_core_cost_model(self, state, force_reference):
        """Kernel and its jnp oracle == the scalar cost model (one
        candidate), Eq. (2') with and without fixed state."""
        from repro.core.cost import post_prefill_latency, H100_TP4_ITER

        kw = dict(s_r=3.2e9, input_len=8192.0, iter_a=H100_TP4_ITER.a,
                  iter_b=H100_TP4_ITER.b, m_min=1e9, beta_max=64,
                  state_bytes=state)
        c, _ = ops.netkv_score(
            free_mem=[4e11], queued=[3.0], batch=[62.0], hit_tokens=[4096.0],
            tier=[2], healthy=[1.0], iter_scale=[1.0],
            tier_bw=[4.5e11, 1.25e10, 6.25e9, 3.125e9],
            tier_lat=[1e-6, 3e-6, 8e-6, 1.5e-5],
            congestion=[0, 0, 0.2, 0.3], n_inflight=[0, 0, 1, 0],
            force_reference=force_reference, **kw)
        expect = post_prefill_latency(
            s_r=3.2e9, hit_tokens=4096, input_len=8192, tier_bw=6.25e9,
            congestion=0.2, n_inflight=1, tier_latency=8e-6, q_d=3, beta_d=62,
            beta_max=64, iter_model=H100_TP4_ITER, state_bytes=state)
        assert abs(float(c[0]) - expect) / expect < 1e-5
        # The state is never discounted: it adds its whole transfer time.
        if state:
            bare = post_prefill_latency(
                s_r=3.2e9, hit_tokens=4096, input_len=8192, tier_bw=6.25e9,
                congestion=0.2, n_inflight=1, tier_latency=8e-6, q_d=3,
                beta_d=62, beta_max=64, iter_model=H100_TP4_ITER)
            assert expect - bare == pytest.approx(
                state / 2 / (6.25e9 * 0.8 / 2), rel=1e-9)


class TestRWKVScan:
    @pytest.mark.parametrize("b,t,h,dh,chunk", [
        (1, 128, 2, 64, 64), (2, 256, 3, 64, 128), (1, 512, 1, 128, 128),
    ])
    def test_allclose(self, b, t, h, dh, chunk):
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        r = _rand(keys[0], (b, t, h, dh), jnp.float32) * 0.3
        k = _rand(keys[1], (b, t, h, dh), jnp.float32) * 0.3
        v = _rand(keys[2], (b, t, h, dh), jnp.float32) * 0.3
        w = jax.nn.sigmoid(_rand(keys[3], (b, t, h, dh), jnp.float32)) * 0.5 + 0.45
        u = _rand(keys[4], (h, dh), jnp.float32) * 0.3
        y1, s1 = ops.rwkv_scan(r, k, v, w, u, chunk=chunk)
        y2, s2 = ref.rwkv_scan_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)

    def test_matches_model_rwkv_core(self):
        """Kernel recurrence == the model's WKV inner loop."""
        import repro.models.rwkv as m

        b, t, d = 1, 64, 128
        cfg_h = d // m.HEAD_DIM
        key = jax.random.PRNGKey(3)
        params = {
            k: v for k, v in zip(
                ["r", "k", "v", "w"],
                [jax.random.normal(kk, (b, t, cfg_h, m.HEAD_DIM)) * 0.3
                 for kk in jax.random.split(key, 4)])
        }
        w = jax.nn.sigmoid(params["w"]) * 0.5 + 0.45
        u = jax.random.normal(jax.random.PRNGKey(9), (cfg_h, m.HEAD_DIM)) * 0.3
        y_k, s_k = ops.rwkv_scan(params["r"], params["k"], params["v"], w, u, chunk=32)
        y_r, s_r = ref.rwkv_scan_ref(params["r"], params["k"], params["v"], w, u)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-4)
