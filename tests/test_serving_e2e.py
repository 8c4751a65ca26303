"""End-to-end disaggregated serving: token exactness + transfer kernels +
checkpoint/restart of training."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_spec
from repro.core.cost import B_TOK
from repro.models import decode_step, forward_logits, init_params, prefill
from repro.serving import (
    DisaggregatedCluster,
    ServeRequest,
    merge_chunk_buffers,
    pack_transfer,
    pack_transfer_chunk,
    unpack_transfer,
)
from repro.train import (
    make_optimizer,
    make_train_step,
    restore_latest,
    save_checkpoint,
    synth_batch,
)


@pytest.fixture(scope="module")
def smoke_cfg():
    return dataclasses.replace(get_spec("qwen3-14b").smoke, compute_dtype=jnp.float32)


class TestTransferPath:
    def test_pack_unpack_cache_roundtrip(self, smoke_cfg):
        cfg = smoke_cfg
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab_size)
        _, cache = prefill(cfg, params, toks, cache_len=64)
        buffers, nbytes = pack_transfer(cache, hit_pages=0)
        assert nbytes > 0
        rebuilt = unpack_transfer(buffers, cache)
        rebuilt["pos"] = cache["pos"]
        # decode from the rebuilt cache must equal decode from the original
        lg1, _ = decode_step(cfg, params, toks[:, -1:], dict(cache))
        lg2, _ = decode_step(cfg, params, toks[:, -1:], rebuilt)
        np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2), atol=1e-6)

    def test_prefix_hit_reduces_bytes(self, smoke_cfg):
        cfg = smoke_cfg
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab_size)
        _, cache = prefill(cfg, params, toks, cache_len=64)
        _, full = pack_transfer(cache, hit_pages=0)
        _, hit2 = pack_transfer(cache, hit_pages=2)
        assert hit2 < full  # Eq. (2) materialised

    def test_chunked_pack_conserves_bytes_and_roundtrips(self, smoke_cfg):
        """The executable twin of kv_streaming: packing the cache chunk by
        chunk (fixed state riding with the final chunk) moves exactly the
        bytes of the one-shot pack, and the merged chunks rebuild a cache
        that decodes identically."""
        cfg = smoke_cfg
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0, cfg.vocab_size)
        _, cache = prefill(cfg, params, toks, cache_len=64)
        full_buffers, full_bytes = pack_transfer(cache, hit_pages=1)
        chunks, total = [], 0
        for start, end, final in ((0, 2, False), (2, 3, False), (3, None, True)):
            b, n = pack_transfer_chunk(cache, hit_pages=1, start_page=start,
                                       end_page=end, final=final)
            chunks.append(b)
            total += n
        assert total == full_bytes  # byte conservation on the real path
        merged = merge_chunk_buffers(chunks)
        for name, (buf, table) in full_buffers.items():
            mbuf, mtable = merged[name]
            # Same page set (chunk tables are page-major, the one-shot pack
            # period-major — unpack scatters by table, so order is free).
            assert sorted(table) == sorted(mtable)
            assert np.asarray(buf).shape == np.asarray(mbuf).shape
        rebuilt = unpack_transfer(merged, cache)
        rebuilt["pos"] = cache["pos"]
        want = unpack_transfer(full_buffers, cache)
        want["pos"] = cache["pos"]
        lg1, _ = decode_step(cfg, params, toks[:, -1:], want)
        lg2, _ = decode_step(cfg, params, toks[:, -1:], rebuilt)
        np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2), atol=1e-6)


def _assert_monolithic(cfg, reqs, res):
    """Served tokens equal greedy decoding by a monolithic forward."""
    params = init_params(cfg, jax.random.PRNGKey(0))
    for r, req in zip(res, reqs):
        toks = list(req.prompt)
        for _ in range(req.max_new):
            lg, _ = forward_logits(cfg, params, jnp.asarray(toks, jnp.int32)[None])
            toks.append(int(jnp.argmax(lg[0, -1])))
        assert r.tokens[:req.max_new] == toks[len(req.prompt):], r.request_id


class TestEndToEndServing:
    def test_token_exact_vs_monolithic(self, smoke_cfg):
        cfg = smoke_cfg
        cluster = DisaggregatedCluster(cfg, scheduler="netkv-full", cache_len=64)
        rng = np.random.default_rng(0)
        reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, size=20),
                             max_new=6, arrival=i * 0.01) for i in range(4)]
        _assert_monolithic(cfg, reqs, cluster.serve(reqs))

    def test_prefix_hit_tokens_exact(self, smoke_cfg):
        """The pages a prefix hit leaves out of the transfer come from the
        decode engine's own prefix cache: tokens stay exact after a hit."""
        cfg = smoke_cfg
        cluster = DisaggregatedCluster(cfg, scheduler="rr", cache_len=64,
                                       n_decode=1)
        rng = np.random.default_rng(3)
        prefix = rng.integers(0, cfg.vocab_size, size=2 * B_TOK)
        reqs = [ServeRequest(i, np.concatenate(
                    [prefix, rng.integers(0, cfg.vocab_size, size=8)]),
                    max_new=4, arrival=i * 0.5) for i in range(2)]
        res = cluster.serve(reqs)
        assert [r.hit_pages for r in res] == [0, 2]
        assert res[1].transfer_bytes < res[0].transfer_bytes
        _assert_monolithic(cfg, reqs, res)

    def test_prefix_sharing_cuts_transfer(self, smoke_cfg):
        cfg = smoke_cfg
        cluster = DisaggregatedCluster(cfg, scheduler="netkv-full", cache_len=64)
        rng = np.random.default_rng(1)
        shared = rng.integers(0, cfg.vocab_size, size=48)
        reqs = [ServeRequest(i, shared.copy(), max_new=2, arrival=i * 0.5)
                for i in range(3)]
        res = cluster.serve(reqs)
        by_inst = {}
        for r in res:
            by_inst.setdefault(r.decode_instance, []).append(r)
        for rs in by_inst.values():
            if len(rs) > 1:
                assert rs[1].transfer_bytes < rs[0].transfer_bytes
                return
        pytest.skip("scheduler spread all requests (no repeat instance)")

    def test_hybrid_decision_prices_what_pack_ships(self):
        """A served hybrid (Jamba: Mamba and attention layers) prices its
        fixed state whole, Eq. (2'): each decision's s_eff is the bytes
        ``pack_transfer`` ships at the decision's hit pages."""
        cfg = dataclasses.replace(get_spec("jamba-v0.1-52b").smoke,
                                  compute_dtype=jnp.float32)
        cluster = DisaggregatedCluster(cfg, scheduler="rr", cache_len=128,
                                       n_decode=1)
        decisions, select = [], cluster.sched.select
        cluster.sched.select = lambda *a: decisions.append(select(*a)) \
            or decisions[-1]
        rng = np.random.default_rng(4)
        prefix = rng.integers(0, cfg.vocab_size, size=2 * B_TOK)
        reqs = [ServeRequest(i, np.concatenate(
                    [prefix, rng.integers(0, cfg.vocab_size, size=2 * B_TOK)]),
                    max_new=2, arrival=i * 0.5) for i in range(2)]
        res = cluster.serve(reqs)
        assert [r.hit_pages for r in res] == [0, 2]
        assert [d.s_eff for d in decisions] == [r.transfer_bytes for r in res]

    def test_scheduler_ladder_runs_e2e(self, smoke_cfg):
        cfg = smoke_cfg
        rng = np.random.default_rng(2)
        for sched in ["rr", "cla", "netkv-static", "netkv-full"]:
            cluster = DisaggregatedCluster(cfg, scheduler=sched, cache_len=64)
            reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, size=16),
                                 max_new=3) for i in range(3)]
            res = cluster.serve(reqs)
            assert all(len(r.tokens) >= 3 for r in res), sched

    def test_serve_launcher_smoke(self, capsys, monkeypatch):
        from repro.core import jaxutil
        from repro.launch.serve import main

        # The launcher turns on the persistent compile cache; keep this
        # test process out of it.
        monkeypatch.setattr(jaxutil, "use_compile_cache", lambda: "")
        assert main(["--real", "--smoke", "--arch", "qwen3-14b",
                     "--requests", "2"]) == 0
        lines = capsys.readouterr().out.splitlines()
        assert [ln.split(":")[0] for ln in lines] == ["req0", "req1"]


class TestCheckpointRestart:
    def test_restart_is_bitwise_reproducible(self, tmp_path, smoke_cfg):
        """Preemption drill: train 6 steps; kill; resume from step 3; the
        final params must equal an uninterrupted run (seeded data pipeline)."""
        cfg = smoke_cfg
        opt = make_optimizer("adamw", lr=1e-3)
        params = init_params(cfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        step_fn = jax.jit(make_train_step(cfg, opt, microbatches=1, batch_shards=1))

        def run(params, state, start, end, ckpt_at=None):
            for i in range(start, end):
                batch = synth_batch(cfg, global_batch=4, seq_len=32, seed=11, step=i)
                params, state, _ = step_fn(params, state, batch)
                if ckpt_at is not None and i == ckpt_at:
                    save_checkpoint(str(tmp_path), i + 1, {"p": params, "o": state})
            return params, state

        # uninterrupted
        p_full, _ = run(params, state, 0, 6)
        # interrupted at step 3 + restart
        p_half, s_half = run(params, state, 0, 3, ckpt_at=2)
        restored = restore_latest(str(tmp_path), {"p": params, "o": state})
        assert restored is not None
        step0, tree = restored
        assert step0 == 3
        p_res, _ = run(tree["p"], tree["o"], step0, 6)
        for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_atomic_no_partial_checkpoints(self, tmp_path, smoke_cfg):
        from repro.train.checkpoint import list_checkpoints

        params = init_params(smoke_cfg, jax.random.PRNGKey(0))
        save_checkpoint(str(tmp_path), 1, {"p": params})
        save_checkpoint(str(tmp_path), 2, {"p": params})
        # a stale tmp dir must never be listed
        os.makedirs(os.path.join(str(tmp_path), ".tmp_dead"), exist_ok=True)
        assert list_checkpoints(str(tmp_path)) == [1, 2]

    def test_retention(self, tmp_path, smoke_cfg):
        from repro.train.checkpoint import list_checkpoints

        params = init_params(smoke_cfg, jax.random.PRNGKey(0))
        for i in range(1, 6):
            save_checkpoint(str(tmp_path), i, {"p": params})
        assert list_checkpoints(str(tmp_path)) == [3, 4, 5]
