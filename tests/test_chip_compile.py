"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (the rest of the suite) proves the kernel bodies compute the
right thing; it does not prove that Mosaic, the TPU kernel compiler,
accepts their block shapes and dtypes.  These tests compile each kernel at
real widths for one chip of a ``v5e:2x2`` topology that is described, not
attached, and assert that the compiled program holds a ``tpu_custom_call``.
Each kernel compiles with ``jax_enable_x64`` off and on: the ScenarioPlane
sweep turns x64 on for the whole process, and Mosaic has no f64 and takes
int32 reduction indices only.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_spec
from repro.core.cost import B_TOK
from repro.kernels.kv_pack import kv_pack, kv_unpack
from repro.kernels.netkv_score import _cohort_program, _prepare
from repro.kernels.waterfill import _pallas_share_argmin, _pallas_shares


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _score(r, d):
    """The scorer's cached program on the operand shapes its host
    preparation gives for an R x D cohort."""
    def build(one_chip):
        f32, i32 = np.float32, np.int32
        args = _prepare(
            np.zeros(d, f32), np.zeros(d, f32), np.zeros(d, f32),
            np.zeros((r, d), f32), np.zeros((r, d), i32), np.zeros(d, f32),
            np.zeros(d, f32), [0.0] * 4, [0.0] * 4, [0.0] * 4,
            np.zeros((r, 4), f32), s_r=[0.0] * r, input_len=[0.0] * r,
            iter_a=0.0124, iter_b=1.6e-5, m_min=2e9, beta_max=64)
        rows, lanes, *bufs = args
        return (_cohort_program(rows, lanes, False),
                [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in bufs])
    return build


def _smollm_pages():
    """(pool pages, selected pages, page shape) of one smollm-135m request:
    a 1024-token prompt in a 2048-token cache, paged at B_TOK tokens."""
    m = get_spec("smollm-135m").model
    per_request = m.n_periods
    return (per_request * 2048 // B_TOK, per_request * 1024 // B_TOK,
            (B_TOK, m.n_kv_heads, m.d_head))


def _pack(one_chip):
    n_pages, n_sel, page = _smollm_pages()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (jax.jit(functools.partial(kv_pack, interpret=False)),
            [s((n_pages, *page), jnp.bfloat16), s((n_sel,), jnp.int32)])


def _unpack(one_chip):
    n_pages, n_sel, page = _smollm_pages()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (jax.jit(functools.partial(kv_unpack, interpret=False)),
            [s((n_pages, *page), jnp.bfloat16), s((n_sel, *page), jnp.bfloat16),
             s((n_sel,), jnp.int32)])


def _waterfill(kernel, batch=None):
    def build(one_chip):
        n = 1000   # links; padded to 1024 lanes inside the kernel
        fn = functools.partial(kernel, interpret=False)
        shape = (n,) if batch is None else (batch, n)
        if batch is not None:     # the ScenarioPlane sweep's vmapped form
            fn = jax.vmap(fn)
        s = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
        return jax.jit(fn), [s, s]
    return build


KERNELS = {
    "netkv_score_cohort-R1-D1008": _score(1, 1008),
    "netkv_score_cohort-R1-D4096": _score(1, 4096),
    "netkv_score_cohort-R64-D1008": _score(64, 1008),
    "netkv_score_cohort-R64-D4096": _score(64, 4096),
    "kv_pack-smollm-135m": _pack,
    "kv_unpack-smollm-135m": _unpack,
    "pallas_shares": _waterfill(_pallas_shares),
    "pallas_shares-vmap4": _waterfill(_pallas_shares, batch=4),
    "pallas_share_argmin": _waterfill(_pallas_share_argmin),
}


@pytest.mark.parametrize("x64", [False, True], ids=["x64off", "x64on"])
@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(name, x64, one_chip, no_compile_cache):
    with jax.enable_x64(x64):
        fn, args = KERNELS[name](one_chip)
        text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("r", [1, 64])
def test_scorer_keeps_its_trace_name(r, one_chip, no_compile_cache):
    """A device trace names a Pallas kernel by its HLO instruction: the
    scorer is the custom call named ``tpu_custom_call`` that returns
    (f32[rows,1,lanes], s32[rows,1,1])."""
    fn, args = _score(r, 1008)(one_chip)
    text = fn.lower(*args).compile().as_text()
    assert re.search(r"tpu_custom_call\S* = \(f32\[\d+,1,\d+\]\S* s32\[\d+,1,1\]",
                     text)


def test_interpret_mode_follows_backend():
    from repro.kernels.ops import interpret_mode

    assert interpret_mode() == (jax.default_backend() != "tpu")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_placement(from_env, tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the cache
    goes to ``<repo>/.jax_cache``."""
    from repro.core.jaxutil import use_compile_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = use_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev[keys[0]]
        else:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert path == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
