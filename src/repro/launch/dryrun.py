import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import (device count and platform
# lock at first backend init).  The dry-run is a host-device analysis: it
# and the children it starts (which inherit this environment) never take an
# accelerator.  Everything below may import jax.

import argparse
import json
import re
import subprocess
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL, SHAPES, get_spec
from repro.models import abstract_params, param_partition_specs
from repro.models.sharding import sanitize_specs
from repro.models.model import decode_step, forward_train, prefill
from repro.models.sharding import (
    LONG_RULES,
    SERVE_RULES,
    SERVE_RULES_MULTIPOD,
    TRAIN_RULES,
    TRAIN_RULES_MULTIPOD,
    axis_rules,
)
from repro.train import make_optimizer, make_train_step, opt_state_specs
from repro.launch.hlo_analysis import collective_bytes_loop_aware
from repro.launch.mesh import batch_axes, batch_shards, make_production_mesh

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

# ---------------------------------------------------------------------------
# Collective-byte accounting from post-optimisation HLO text
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, Any]:
    """Sum operand bytes of every collective op in the compiled HLO."""
    per_kind: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*\S+\s+([a-z\-]+)(?:-start|-done)?\(", stripped)
        if not m:
            continue
        op = m.group(1)
        base = op.replace("-start", "").replace("-done", "")
        if base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        shapes = _SHAPE_RE.findall(stripped)
        if not shapes:
            continue
        # first shape = output (possibly tuple elements first); operands follow
        # the opening paren — take shapes appearing after '('.
        paren = stripped.index("(")
        operand_shapes = _SHAPE_RE.findall(stripped[paren:])
        use = operand_shapes if operand_shapes else shapes[-1:]
        total = sum(_shape_bytes(dt, dims) for dt, dims in use)
        per_kind[base] += total
        counts[base] += 1
    return {
        "bytes_by_kind": per_kind,
        "counts": counts,
        "total_bytes": sum(per_kind.values()),
    }


def _memory_dict(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception as e:  # pragma: no cover
        out["error"] = repr(e)
    return out


def _cost_dict(compiled) -> dict:
    out = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for k, v in dict(ca).items():
            if isinstance(v, (int, float)):
                out[k] = float(v)
    except Exception as e:  # pragma: no cover
        out["error"] = repr(e)
    return out


# ---------------------------------------------------------------------------
# Cell construction: (arch, shape, mesh) -> jitted fn + abstract args
# ---------------------------------------------------------------------------

def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _cache_specs(spec, shape_name: str, multi_pod: bool):
    return _cache_specs_for(spec, shape_name, multi_pod,
                            spec.input_specs(shape_name)["cache"])


def _cache_specs_for(spec, shape_name: str, multi_pod: bool, cache_tree):
    """PartitionSpec per decode-cache leaf, by leaf name."""
    ba = batch_axes(multi_pod)
    bt = ba if len(ba) > 1 else ba[0]
    long = shape_name == "long_500k"
    seq_mode = spec.decode_cache_shard == "seq"

    def leaf_spec(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        nd = len(leaf.shape)
        if name == "pos":
            return P()
        if name.startswith(("k", "v", "ck", "cv")) and nd == 5:
            if long:
                return P(None, None, "data", "model", None)
            if seq_mode:
                return P(None, bt, "model", None, None)
            return P(None, bt, None, "model", None)
        if name.startswith("ssm"):
            return P(None, None if long else bt, "model", None)
        if name.startswith("conv"):
            return P(None, None if long else bt, None, "model")
        if name.startswith("wkv"):
            return P(None, None if long else bt, "model", None, None)
        if name.startswith(("sa", "sc")):
            return P(None, None if long else bt, "model")
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_tree)


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    spec = get_spec(arch)
    cfg = spec.model
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    kind = SHAPES[shape_name]["kind"]
    ba = batch_axes(multi_pod)
    bt = ba if len(ba) > 1 else ba[0]

    if kind == "train":
        rules = dict(TRAIN_RULES_MULTIPOD if multi_pod else TRAIN_RULES)
        aparams = abstract_params(cfg, dtype=jnp.dtype(spec.train_param_dtype))
        pspecs = sanitize_specs(aparams, param_partition_specs(aparams, "train", multi_pod), sizes)
        opt = make_optimizer(spec.optimizer)
        astate = jax.eval_shape(opt.init, aparams)
        sspecs = sanitize_specs(astate, opt_state_specs(opt, aparams, astate, pspecs), sizes)
        abatch = spec.input_specs(shape_name)["batch"]
        bspecs = jax.tree.map(lambda l: P(bt, *([None] * (len(l.shape) - 1))), abatch)
        step = make_train_step(cfg, opt, microbatches=spec.train_microbatches,
                               batch_shards=batch_shards(multi_pod),
                               accum_dtype=jnp.dtype(spec.grad_accum_dtype))
        fn = jax.jit(
            step,
            in_shardings=(_named(mesh, pspecs), _named(mesh, sspecs), _named(mesh, bspecs)),
            out_shardings=(_named(mesh, pspecs), _named(mesh, sspecs), None),
            donate_argnums=(0, 1),
        )
        args = (aparams, astate, abatch)
        return mesh, rules, fn, args

    # serving paths
    if shape_name == "long_500k":
        rules = dict(LONG_RULES)
    else:
        rules = dict(SERVE_RULES_MULTIPOD if multi_pod else SERVE_RULES)
    if spec.serve_fsdp:
        rules["fsdp"] = ("pod", "data") if multi_pod else ("data",)
        rules["experts"] = rules["fsdp"]
    mode = "train" if spec.serve_fsdp else "serve"
    aparams = abstract_params(cfg, dtype=jnp.bfloat16)
    pspecs = sanitize_specs(aparams, param_partition_specs(aparams, mode, multi_pod), sizes)
    ins = spec.input_specs(shape_name)

    if kind == "prefill":
        tok_spec = P(bt, None)

        def prefill_fn(params, tokens, frames=None, prefix_embeds=None):
            memory = None
            if cfg.is_enc_dec:
                from repro.models.model import encode

                memory = encode(cfg, params, frames)
            return prefill(cfg, params, tokens, prefix_embeds=prefix_embeds,
                           memory=memory, cache_len=SHAPES[shape_name]["seq_len"])

        # Explicit out shardings: without them the compiler may replicate the
        # produced KV cache (157 GB/device on arctic prefill_32k baseline).
        from repro.models.model import make_decode_cache

        acache = make_decode_cache(cfg, SHAPES[shape_name]["global_batch"],
                                   SHAPES[shape_name]["seq_len"],
                                   enc_len=SHAPES[shape_name]["seq_len"] if cfg.is_enc_dec else 0)
        ccspec = _named(mesh, sanitize_specs(
            acache, _cache_specs_for(spec, shape_name, multi_pod, acache), sizes))
        if cfg.is_enc_dec:
            ccspec = dict(ccspec)
            ccspec["cross_memory"] = NamedSharding(mesh, P(bt, None, None))
        out_sh = (None, ccspec)
        in_sh = [_named(mesh, pspecs), NamedSharding(mesh, tok_spec)]
        args = [aparams, ins["tokens"]]
        if "frames" in ins:
            in_sh.append(NamedSharding(mesh, P(bt, None, None)))
            args.append(ins["frames"])
            fn = jax.jit(lambda p, t, f: prefill_fn(p, t, frames=f),
                         in_shardings=tuple(in_sh), out_shardings=out_sh)
        elif "prefix_embeds" in ins:
            in_sh.append(NamedSharding(mesh, P(bt, None, None)))
            args.append(ins["prefix_embeds"])
            fn = jax.jit(lambda p, t, e: prefill_fn(p, t, prefix_embeds=e),
                         in_shardings=tuple(in_sh), out_shardings=out_sh)
        else:
            fn = jax.jit(prefill_fn, in_shardings=tuple(in_sh), out_shardings=out_sh)
        return mesh, rules, fn, tuple(args)

    # decode: READ-ONLY cache (paged semantics) — an in-place
    # dynamic-update-slice on a seq-sharded cache forces GSPMD to re-gather
    # the whole cache every step (85.9 GB/step on qwen3 decode_32k,
    # EXPERIMENTS.md §Perf); the new token's KV returns as a fragment.
    cspecs = sanitize_specs(ins["cache"], _cache_specs(spec, shape_name, multi_pod), sizes)
    tok_spec = P(None, None) if shape_name == "long_500k" else P(bt, None)
    fn = jax.jit(
        lambda p, t, c: decode_step(cfg, p, t, c, update_cache=False),
        in_shardings=(
            _named(mesh, pspecs),
            NamedSharding(mesh, tok_spec),
            _named(mesh, cspecs),
        ),
    )
    args = (aparams, ins["token"], ins["cache"])
    return mesh, rules, fn, args


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    t0 = time.time()
    spec = get_spec(arch)
    mesh_name = "multipod" if multi_pod else "pod"
    rec: dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_devices": 512 if multi_pod else 256,
    }
    if shape_name not in spec.runnable_shapes():
        rec["status"] = "skipped"
        rec["reason"] = spec.skip_notes.get(shape_name, "not applicable")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json"), "w") as f:
            json.dump(rec, f, indent=1)
        return rec
    try:
        mesh, rules, fn, args = build_cell(arch, shape_name, multi_pod)
        with mesh, axis_rules(rules, mesh=mesh):
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            hlo = compiled.as_text()
        rec["status"] = "ok"
        rec["lower_s"] = round(t_lower - t0, 2)
        rec["compile_s"] = round(t_compile - t_lower, 2)
        rec["memory"] = _memory_dict(compiled)
        rec["cost"] = _cost_dict(compiled)
        rec["collectives"] = collective_bytes(hlo)            # raw (loop-once)
        rec["collectives_loop_aware"] = collective_bytes_loop_aware(hlo)
        rec["hlo_bytes"] = len(hlo)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    rec["wall_s"] = round(time.time() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description="Multi-pod dry-run: lower+compile every cell")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true", help="every (arch x shape) via subprocesses")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ART_DIR))
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    if args.all:
        archs = ALL if args.arch is None else [args.arch]
        shapes = list(SHAPES) if args.shape is None else [args.shape]
        failures = 0
        for arch in archs:
            for shape in shapes:
                for mesh in meshes:
                    path = os.path.join(args.out, f"{arch}__{shape}__{mesh}.json")
                    if args.skip_existing and os.path.exists(path):
                        with open(path) as f:
                            prev = json.load(f)
                        if prev.get("status") in ("ok", "skipped"):
                            print(f"[skip] {arch} {shape} {mesh}: cached {prev['status']}")
                            continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mesh,
                           "--out", args.out]
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       env={**os.environ})
                    tail = (r.stdout + r.stderr).strip().splitlines()
                    print(f"[{arch} {shape} {mesh}] rc={r.returncode} "
                          + (tail[-1] if tail else ""))
                    if r.returncode != 0:
                        failures += 1
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape required without --all"
    ok = True
    for mesh in meshes:
        rec = run_cell(args.arch, args.shape, mesh == "multipod", args.out)
        status = rec["status"]
        if status == "ok":
            mem = rec["memory"]
            per_dev = (mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)) / 1e9
            flops = rec["cost"].get("flops", 0)
            print(f"{args.arch} {args.shape} {mesh}: OK compile={rec['compile_s']}s "
                  f"mem/dev={per_dev:.2f}GB flops={flops:.3g} "
                  f"coll={rec['collectives']['total_bytes']/1e9:.3f}GB")
        elif status == "skipped":
            print(f"{args.arch} {args.shape} {mesh}: SKIPPED ({rec['reason']})")
        else:
            print(f"{args.arch} {args.shape} {mesh}: ERROR {rec['error']}")
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
