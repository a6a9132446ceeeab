import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
# init, and the production meshes need 512 placeholder host devices.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds ShapeDtypeStruct stand-ins for params/opt/batch/caches (no
     allocation anywhere),
  2. jits the real step (train_step for train cells — fwd+bwd+AdamW;
     forward for prefill; decode_step for decode) with the production
     GSPMD shardings,
  3. ``.lower().compile()`` — failures (sharding mismatch, OOM at compile,
     unsupported collective) are bugs in the system,
  4. records memory_analysis / cost_analysis / per-collective byte counts
     parsed from the partitioned HLO into results/dryrun/<cell>.json —
     the §Roofline inputs.

Usage:
  python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, get_config, input_specs
from ..configs.base import ModelConfig, ShapeSpec
from ..dist import sharding
from . import hlo_analysis
from ..models import model as model_lib
from ..train import optimizer
from ..train.trainer import TrainConfig, make_train_step
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh

_DTYPE_BYTES = {
    "f32": 4, "f16": 2, "bf16": 2, "f64": 8, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s64": 8, "u64": 8, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum result sizes of every collective op in the partitioned HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("%") or ls.startswith("ROOT"):
            m = re.search(r"=\s*(.+?)\s+(all-gather|all-reduce|reduce-scatter|"
                          r"all-to-all|collective-permute)", ls)
            if m:
                out[m.group(2)] += _shape_bytes(m.group(1))
                counts[m.group(2)] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def serve_param_specs(cfg: ModelConfig):
    """bf16 weights for serving cells (the deployment dtype)."""
    shapes = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        shapes)


def build_cell(cfg: ModelConfig, shape_name: str, mesh):
    """Returns (fn, arg_specs, in_shardings) for one cell."""
    spec = SHAPES[shape_name]
    pspec = sharding.param_pspecs(cfg, mesh)
    bspec = sharding.batch_pspecs(cfg, spec, mesh)

    if spec.kind == "train":
        params_sds = jax.eval_shape(
            lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
        opt_sds = jax.eval_shape(lambda: optimizer.init(params_sds))
        batch = input_specs(cfg, shape_name)
        tc = TrainConfig(remat=True)
        raw_step = make_train_step(cfg, tc, use_balancer=False)

        def fn(tree, batch):
            return raw_step(tree, batch, None, None)

        opt_spec = optimizer.AdamWState(
            step=P(), m=sharding.opt_pspecs(pspec, params_sds, mesh),
            v=sharding.opt_pspecs(pspec, params_sds, mesh))
        tree_sds = {"params": params_sds, "opt": opt_sds}
        tree_spec = {"params": pspec, "opt": opt_spec}
        in_shardings = (sharding.shardings_of(tree_spec, mesh),
                        sharding.shardings_of(
                            {k: bspec[k] for k in batch}, mesh))
        return fn, (tree_sds, batch), in_shardings

    if spec.kind == "prefill":
        params_sds = serve_param_specs(cfg)
        batch = input_specs(cfg, shape_name)

        def fn(params, batch):
            logits, _ = model_lib.forward(params, cfg, batch, remat=False)
            return logits

        in_shardings = (sharding.shardings_of(pspec, mesh),
                        sharding.shardings_of(
                            {k: bspec[k] for k in batch}, mesh))
        return fn, (params_sds, batch), in_shardings

    # decode
    params_sds = serve_param_specs(cfg)
    specs = input_specs(cfg, shape_name, include_cache=True)
    cache_sds = specs.pop("cache")
    cache_spec = sharding.cache_pspecs(cfg, spec, mesh)
    tokens_sds = specs["tokens"]
    cl_sds = specs["cache_len"]

    def fn(params, tokens, cache, cache_len):
        return model_lib.decode_step(params, cfg, tokens, cache, cache_len)

    dp = sharding.data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    b_ax = dp if spec.global_batch % dp_size == 0 else None
    in_shardings = (
        sharding.shardings_of(pspec, mesh),
        NamedSharding(mesh, P(b_ax, None)),
        sharding.shardings_of(cache_spec, mesh),
        NamedSharding(mesh, P()),
    )
    return fn, (params_sds, tokens_sds, cache_sds, cl_sds), in_shardings


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: Dict[str, Any] = None) -> Dict[str, Any]:
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args, in_shardings = build_cell(cfg, shape_name, mesh)
        jitted = jax.jit(fn, in_shardings=in_shardings)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    res: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": int(np.prod(list(mesh.shape.values()))),
        "seq_len": spec.seq_len, "global_batch": spec.global_batch,
        "kind": spec.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    ma = compiled.memory_analysis()
    if ma is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            res[k] = int(getattr(ma, k, 0) or 0)
    ca = compiled.cost_analysis()
    if ca:
        # NOTE: xla cost_analysis does not multiply while bodies by trip
        # count; kept for reference only. The roofline uses hlo_analysis.
        res["xla_flops"] = float(ca.get("flops", 0.0))
        res["xla_bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    hlo_text = compiled.as_text()
    hlo = hlo_analysis.analyze_text(hlo_text)
    res["flops"] = hlo["flops"]
    res["bytes_accessed"] = hlo["hbm_bytes"]
    res["collectives"] = {
        "bytes": hlo["collective_bytes"],
        "counts": hlo["collective_counts"],
        "total_bytes": hlo["collective_total_bytes"],
    }
    hlo_dir = os.environ.get("REPRO_HLO_DIR")
    if hlo_dir:
        import gzip
        os.makedirs(hlo_dir, exist_ok=True)
        with gzip.open(os.path.join(
                hlo_dir, f"{arch}__{shape_name}__{mesh_kind}.hlo.gz"),
                "wt") as f:
            f.write(hlo_text)
    return res


def roofline_terms(res: Dict[str, Any]) -> Dict[str, float]:
    """The three §Roofline terms, in seconds, from a cell result.

    cost_analysis flops/bytes are for the whole partitioned program of one
    device (XLA reports the per-module analysis after SPMD partitioning),
    so divide by per-chip peaks directly.
    """
    n_dev = res.get("devices", 256)
    flops = res.get("flops", 0.0)
    byts = res.get("bytes_accessed", 0.0)
    coll = res.get("collectives", {}).get("total_bytes", 0)
    return {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": byts / HBM_BW,
        "collective_s": coll / ICI_BW,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    help="config override key=value (perf variants), e.g. "
                         "--set moe_token_groups=16")
    ap.add_argument("--tag", default="",
                    help="suffix for output files of a perf variant")
    args = ap.parse_args()

    overrides: Dict[str, Any] = {}
    for kv in args.overrides:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    for a in archs:
        cfg = get_config(a)
        shapes = ([args.shape] if args.shape else
                  [s for s in SHAPES if s not in cfg.skip_shapes])
        for s in shapes:
            if s in cfg.skip_shapes:
                print(f"SKIP {a} {s} (noted in DESIGN.md)")
                continue
            meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
            for m in meshes:
                cells.append((a, s, m))

    failures = 0
    for a, s, m in cells:
        tag = f"__{args.tag}" if args.tag else ""
        path = os.path.join(args.out, f"{a}__{s}__{m}{tag}.json")
        if os.path.exists(path) and not args.force:
            print(f"CACHED {a} {s} {m}")
            continue
        print(f"RUN    {a} {s} {m} {overrides or ''} ...", flush=True)
        try:
            res = run_cell(a, s, m, overrides)
            res["overrides"] = overrides
            res["roofline"] = roofline_terms(res)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            print(f"OK     {a} {s} {m}: compile={res['compile_s']}s "
                  f"flops={res.get('flops', 0):.3g} "
                  f"coll={res['collectives']['total_bytes']:.3g}B", flush=True)
        except Exception as e:
            failures += 1
            with open(path + ".fail", "w") as f:
                f.write(traceback.format_exc())
            print(f"FAIL   {a} {s} {m}: {type(e).__name__}: {e}", flush=True)
    print(f"done: {len(cells)} cells, {failures} failures")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
