import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  This module is the ONLY place the 512 placeholder
# devices exist; tests and benchmarks see the real device count.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact with:
  - memory_analysis (bytes per device: argument/output/temp/peak)
  - cost_analysis   (per-device HLO FLOPs / bytes accessed)
  - collective op census + estimated bytes moved (parsed from optimized HLO)
  - analytic MODEL_FLOPS (6*N_active*D train, 2*N_active*D inference)
which EXPERIMENTS.md §Dry-run / §Roofline and benchmarks/roofline_report.py
consume.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_config, get_shape, cells
from repro.models import model as M
from repro.models import transformer as T
from repro.optim import AdamW
from repro.roofline.analysis import roofline_terms
from repro.roofline.hlo_cost import analyze as hlo_analyze
from repro.sharding.partition import PartitionRules, ShardCtx, make_mesh

ART_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading pod axis (2 pods)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16))


def _opt_for(cfg) -> AdamW:
    # >=100B params: bf16 moments so optimizer state fits 16 GB/chip HBM.
    big = cfg.param_count() >= 100e9
    return AdamW(state_dtype="bfloat16" if big else "float32")


def _grad_dtype_for(cfg) -> str:
    return "bfloat16" if cfg.param_count() >= 100e9 else "float32"


def build_cell(arch: str, shape_name: str, mesh, rules=None,
               cfg_overrides=None, mu_override=None):
    """Returns (fn, in_avals tuple, in_shardings tuple, out_shardings)."""
    import dataclasses
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = get_shape(shape_name)
    rules = rules or PartitionRules()
    sctx = ShardCtx(mesh, rules)

    p_aval = T.abstract_params(cfg)
    p_spec = T.param_pspecs(cfg, mesh, rules)
    p_sh = jax.tree.map(lambda s: jax.NamedSharding(mesh, s), p_spec)

    def shard_tree(axes_tree, aval_tree):
        specs = rules.tree_specs(axes_tree, aval_tree, mesh)
        return jax.tree.map(lambda s: jax.NamedSharding(mesh, s), specs)

    in_aval = M.input_specs(cfg, shape)
    in_sh = shard_tree(M.input_axes(cfg, shape), in_aval)
    repl = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())

    if shape.kind == "train":
        opt = _opt_for(cfg)
        o_aval = opt.abstract_state(p_aval)
        o_sh = type(o_aval)(repl, p_sh, p_sh)
        bspec = rules.spec_for(("batch",), (shape.global_batch,), mesh)
        n_batch_shards = 1
        for a in (jax.tree.leaves(tuple(bspec)) or []):
            n_batch_shards *= mesh.shape.get(a, 1)
        mu = (mu_override if mu_override else
              M.auto_microbatches(cfg, shape, n_batch_shards))
        # microbatching must not shrink the global batch below the number
        # of batch shards, or the partitioner replicates everything
        while mu > 1 and shape.global_batch // mu < n_batch_shards:
            mu //= 2
        fn = M.make_train_step(cfg, opt, sctx, microbatches=mu,
                               grad_dtype=_grad_dtype_for(cfg))
        fn.microbatches = mu
        avals = (p_aval, o_aval, in_aval)
        in_shardings = (p_sh, o_sh, in_sh)
        out_shardings = (p_sh, o_sh, repl)
    elif shape.kind == "prefill":
        fn = M.make_prefill_step(cfg, sctx)
        avals = (p_aval, in_aval)
        in_shardings = (p_sh, in_sh)
        cache_sh = shard_tree(T.cache_axes(cfg),
                              T.cache_specs(cfg, shape.global_batch,
                                            shape.seq_len, cfg.dtype))
        out_shardings = (repl, cache_sh)
    else:  # decode
        fn = M.make_decode_step(cfg, sctx)
        avals = (p_aval, in_aval["token"], in_aval["cache"], in_aval["pos"])
        in_shardings = (p_sh, in_sh["token"], in_sh["cache"], in_sh["pos"])
        out_shardings = (repl, in_sh["cache"])
    return cfg, shape, fn, avals, in_shardings, out_shardings


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             rules=None, cfg_overrides=None, tag: str = "", mu=None,
             mesh_shape=None):
    if mesh_shape:  # alternative carve of the same 256-chip pod (§Perf)
        mesh_name = f"pod{mesh_shape[0]}x{mesh_shape[1]}"
    else:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    suffix = f"__{tag}" if tag else ""
    out_path = out_dir / mesh_name / f"{arch}__{shape_name}{suffix}.json"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    t0 = time.time()
    if mesh_shape:
        mesh = make_mesh(mesh_shape)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    cfg, shape, fn, avals, in_sh, out_sh = build_cell(
        arch, shape_name, mesh, rules=rules, cfg_overrides=cfg_overrides,
        mu_override=mu)
    shape_cfg = SHAPES[shape_name]
    # donate params/opt_state (train) or the KV cache (decode): in-place
    # updates, halving peak residency — matches production deployment.
    donate = (0, 1) if shape_cfg.kind == "train" else (
        (2,) if shape_cfg.kind == "decode" else ())
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*avals)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        print(mem)     # proves it fits (bytes per device)
        print({k: v for k, v in cost.items()
               if k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()

    acc = hlo_analyze(hlo)
    census = {"ops": acc["collectives"],
              "moved_bytes_per_device": acc["coll_bytes"]}
    n_chips = mesh.devices.size
    mem_d = {k: getattr(mem, k, None) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    # CPU backend ignores donation (outputs land in temp despite the alias
    # claim): args + temp - alias approximates the TPU peak where donated
    # params/opt/cache update in place.
    peak = (mem_d.get("argument_size_in_bytes") or 0) + \
           (mem_d.get("temp_size_in_bytes") or 0) - \
           (mem_d.get("alias_size_in_bytes") or 0)
    training = shape.kind == "train"
    model_flops = cfg.model_flops_per_token(training) * shape.tokens
    artifact = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_d,
        "peak_bytes_per_device": peak,
        "fits_16GB": bool(peak < 16e9),
        "cost": {"flops_per_device": acc["flops"],
                 "bytes_per_device": acc["bytes"],
                 "bytes_by_scope": dict(sorted(
                     acc["bytes_by_scope"].items(),
                     key=lambda kv: -kv[1])[:60]),
                 "xla_flops_body_once": cost.get("flops"),
                 "xla_bytes_body_once": cost.get("bytes accessed")},
        "collectives": census,
        "model_flops_global": model_flops,
        "microbatches": getattr(fn, "microbatches", 1),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens": shape.tokens,
    }
    artifact["roofline"] = roofline_terms(artifact)
    out_path.write_text(json.dumps(artifact, indent=1))
    print(f"[dryrun] {mesh_name} {arch} {shape_name}: OK "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s, "
          f"peak/device {peak/1e9:.2f} GB)")
    return artifact


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--out", default=str(ART_DIR))
    ap.add_argument("--tag", default="", help="variant tag for the artifact")
    ap.add_argument("--rules-json", default="",
                    help='partition-rule overrides, e.g. '
                         '\'{"batch": [["data","model"]]}\'')
    ap.add_argument("--cfg-json", default="",
                    help='ModelConfig overrides, e.g. '
                         '\'{"moe_dispatch": "gather"}\'')
    ap.add_argument("--mu", type=int, default=0,
                    help="override gradient-accumulation depth")
    ap.add_argument("--mesh-shape", type=int, nargs=2, default=None,
                    help="alternative (data, model) carve of the 256-chip "
                         "pod (perf exploration)")
    args = ap.parse_args(argv)
    out_dir = Path(args.out)
    rules = None
    if args.rules_json:
        overrides = {k: tuple(tuple(c) for c in v)
                     for k, v in json.loads(args.rules_json).items()}
        rules = PartitionRules(overrides)
    cfg_overrides = json.loads(args.cfg_json) if args.cfg_json else None

    todo = []
    if args.all:
        for arch in ARCHS:
            for shape_name, status in cells(arch):
                if status != "RUN":
                    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
                    p = out_dir / mesh_name / f"{arch}__{shape_name}.json"
                    p.parent.mkdir(parents=True, exist_ok=True)
                    p.write_text(json.dumps({
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "status": status}, indent=1))
                    continue
                todo.append((arch, shape_name))
    else:
        todo.append((args.arch, args.shape))

    failures = []
    for arch, shape_name in todo:
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        p = out_dir / mesh_name / f"{arch}__{shape_name}.json"
        if args.skip_done and p.exists():
            try:
                if json.loads(p.read_text()).get("status") == "ok":
                    continue
            except Exception:
                pass
        try:
            run_cell(arch, shape_name, args.multi_pod, out_dir,
                     rules=rules, cfg_overrides=cfg_overrides, tag=args.tag,
                     mu=args.mu or None, mesh_shape=args.mesh_shape)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape_name, repr(e)))
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(json.dumps({
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "FAIL", "error": repr(e)[:2000]}, indent=1))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:", file=sys.stderr)
        for f in failures:
            print("  ", f, file=sys.stderr)
        sys.exit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    main()
