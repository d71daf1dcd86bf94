import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, and extract the roofline terms from the
compiled artifact.

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
    python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
    python -m repro.launch.dryrun --all            # every runnable cell
    python -m repro.launch.dryrun --list

Results land in experiments/dryrun/<arch>@<shape>@<mesh>.json:
memory_analysis (bytes/device), cost_analysis (FLOPs, bytes), the
collective-byte breakdown parsed from the optimized HLO, and timing.
"""  # noqa: E402

import argparse
import json
import pathlib
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import shape_by_name
from repro.launch import hlo_analysis
from repro.launch.cells import (
    all_cells, cell_config, grad_accum_dtype, input_specs, runnable_cells,
    train_microbatches)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    abstract_train_state, batch_shardings, cache_shardings,
    make_prefill_step, make_serve_step, make_train_step, replicated,
    train_state_shardings)
from repro.models import Model
from repro.optim import AdamWConfig
from repro.sharding import ShardingRules

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Lower + compile one cell. Returns (lowered, compiled, model, meta).
    overrides may include the exec-level key "microbatches"."""
    shape = shape_by_name(shape_name)
    overrides = dict(overrides or {})
    mb_override = overrides.pop("microbatches", None)
    cfg = cell_config(arch, shape, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ShardingRules(mesh)
    model = Model(cfg, rules)
    specs = input_specs(cfg, shape)
    b_sh = batch_shardings(cfg, shape, rules, specs)

    if shape.kind == "train":
        opt_cfg = AdamWConfig(
            state_dtype="bfloat16" if cfg.param_dtype == "bfloat16"
            else "float32")
        step = make_train_step(
            model, opt_cfg,
            microbatches=(mb_override if mb_override is not None
                          else train_microbatches(arch, shape)),
            grad_accum_dtype=grad_accum_dtype(arch))
        state_sds = abstract_train_state(model, opt_cfg)
        state_sh = train_state_shardings(model, rules, opt_cfg)
        lowered = jax.jit(
            step,
            in_shardings=(state_sh, b_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        ).lower(state_sds, specs)
    elif shape.kind == "prefill":
        step = make_prefill_step(model, max_len=shape.seq_len)
        p_sds, _ = model.abstract_params()
        p_sh = model.param_shardings(rules)
        lowered = jax.jit(
            step, in_shardings=(p_sh, b_sh),
        ).lower(p_sds, specs)
    else:  # decode
        step = make_serve_step(model)
        p_sds, _ = model.abstract_params()
        p_sh = model.param_shardings(rules)
        c_sds, _ = model.cache_spec(shape.global_batch, shape.seq_len)
        c_sh = cache_shardings(model, rules, shape.global_batch,
                               shape.seq_len)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jax.jit(
            step,
            in_shardings=(p_sh, b_sh["tokens"], c_sh, replicated(rules)),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),
        ).lower(p_sds, specs["tokens"], c_sds, pos_sds)
    compiled = lowered.compile()
    return lowered, compiled, model, {"cfg": cfg, "shape": shape,
                                      "mesh": mesh}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None, tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}@{shape_name}@{mesh_name}" + (f"@{tag}" if tag else "")
    t0 = time.time()
    lowered, compiled, model, meta = lower_cell(
        arch, shape_name, multi_pod, overrides)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = hlo_analysis.collective_bytes(compiled.as_text(), model)
    n_chips = 512 if multi_pod else 256

    result = {
        "cell": name,
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_chips": n_chips,
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "cost": {
            "flops_per_device": cost.get("flops"),
            "bytes_accessed_per_device": cost.get("bytes accessed"),
        },
        "collectives": coll,
        "params": model.cfg.param_count(),
        "active_params": model.cfg.active_param_count(),
        "padded_vocab": model.padded_vocab,
    }
    roof = hlo_analysis.roofline_terms(result, meta["shape"])
    result["roofline"] = roof
    print(f"[dryrun] {name}: compile={t_compile:.1f}s "
          f"peak={result['memory']['peak_bytes'] / 1e9:.2f}GB/dev "
          f"dominant={roof['dominant']}")
    return result


def save_result(res: dict):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / (res["cell"].replace("/", "_") + ".json")
    path.write_text(json.dumps(res, indent=2, default=str))
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--kv-policy", default=None,
                    help="override kv policy (flat|tiered)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable), e.g. "
                         "--set mla_absorbed=true --set kv_hot_window=4096")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.list:
        for c in all_cells():
            status = f"SKIP({c.skip_reason})" if c.skip_reason else "RUN"
            print(f"{c.name:45s} {status}")
        return

    overrides = {}
    if args.kv_policy:
        overrides["kv_policy"] = args.kv_policy
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = v

    if args.all:
        ok, fail = 0, 0
        for c in runnable_cells():
            for mp in ((False, True) if args.both_meshes else (False,)):
                try:
                    res = run_cell(c.arch, c.shape.name, mp, overrides,
                                   args.tag)
                    save_result(res)
                    ok += 1
                except Exception as e:  # noqa: BLE001
                    fail += 1
                    print(f"[dryrun] FAIL {c.name} multi_pod={mp}: {e}")
                    traceback.print_exc()
        print(f"[dryrun] done: {ok} ok, {fail} failed")
        sys.exit(1 if fail else 0)

    meshes = ((False, True) if args.both_meshes
              else ((args.multi_pod,)))
    for mp in meshes:
        res = run_cell(args.arch, args.shape, mp, overrides, args.tag)
        path = save_result(res)
        print(f"[dryrun] wrote {path}")


if __name__ == "__main__":
    main()
