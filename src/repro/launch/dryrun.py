import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import: jax locks the device count on first init.
# Multi-pod dry-run: AOT lower + compile every (arch × shape) cell on the
# production mesh and record memory/cost/collective statistics.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
#         --shape decode_32k --mesh single
#     PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
#
# Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json (skipped if it
# already exists — the sweep is resumable). Failures are recorded with the
# exception text: a failing cell is a bug in the sharding config.
# (No module docstring: the XLA_FLAGS assignment must be the first statement,
# and `from __future__` cannot follow a docstring-after-code.)

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import arch_names, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as S

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(?P<otype>\([^)]*\)|[\w!]+\[[^\]]*\][^\s]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(", )
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective, by op and by group
    size (group size tells us which mesh axis the collective spans)."""
    by_op: dict[str, int] = {}
    by_group: dict[str, int] = {}
    count = 0
    for m in _COLL_RE.finditer(hlo_text):
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end if line_end > 0 else None]
        nbytes = _shape_bytes(m.group("otype"))
        op = m.group("op")
        gm = _GROUPS_RE.search(line)
        if gm:
            gsize = len(gm.group(1).split(","))
        else:
            gm2 = _GROUPS_V2_RE.search(line)
            gsize = int(gm2.group(2)) if gm2 else 0
        by_op[op] = by_op.get(op, 0) + nbytes
        key = f"group{gsize}"
        by_group[key] = by_group.get(key, 0) + nbytes
        count += 1
    return {"bytes_by_op": by_op, "bytes_by_group_size": by_group,
            "n_collectives": count,
            "total_bytes": sum(by_op.values())}


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "kind": shape.kind, "status": None}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    if shape_name == "long_500k":
        cfg = cfg.replace(seq_shard_kv=True)

    mesh = make_production_mesh(multi_pod=multi_pod)
    mode = "train" if shape.kind == "train" else (
        "weight_gather" if cfg.weight_gather_serve else "serve")
    from repro.sharding import rules as R
    if (cfg.pure_fsdp_train and shape.kind == "train"
            and shape.global_batch % mesh.devices.size == 0):
        # ZeRO-3-only profile needs batch divisible by ALL axes; otherwise
        # fall back to the TP+FSDP profile (e.g. batch 256 on 512 chips)
        R.set_batch_axes(("pod", "data", "model"))
    t0 = time.time()
    with mesh:
        params, _ = S.param_shardings(cfg, mesh, mode)
        inputs = S.input_specs(cfg, shape, mesh)
        step = S.make_step_fn(cfg, shape)
        if shape.kind == "train":
            opt = S.opt_state_specs(params, mesh)
            args = (params, opt, inputs)
            donate = (0, 1)          # params/opt update in place
        elif shape.kind == "prefill":
            args = (params, inputs)
            donate = ()
        else:
            caches = S.cache_structs(cfg, shape, mesh)
            args = (params, inputs, caches)
            donate = (2,)            # KV caches update in place
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        hlo_text = compiled.as_text()
        colls = parse_collectives(hlo_text)
        # trip-count-aware models (XLA counts while bodies once — see costs.py)
        from repro.launch.costs import (collectives_with_trip_counts,
                                        jaxpr_cost)
        cost = compiled.cost_analysis()
        colls_tc = collectives_with_trip_counts(hlo_text)
        jcost = jaxpr_cost(step, *args)

    R.set_batch_axes(("pod", "data"))
    n_dev = mesh.devices.size
    rec.update(
        status="ok",
        n_devices=n_dev,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        # memory_analysis and cost_analysis are per-device (post-SPMD)
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        cost={
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "transcendentals": cost.get("transcendentals", 0.0),
        },
        # GLOBAL trip-count-aware semantic cost (divide by n_devices for the
        # ideal per-device cost) — see costs.py
        jaxpr_cost={k: int(v) for k, v in jcost.items()},
        collectives=colls,
        collectives_tc=colls_tc,
    )
    return rec


def cell_path(arch: str, shape_name: str, mesh_name: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{mesh_name}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="sweep all assigned (arch x shape) cells")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--assigned-only", action="store_true", default=True)
    args = ap.parse_args()

    assigned = [a for a in arch_names() if not a.startswith("prosparse")]
    archs = [args.arch] if args.arch else assigned
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                path = cell_path(arch, shape_name, mesh_name)
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {arch} {shape_name} {mesh_name}")
                    continue
                print(f"[run] {arch} {shape_name} {mesh_name}", flush=True)
                try:
                    rec = run_cell(arch, shape_name, multi)
                except Exception as e:  # a failing cell is a sharding bug
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "failed",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    peak = rec["memory"]["peak_bytes_est"] / 2**30
                    extra = (f" peak={peak:.2f}GiB flops={rec['cost']['flops']:.3g}"
                             f" coll={rec['collectives']['total_bytes']/2**20:.1f}MiB"
                             f" compile={rec['compile_s']:.0f}s")
                elif status == "failed":
                    extra = " " + rec["error"][:200]
                print(f"[{status}] {arch} {shape_name} {mesh_name}{extra}",
                      flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
