import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import: jax locks the device
# count at first init.  The dry run lowers for 512 host CPU devices and
# never takes an attached accelerator.  Everything below is ordinary code.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (sharding
propagates, collectives legal, memory fits) and extracts the §Roofline
inputs:  cost_analysis FLOPs/bytes + collective bytes parsed from the
partitioned HLO.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Records go to ``results/dryrun/<arch>.<shape>.<mesh>.json``; ``--out`` writes
a single cell's record elsewhere.
"""
import argparse
import gc
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, all_names, applicable, get
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod
from repro.launch import hloanalysis

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

def run_cell(arch: str, shape_name: str, mesh_kind: str,
             tp_mode: str | None = None) -> dict:
    cfg = get(arch)
    if tp_mode:
        cfg = cfg.with_policy(tp_mode=tp_mode)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": why}
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "tp_mode": cfg.policy.tp_mode}
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        kind, fn, shapes, _specs = steps_mod.make_step_for(cfg, mesh, shape)
        rec["step"] = kind
        t0 = time.time()
        lowered = fn.lower(*shapes)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

        # modern jax returns a list of per-computation dicts (older
        # releases returned the dict directly); normalize to one dict
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        rec["xla_flops_body_once"] = float(ca.get("flops", 0.0))
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
            "peak_bytes_per_device": (
                getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                - getattr(ma, "alias_size_in_bytes", 0)),
        }
        hlo = compiled.as_text()
        # trip-count-aware static analysis (launch/hloanalysis.py):
        # per-device FLOPs, fusion-boundary HBM traffic, ring-model link bytes
        rep = hloanalysis.analyze(hlo)
        rec["flops"] = rep.flops
        rec["dot_flops"] = rep.dot_flops
        rec["hlo_bytes"] = rep.hbm_bytes
        rec["collectives"] = dict(
            rep.collectives,
            total_link_bytes=rep.collective_link_bytes,
            total_link_bytes_bf16=rep.collective_link_bytes_bf16)
        rec["unknown_trip_loops"] = rep.unknown_trip_loops
        rec["hlo_lines"] = hlo.count("\n")
        rec["ok"] = True
        del compiled, lowered, fn
        gc.collect()
    except Exception as e:  # a failure here is a bug in the system
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def cell_path(arch, shape, mesh_kind, tp_mode=None) -> Path:
    tag = f".{tp_mode}" if tp_mode else ""
    return RESULTS_DIR / f"{arch}.{shape}.{mesh_kind}{tag}.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tp-mode", default=None,
                    choices=[None, "allreduce", "allgather", "ame_pim"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", type=Path, default=None,
                    help="write the record of one cell (one arch, one "
                         "shape, one mesh) to this path")
    args = ap.parse_args(argv)

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s) for a in all_names() for s in SHAPES]
    else:
        cells = [(args.arch, args.shape)]
    if args.out is not None and (args.all or len(meshes) > 1):
        ap.error("--out takes the record of one cell")

    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            out = args.out or cell_path(arch, shape, mk, args.tp_mode)
            if out.exists() and not args.force:
                rec = json.loads(out.read_text())
                status = ("SKIP " + rec.get("skipped", "")) if "skipped" in rec \
                    else ("ok" if rec.get("ok") else "FAIL(cached)")
                print(f"[cached] {arch} {shape} {mk}: {status}")
                failures += int(not rec.get("ok", True) and "skipped" not in rec)
                continue
            rec = run_cell(arch, shape, mk, args.tp_mode)
            out.write_text(json.dumps(rec, indent=1))
            if "skipped" in rec:
                print(f"{arch} {shape} {mk}: SKIP ({rec['skipped']})")
            elif rec["ok"]:
                mem = rec["memory"]["peak_bytes_per_device"] / 2 ** 30
                print(f"{arch} {shape} {mk}: ok  {rec['step']} "
                      f"flops={rec['flops']:.3g} mem/dev={mem:.2f}GiB "
                      f"link={rec['collectives']['total_link_bytes']:.3g}B "
                      f"(compile {rec['compile_s']}s)")
            else:
                failures += 1
                print(f"{arch} {shape} {mk}: FAILED  {rec['error']}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
