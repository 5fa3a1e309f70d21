import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))
os.environ["JAX_PLATFORMS"] = "cpu"     # 512 host devices, never the chip
"""Profiling-by-static-analysis: attribute a cell's roofline terms to
instructions (the dry-run 'profiler' — there is no wall clock on CPU).

  PYTHONPATH=src python -m repro.launch.attribute --arch command-r-35b \
      --shape train_4k [--what coll|mem] [--top 15] [--set tp_mode=allgather]
"""
import argparse
import re

from repro.configs import SHAPES, get
from repro.launch import hloanalysis as ha


def apply_overrides(cfg, sets):
    for kv in sets or []:
        k, v = kv.split("=", 1)
        if v in ("True", "true", "False", "false"):
            v = v.lower() == "true"
        elif v.isdigit():
            v = int(v)
        cfg = cfg.with_policy(**{k: v})
    return cfg


def compile_cell(arch, shape, sets=None, mesh_kind="single"):
    from repro.launch.mesh import make_production_mesh
    from repro.launch import steps as steps_mod
    cfg = apply_overrides(get(arch), sets)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    kind, fn, shapes, _ = steps_mod.make_step_for(cfg, mesh, SHAPES[shape])
    return fn.lower(*shapes).compile()


def attribute(hlo: str, what: str = "coll", top: int = 15):
    comps = ha.parse_hlo(hlo)
    mult = ha._multipliers(comps)
    seq = {comps["__entry__"].name} if "__entry__" in comps else set()
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op in ha._SEQUENTIAL_CALLERS or ins.op == "while":
                for nm in ha._called_names(ins.line):
                    seq.add(nm)
    rows = []
    for comp in comps.values():
        m = mult.get(comp.name, 0)
        if m <= 0:
            continue
        for ins in comp.instrs:
            base = ins.op.replace("-start", "")
            if what == "coll":
                if base in ha.COLLECTIVES and not ins.op.endswith("-done"):
                    kindc, link = ha._collective_link_bytes(ins)
                    if link:
                        rows.append((m * link, m, link, kindc, ins))
            else:
                if comp.name not in seq or ins.op in ha.NO_TRAFFIC_OPS \
                        or ins.op.endswith("-done"):
                    continue
                opds = ins.operands()
                if ins.op == "fusion" and len(opds) <= 1 and \
                        re.match(r"^(convert|copy)[._]", ins.name):
                    continue
                io = ins.out_bytes()
                sizes = []
                for opd in opds:
                    part = comp.shapes.get(opd)
                    if part:
                        s = sum(ha._shape_bytes(sm)
                                for sm in ha._SHAPE_RE.finditer(part))
                        sizes.append(s)
                        io += s
                if "dynamic-update-slice" in ins.op or \
                        ins.name.startswith("dynamic-update-slice"):
                    if sizes:
                        io = max(io - 2 * max(sizes), 0)
                if io:
                    rows.append((m * io, m, io, ins.op, ins))
    rows.sort(key=lambda r: -r[0])
    out = []
    for tot, m, each, kindc, ins in rows[:top]:
        mm = re.search(r'op_name="([^"]+)"', ins.line)
        opn = (mm.group(1) if mm else ins.name)[-100:]
        out.append(f"{tot/1e9:10.2f}GB  m={m:7.0f} each={each/1e6:9.2f}MB "
                   f"{kindc:16s} {opn}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--what", default="coll", choices=["coll", "mem"])
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()
    compiled = compile_cell(args.arch, args.shape, args.set)
    hlo = compiled.as_text()
    rep = ha.analyze(hlo)
    print(f"flops={rep.flops:.4g} hbm={rep.hbm_bytes:.4g} "
          f"link={rep.collective_link_bytes:.4g}")
    for line in attribute(hlo, args.what, args.top):
        print(line)


if __name__ == "__main__":
    main()
