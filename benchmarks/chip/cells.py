"""Find a cell and everything that belongs to it, by the names in
``BENCHMARK.json``.

A cell names a configuration (``configs`` entry, its ``file``, whose
``family`` is read by ``<paths[0]>/families/<family>.py``), a traffic mix
(``<paths[0]>/traffic/<mix>.json``) and the chips it needs.  Its metrics are
the ``end_to_end`` and ``per_layer`` entries that list it under
``workloads``, or that have no such list; each per-layer metric is read by
``<paths[0]>/metrics/<metric>.py``.  Adding a cell adds files and entries and
edits none of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

from chip import shapes, traffic
from chip.shapes import Shape

REPO_ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # configs/<config>.json
    mix: dict                   # traffic/<mix>.json
    shape: Shape
    end_to_end: List[dict]      # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    readers: Dict[str, Callable]
    grid: List[int]             # every prompt length the mix can send


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have {[e['name'] for e in entries]})")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _reader(path: Path) -> Callable:
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(workload: str, root: Path = REPO_ROOT) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    chip_dir = root / bench["paths"][0]
    w = _by_name(bench["workloads"], workload, "workload")
    with open(root / _by_name(bench["configs"], w["config"], "config")["file"]) as f:
        config = json.load(f)
    mix = traffic.load(chip_dir, w["traffic"])
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    if longest >= mix["cache_len"]:
        raise ValueError(f"mix {w['traffic']!r}: prompt + answer can reach "
                         f"{longest} tokens, cache_len {mix['cache_len']} "
                         f"would cut it short")
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload)]
    return Cell(
        name=workload, chips=w["chips"], config=config, mix=mix,
        shape=shapes.from_config(config, chip_dir),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=per_layer,
        readers={m["name"]: _reader(chip_dir / "metrics" / f"{m['name']}.py")
                 for m in per_layer},
        grid=traffic.prompt_grid(mix))


def _override(obj, path: List[str], value):
    if len(path) == 1:
        return dataclasses.replace(obj, **{path[0]: value})
    inner = getattr(obj, path[0])
    return dataclasses.replace(
        obj, **{path[0]: _override(inner, path[1:], value)})


def program_config(cell: Cell):
    """The program's ``ArchConfig`` for the cell: the registry entry with the
    configuration file's overrides, checked against the published sizes."""
    from repro.configs.base import get
    cfg = get(cell.config["registry"])
    for key, value in cell.config.get("program_overrides", {}).items():
        cfg = _override(cfg, key.split("."), value)
    wrong = {k: (shapes.get_path(cfg, k), v)
             for k, v in shapes.program_sizes(cell.shape).items()
             if shapes.get_path(cfg, k) != v}
    if wrong:
        raise ValueError(f"the program's {cell.config['registry']!r} differs "
                         f"from the published configuration (program, "
                         f"published): {wrong}")
    return cfg
