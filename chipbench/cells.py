"""From a cell's name in BENCHMARK.json to its files. Everything that
belongs to one configuration, one traffic mix or one per-layer metric is a
file of its own, found by name: `configs/<config>.json` (BENCHMARK.json
gives the path), `traffic/<traffic>.json`, `layers/<metric>.json`."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def named(spec: str, default_module: str):
    """The attribute a data file names as `"<module>:<attribute>"` (a
    module under chipbench/) or, from `default_module`, as `"<attribute>"`."""
    mod, _, attr = spec.rpartition(":")
    module = importlib.import_module(f"chipbench.{mod or default_module}")
    return getattr(module, attr)


def resolve(workload: str, *, rehearse: bool = False) -> dict:
    """{"cell", "config", "traffic", "end_to_end": [names], "per_layer":
    {name: (function, args)}} for one entry of `workloads`. With
    `rehearse`, each file's `rehearsal` block is laid over it."""
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(REPO, cfg_entry["file"]))
    traffic = _load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        config = _overlay(config, config.get("rehearsal", {}))
        traffic = _overlay(traffic, traffic.get("rehearsal", {}))

    def here(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    readers = {}
    for m in bench["per_layer"]:
        if here(m):
            spec = _load(os.path.join(HERE, "layers", m["name"] + ".json"))
            readers[m["name"]] = (named(spec["reducer"], "reducers"),
                                  spec.get("args", {}))
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m["name"] for m in bench["end_to_end"] if here(m)],
            "per_layer": readers, "units": units}
