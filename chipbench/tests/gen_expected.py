"""What every per-layer reader of every cell returns on ONE synthetic run,
written down from a run of another checkout's code (the parent's), so that
a PR that merges or renames entries can hold each reading to the bit:

    python3 chipbench/tests/gen_expected.py <checkout> \
        > chipbench/tests/expected_readings.json

`<checkout>` is the root of the tree whose `BENCHMARK.json` and `chipbench/`
are read and run (PR 56: `git archive 44f788f`, the parent). The output is
{"scopes": [the capture's scope names], "readings": {cell: {metric name
THERE: value}}} for every reader that returns a number.
`test_merged_readings.py` builds the same facts (`synthetic_facts`, below,
from the file's `scopes`, so a scope a later PR brings changes nothing) for
this tree's readers and compares through the old name -> new name map.

The run is synthetic and so are its numbers (a share may pass 100): a
capture of two devices with one operation under every scope any
configuration declares or any metric's file names, in the decode step and
in the prefill chunk; a scrape that holds EVERY series, each value a fixed
function of the series' name; a trace summary and the client's statistics
as constants. Nothing here is a device's number.
"""

from __future__ import annotations

import json
import os
import sys
import zlib

_PROGRAMS = {"jit_decode_step": "jit(decode_step)/layers.scan/while",
             "jit_prefill_chunk": "jit(prefill_chunk)/while"}


#: the series a reader finds by walking the scrape, not by name
_LISTED = ('kv_pool_blocks_in_use{kind="full"}',
           'kv_pool_blocks_in_use{kind="window"}')


class Series(dict):
    """A scrape that holds every series: a value made from the name."""

    def __init__(self, at_end: bool):
        super().__init__()
        self.at_end = at_end
        for key in _LISTED:
            self[key] = self[key]

    def __bool__(self):
        return True

    def __contains__(self, key):
        return True

    def __getitem__(self, key):
        crc = zlib.crc32(str(key).encode())
        start = (crc % 1009) / 8.0
        return start + 512.0 + (crc % 9973) / 4.0 if self.at_end else start

    def get(self, key, default=None):
        return self[key]


def scope_names(bench: dict, root: str) -> list:
    """Every prefix a configuration declares and every scope a metric's
    file names, sorted; a stem that ends in `.` gets a leaf under it."""
    names = set()
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            names.update(json.load(f).get("trace", {}).get("known_scopes", ()))
    layers = os.path.join(root, "chipbench", "layers")
    for name in os.listdir(layers):
        with open(os.path.join(layers, name)) as f:
            args = json.load(f).get("args", {})
        for key in ("scopes", "scope"):
            value = args.get(key) or []
            names.update([value] if isinstance(value, str) else value)
    return sorted(n + "leaf" if n.endswith(".") else n for n in names)


def capture(names: list) -> dict:
    """`scopes.py`'s form: two devices, each program one enclosing
    operation with one operation a scope inside it, and one with no name."""
    devices = []
    for dev in range(2):
        ops, t = [], 1000
        for program, outer in _PROGRAMS.items():
            start = t
            t += 50
            for n in names:
                ns = 100 + zlib.crc32(f"{program}/{n}/{dev}".encode()) % 1900
                ops.append([t, ns, f"{outer}/body/llama.block/{n}/op"])
                t += ns + 20
            ops.append([start, t - start, outer])
            t += 300
        ops.append([t, 777 + dev, None])
        devices.append({"name": f"/device:TPU:{dev}", "ops": ops})
    return {"devices": devices}


def synthetic_facts(cell: dict, names: list, peaks: dict) -> dict:
    return {
        "config": cell["config"], "traffic": cell["traffic"],
        "metrics0": Series(False), "metrics1": Series(True),
        "client": {"mean_live_positions": 4230.5, "mean_live_requests": 15.5,
                   "tok_s": 1234.5, "ttft_p50_ms": 321.0, "ttft_p95_ms": 654.0,
                   "itl_p50_ms": 4.5, "itl_p95_ms": 9.5},
        "peaks": peaks, "memory_peak_bytes": 9_876_543_210,
        "trace": {"programs": {
            "jit_decode_step": {"count": 40, "mean_ms": 21.7,
                                "total_s": 0.868},
            "jit_prefill_chunk": {"count": 12, "mean_ms": 37.5,
                                  "total_s": 0.45},
            "jit_prefill_finish": {"count": 3, "mean_ms": 1.25,
                                   "total_s": 0.00375}},
            "busy_s": 1.4, "window_s": 1.6, "op_s": {}},
        "scopes_capture": capture(names),
        # the GPT-2 readers' and the host readers' captures: not built here
        "spans_capture": None, "hosttime_capture": None,
        "rpctime_capture": None, "trace_capture": None,
    }


def readings(root: str) -> dict:
    """{"scopes": [...], "readings": {cell: {metric: value}}} from the code
    and files under `root`."""
    sys.path.insert(0, root)
    from chipbench import cells
    from chipbench import peaks as pk

    assert os.path.dirname(os.path.dirname(os.path.abspath(
        cells.__file__))) == os.path.abspath(root), cells.__file__
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = scope_names(bench, root)
    out = {}
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        facts = synthetic_facts(cell, names, pk.PEAKS["TPU v5e"])
        got = {}
        for name, (fn, args) in cell["per_layer"].items():
            try:
                value = fn(facts, **args)
            except Exception:  # noqa: BLE001 — a reader of a capture that
                continue       # is not built here
            if value is not None:
                got[name] = value
        out[w["name"]] = got
    return {"scopes": names, "readings": out}


if __name__ == "__main__":
    json.dump(readings(sys.argv[1]), sys.stdout, indent=1, sort_keys=True)
    print()
