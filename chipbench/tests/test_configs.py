"""The configuration files and the per-layer metrics' files against what
they state: the bytes a roofline is priced at, the scopes a share may name,
and that `BENCHMARK.json` and `layers/` list the same metrics."""

import json
import os

import pytest

from chipbench import peaks as pk
from chipbench import scopes, spans

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)

#: the scope prefixes the programs of the cells write (PERF.md section 3,
#: the table "Written by the program"; `tests/test_benchmark_contract.py`
#: looks each of them up in the lowered step programs)
WRITTEN_SCOPES = {"attn.", "kv_pool.", "layers.scan", "sample", "gpt.",
                  "llama.", "moe.experts", "moe.route", "moe.combine"}


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(REPO, "BENCHMARK.json"))
CONFIGS = {c["name"]: _load(os.path.join(REPO, c["file"]))
           for c in BENCH["configs"]}
SERVING = sorted(n for n, c in CONFIGS.items()
                 if c["run"]["driver"] != "pipe")
LAYER_FILES = sorted(n[:-len(".json")]
                     for n in os.listdir(os.path.join(HERE, "layers")))


@pytest.mark.parametrize("name", SERVING)
def test_weights_are_priced_at_the_served_dtype(name):
    run = CONFIGS[name]["run"]
    assert run["weight_bytes_per_param"] == \
        scopes.OPERAND_BYTES[run["dtype"]]
    assert run["kv_bytes_per_element"] == 2  # the daemon's pool: bfloat16


def test_least_time_of_a_gpt2_large_chat_step():
    """16 tokens over 4230 live positions (a chat step, PERF.md section
    5): 772.7 M parameters x 2 B + 4230 x 184 320 B at 819e9 B/s."""
    cfg = CONFIGS["gpt2-large"]
    run = cfg["run"]
    assert pk.gpt_step_weight_bytes(cfg, 2) == pytest.approx(1.5454e9,
                                                             rel=1e-4)
    assert pk.kv_bytes_per_pos(cfg, run["kv_bytes_per_element"]) == 184320
    least = pk.decode_step_least_s(
        cfg, tokens=16, live_positions=4230,
        bytes_per_param=run["weight_bytes_per_param"],
        kv_bytes=run["kv_bytes_per_element"],
        peaks=pk.peaks_for("TPU v5 lite"))
    assert least["bound"] == "bandwidth"
    assert least["least_s"] == pytest.approx(2.84e-3, rel=0.01)


@pytest.mark.parametrize("metric", LAYER_FILES)
def test_a_layers_file_names_only_written_scopes(metric):
    args = _load(os.path.join(HERE, "layers", metric + ".json")).get(
        "args", {})
    named = set()
    for key in ("scopes", "known", "scope"):
        value = args.get(key) or []
        named.update([value] if isinstance(value, str) else value)
    assert named <= WRITTEN_SCOPES, metric


def test_the_gpt2_readers_scopes_are_written_ones():
    assert set(spans.SCOPES) <= WRITTEN_SCOPES


def test_every_per_layer_entry_has_its_file_and_no_file_is_left_over():
    assert sorted(m["name"] for m in BENCH["per_layer"]) == LAYER_FILES
