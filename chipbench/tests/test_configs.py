"""The configuration files and the per-layer metrics' files against what
they state: the bytes a roofline is priced at, the scopes a share may name
(a configuration declares the ones its programs write, `trace.known_scopes`),
how many readers each cell resolves to, and that `BENCHMARK.json` and
`layers/` list the same metrics."""

import json
import os

import pytest

from chipbench import cells, scopes, spans, step_roofline
from chipbench import peaks as pk

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)


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

#: the scope prefixes the programs of the cells write: what each
#: configuration declares and, for the two that declare none, the GPT-2
#: readers' own (PERF.md section 3, the table "Written by the program";
#: `tests/test_benchmark_contract.py` looks each of them up in the lowered
#: step programs)
WRITTEN_SCOPES = set(spans.SCOPES).union(*(
    scopes.known_scopes({"config": c}) or () for c in CONFIGS.values()))

#: each configuration's list, letter for letter: the three that every
#: `moe_scope_*`, `keye_scope_*` and `joy_scope_*` file (and
#: `moe_experts_roofline_pct`) repeated under `args.known` until PR 39, and
#: the five that PRs 41-54 brought with their configurations
_EXPERTS = ["moe.experts", "moe.route", "moe.combine"]
_LLAMA = ["attn.", "kv_pool.", "llama.", "sample", "layers.scan"]
KNOWN_SCOPES = {
    "olmoe-1b-7b-1chip": _EXPERTS + _LLAMA,
    "keye-vl-2.0-30b-a3b-ep8-1chip":
        ["dsa.index", "dsa.select"] + _EXPERTS + _LLAMA,
    "joyai-llm-flash-ep16-1chip":
        ["mla.project", "mla.absorb", "mla.up_project"] + _EXPERTS
        + ["moe.shared"] + _LLAMA,
    "dots3-note-prev-ep8-1chip":
        ["dsa.index", "dsa.select", "mla.project", "mla.absorb",
         "mla.up_project", "mla.gate"] + _EXPERTS + ["moe.shared"] + _LLAMA,
    "k-exaone-236b-a23b-ep8-1chip": _EXPERTS + ["moe.shared"] + _LLAMA,
    "solar-open2-250b-ep8-1chip":
        ["kda.project", "kda.scan", "kda.step", "kda.out", "state_pool.",
         "attn_gate"] + _EXPERTS + ["moe.shared"] + _LLAMA,
    # no K/V layer: no `attn.`, no `kv_pool.`
    "brumby-14b-pp8-1chip":
        ["ret.project", "ret.chunk", "ret.step", "ret.out", "state_pool.",
         "llama.", "sample", "layers.scan"],
    "falcon-h1-34b-pp8-1chip": ["ssm.", "state_pool."] + _LLAMA,
}

#: the module whose `decode_step_roofline_pct` counts a configuration's
#: step (`trace.roofline`, PR 56); OLMoE's and the pipeline's name none
ROOFLINE = {
    "gpt2-large": "reducers",
    "keye-vl-2.0-30b-a3b-ep8-1chip": "keye_roofline",
    "joyai-llm-flash-ep16-1chip": "mla_roofline",
    "dots3-note-prev-ep8-1chip": "dots3_roofline",
    "k-exaone-236b-a23b-ep8-1chip": "exaone_roofline",
    "solar-open2-250b-ep8-1chip": "solar_roofline",
    "brumby-14b-pp8-1chip": "brumby_roofline",
    "falcon-h1-34b-pp8-1chip": "falcon_h1_roofline",
}

#: readers a cell, in `BENCHMARK.json`'s order of cells (the ledger's PR 55
#: lines hold as many per-layer readings a cell, and PR 56's tree resolves
#: to exactly these): merging entries that share a reader takes no reading
#: from a cell and gives it none
READERS = {"large-chat-saturated": 34, "large-chat-steady": 20,
           "large-prefill-saturated": 33, "pipe4-batch-forward": 3,
           "large-chat-bursty": 23, "olmoe-chat-saturated": 36,
           "keye-videoqa-saturated": 34, "joyai-docreport-saturated": 36,
           "dots3-longnote-saturated": 49,
           "kexaone-reasoning-saturated": 45,
           "solar2-longchat-saturated": 45, "brumby-fewshot-saturated": 37,
           "falconh1-chat-saturated": 37}


@pytest.mark.parametrize("name", SERVING)
def test_weights_are_priced_at_the_served_dtype(name):
    run = CONFIGS[name]["run"]
    assert run["weight_bytes_per_param"] == \
        scopes.OPERAND_BYTES[run["dtype"]]
    # the daemon's pool: bfloat16 (a configuration with no K/V layer states
    # it too: its rooflines price no cached position)
    assert run["kv_bytes_per_element"] == 2


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
    assert "known" not in args, "the configuration declares the prefixes"
    named = set()
    for key in ("scopes", "scope"):
        value = args.get(key) or []
        named.update([value] if isinstance(value, str) else value)
    # a declared prefix, one scope under it (`attn.mla_decode` under
    # `attn.`) or a stem of several (`dsa.` of `dsa.index`, `dsa.select`)
    strays = {n for n in named if not any(
        n.startswith(w) or w.startswith(n) for w in WRITTEN_SCOPES)}
    assert not strays, (metric, strays)


@pytest.mark.parametrize("name", sorted(KNOWN_SCOPES) + [
    "gpt2-large", "gpt2-large-pipe4"])
def test_a_configuration_declares_the_list_its_files_held(name):
    assert scopes.known_scopes({"config": CONFIGS[name]}) == \
        KNOWN_SCOPES.get(name)


@pytest.mark.parametrize("name", sorted(ROOFLINE) + [
    "olmoe-1b-7b-1chip", "gpt2-large-pipe4"])
def test_a_configuration_names_the_module_that_counts_its_step(name):
    assert step_roofline.roofline_module({"config": CONFIGS[name]}) == \
        ROOFLINE.get(name)
    assert set(CONFIGS[name].get("trace", {})) <= {"roofline",
                                                   "known_scopes"}


def test_every_cell_resolves_to_as_many_readers_as_before():
    """The thirteen cells of PR 55; a cell a later PR adds is held by the
    test file it brings."""
    assert [w["name"] for w in BENCH["workloads"]][:13] == list(READERS)
    got = {w: len(cells.resolve(w)["per_layer"]) for w in READERS}
    # equal on PR 56's tree; a later PR's new entry may add to a cell, none
    # takes a reading away (and none reports one twice:
    # `test_merged_readings.py`)
    assert all(got[w] >= n for w, n in READERS.items()), got


@pytest.mark.parametrize("workload", [
    w["name"] for w in BENCH["workloads"] if w["config"] in KNOWN_SCOPES])
def test_a_cells_scope_shares_cover_its_configurations_list_once(workload):
    """Each prefix the configuration declares goes to exactly one
    `scopes:share_pct` entry the cell reports, and one entry takes the
    operations with no scope: the shares add up to 100."""
    cell = cells.resolve(workload)
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(cell["config"]["trace"]["known_scopes"])


def test_a_configuration_without_the_block_has_no_scope_shares():
    without = [n for n, c in CONFIGS.items()
               if "known_scopes" not in c.get("trace", {})]
    assert sorted(without) == ["gpt2-large", "gpt2-large-pipe4"]
    for name in without:
        facts = _family_facts(CONFIGS[name])
        assert scopes.share_pct(facts, scopes=["attn."]) is None
        assert scopes.share_pct(facts, scopes=None) is None
    for w in BENCH["workloads"]:
        if w["config"] in without:
            assert not [n for n, (fn, _) in cells.resolve(
                w["name"])["per_layer"].items() if fn is scopes.share_pct]


# ----------------------------------------------------------------------
# the same numbers from the configuration's list as from the files' copies
# ----------------------------------------------------------------------

_D, _P = "jit(decode_step)/layers.scan/while", "jit(prefill_chunk)/while"
#: one capture with operations under the scopes of all three families
#: ([start_ns, duration_ns, op_name], `scopes.py`'s form): a family's list
#: decides which component of a name is its scope
_OPS = [
    [0, 5000, _D],
    [100, 700, _D + "/body/llama.block.attn/mla.project/dot_general"],
    [900, 130, _D + "/body/llama.block.attn/mla.absorb/dot_general"],
    [1100, 900,
     _D + "/body/llama.block.cached_attn/attn.mla_decode/pallas_call"],
    [2100, 90, _D + "/body/kv_pool.write/dynamic_update_slice"],
    [2300, 310, _D + "/body/llama.block.mlp/moe.route/top_k"],
    [2700, 1100, _D + "/body/llama.block.mlp/moe.experts/grouped_matmul"],
    [3900, 170, _D + "/body/llama.block.mlp/moe.combine/add"],
    [4100, 230, _D + "/body/llama.block.mlp/moe.shared/dot_general"],
    [4400, 410, _D + "/body/llama.block.attn/dsa.index/einsum"],
    [4850, 120, _D + "/body/llama.block.attn/dsa.select/top_k"],
    [5100, 260, "jit(decode_step)/llama.head/dot_general"],
    [5400, 75, "jit(decode_step)/sample/argmax"],
    [5500, 333, None],
    [6000, 1500, _P + "/body/llama.block.mlp/moe.experts/grouped_matmul"],
    [7600, 640, _P + "/body/llama.block.attn/mla.up_project/dot_general"],
    [8300, 820,
     _P + "/body/llama.block.cached_attn/attn.sparse_prefill/pallas_call"],
]
_OPS2 = [[s + 7, d - 3 if n else d, n] for s, d, n in _OPS if n != _D] \
    + [[0, 5100, _D]]

#: what the PARENT's form read on that capture (ac4a227: `share_pct` and
#: `experts_roofline_pct` handed the same list as `known=` from each
#: metric's own file), under the names the entries have now
_PARENT_READ = {
    "olmoe-chat-saturated": {
        "scope_experts_pct": 29.952136554985294,
        "scope_route_pct": 5.501412836629953,
        "scope_attn_pct": 19.802779539818925,
        "scope_kv_pool_pct": 1.0207023816388905,
        "scope_model_pct": 39.88235972550603,
        "scope_unscoped_pct": 3.84060896142091,
        "moe_experts_roofline_pct": 462241.2087078451,
    },
    "keye-videoqa-saturated": {
        "scope_index_pct": 4.711377659881206,
        "scope_select_pct": 1.3667031889741077,
        "scope_attn_pct": 19.802779539818925,
        "scope_experts_pct": 29.952136554985294,
        "scope_route_pct": 5.501412836629953,
        "scope_kv_pool_pct": 1.0207023816388905,
        "scope_model_pct": 33.80427887665072,
        "scope_unscoped_pct": 3.84060896142091,
    },
    "joyai-docreport-saturated": {
        "scope_mla_project_pct": 8.056052130788304,
        "scope_mla_absorb_pct": 1.4820367914191799,
        "scope_mla_up_project_pct": 7.364050516117873,
        "scope_attn_pct": 19.802779539818925,
        "scope_kv_pool_pct": 1.0207023816388905,
        "scope_experts_pct": 29.952136554985294,
        "scope_route_pct": 5.501412836629953,
        "scope_shared_pct": 2.635372815869904,
        "scope_model_pct": 20.344847471310768,
        "scope_unscoped_pct": 3.84060896142091,
    },
}


def _family_facts(config):
    series = {n: f'moe_{n}_total{{program="decode"}}' for n in
              ("layer_calls", "assignments", "active_experts")}
    m1 = {series["layer_calls"]: 6.0, series["assignments"]: 768.0,
          series["active_experts"]: 330.0}
    return {"scopes_capture": {"devices": [
                {"name": "/device:TPU:0", "ops": [list(o) for o in _OPS]},
                {"name": "/device:TPU:1", "ops": [list(o) for o in _OPS2]}]},
            "trace": {"programs": {"jit_decode_step": {"count": 2}}},
            "peaks": pk.PEAKS["TPU v5e"], "config": config,
            "metrics0": dict.fromkeys(m1, 0.0), "metrics1": m1}


@pytest.mark.parametrize("workload", sorted(_PARENT_READ))
def test_the_configurations_list_reads_what_the_files_copies_read(workload):
    cell = cells.resolve(workload)
    facts = _family_facts(cell["config"])
    got = {name: fn(facts, **args)
           for name, (fn, args) in cell["per_layer"].items()
           if fn in (scopes.share_pct, scopes.experts_roofline_pct)}
    assert got == _PARENT_READ[workload]  # to the bit
    assert sum(v for k, v in got.items() if "roofline" not in k) == \
        pytest.approx(100.0)


def test_every_per_layer_entry_has_its_file_and_no_file_is_left_over():
    assert sorted(m["name"] for m in BENCH["per_layer"]) == LAYER_FILES
