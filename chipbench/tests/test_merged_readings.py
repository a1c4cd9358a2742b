"""PR 56 merged thirteen letter-for-letter duplicates into five entries,
seven of the eight whole-step rooflines into ONE whose reader the
configuration names (`trace.roofline`, `step_roofline.py`), and renamed five
entries that a second cell had joined under the first cell's prefix. Here:
every reading of every cell is, TO THE BIT, what the parent's entry returned
on the same synthetic run (`expected_readings.json`, written by
`gen_expected.py` from the parent's checkout), under its new name; and the
rule that would have caught the duplicates."""

import json
import os

import pytest
from gen_expected import synthetic_facts

from chipbench import cells, step_roofline
from chipbench import peaks as pk

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(REPO, "BENCHMARK.json"))
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
CONFIGS = {c["name"]: _load(os.path.join(REPO, c["file"]))
           for c in BENCH["configs"]}
EXPECTED = _load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "expected_readings.json"))
PARENT_READ = EXPECTED["readings"]

#: the ledger's lines up to PR 55 use the names on the left
MAPPING = {
    "joy_scope_shared_pct": "scope_shared_pct",
    "dots_scope_shared_pct": "scope_shared_pct",
    "kx_scope_shared_pct": "scope_shared_pct",
    "keye_scope_index_pct": "scope_index_pct",
    "dots_scope_index_pct": "scope_index_pct",
    "keye_scope_select_pct": "scope_select_pct",
    "dots_scope_select_pct": "scope_select_pct",
    "keye_selected_share": "srv_selected_share",
    "dots_selected_share": "srv_selected_share",
    "joy_scope_mla_project_pct": "scope_mla_project_pct",
    "dots_scope_mla_project_pct": "scope_mla_project_pct",
    "joy_scope_mla_absorb_pct": "scope_mla_absorb_pct",
    "dots_scope_mla_absorb_pct": "scope_mla_absorb_pct",
    "joy_scope_mla_up_project_pct": "scope_mla_up_project_pct",
    "dots_scope_mla_up_project_pct": "scope_mla_up_project_pct",
    "sat_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "keye_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "dots_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "kx_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "sol_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "brm_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "fh1_decode_step_roofline_pct": "srv_decode_step_roofline_pct",
    "dots_window_blocks_share": "srv_window_blocks_share",
    "dots_window_blocks_freed_per_step": "srv_window_blocks_freed_per_step",
    "sol_scope_state_pool_pct": "scope_state_pool_pct",
    "sol_state_read_share": "srv_state_read_share",
    "sol_pad_positions_share": "srv_pad_positions_share",
}

#: the cells that report the whole step's share through the one entry (the
#: module each configuration names: `test_configs.py`, `ROOFLINE`). JoyAI's
#: stays `joy_decode_step_roofline_pct` (`tests/test_mla.py` holds the cell
#: to that name); its file names its module all the same
STEP_CELLS = [
    "large-chat-saturated", "keye-videoqa-saturated",
    "dots3-longnote-saturated", "kexaone-reasoning-saturated",
    "solar2-longchat-saturated", "brumby-fewshot-saturated",
    "falconh1-chat-saturated"]

_FACTS = {}


def _facts(cell_name):
    """One synthetic run a cell, shared by the cases (readers cache what
    they parse on `facts`, as in a real run)."""
    if cell_name not in _FACTS:
        cell = cells.resolve(cell_name)
        _FACTS[cell_name] = (cell, _synthetic(cell))
    return _FACTS[cell_name]


def _synthetic(cell):
    return synthetic_facts(cell, EXPECTED["scopes"], pk.PEAKS["TPU v5e"])


def _empty(config):
    """A run with no capture of a daemon that predates every counter."""
    return {"config": config, "metrics0": {}, "metrics1": {}, "trace": None,
            "peaks": None, "trace_capture": None, "client": {}}


def _read(cell_name, metric):
    cell, facts = _facts(cell_name)
    fn, args = cell["per_layer"][metric]
    return fn(facts, **args)


@pytest.mark.parametrize("cell,old", [
    pytest.param(cell, old, id=f"{cell}:{old}")
    for cell in sorted(PARENT_READ) for old in sorted(PARENT_READ[cell])
    if old in MAPPING])
def test_a_merged_or_renamed_reading_is_the_parents_to_the_bit(cell, old):
    assert _read(cell, MAPPING[old]) == PARENT_READ[cell][old]


@pytest.mark.parametrize("cell", sorted(PARENT_READ))
def test_every_other_reading_of_a_cell_is_the_parents_to_the_bit(cell):
    kept = {n: v for n, v in PARENT_READ[cell].items() if n not in MAPPING}
    assert kept  # the cell reads something here
    assert {n: _read(cell, n) for n in kept} == kept


@pytest.mark.parametrize("new", sorted(set(MAPPING.values())))
def test_every_cell_of_a_new_name_was_compared(new):
    """The synthetic run gives each merged or renamed reader something to
    read in each of its cells: none of the cases above is vacuous."""
    olds = {o for o, n in MAPPING.items() if n == new}
    for cell in ENTRIES[new]["workloads"]:
        if cell in PARENT_READ:  # a cell a later PR added has no parent
            assert len(olds & set(PARENT_READ[cell])) == 1, (new, cell)


def test_the_old_names_are_gone_and_each_new_name_is_there_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    files = [n[:-len(".json")] for n in os.listdir(
        os.path.join(HERE, "layers"))]
    for old, new in MAPPING.items():
        assert old not in names and old not in files, old
        assert names.count(new) == 1 and files.count(new) == 1, new
    assert len(set(MAPPING.values())) == 13
    # 127 at PR 55: thirteen duplicates became five, seven of the eight
    # whole-step rooflines one; later PRs add, and 128 is all the list holds
    assert 113 <= len(names) <= 128


def test_no_two_entries_agree_in_file_and_fields():
    """One entry a reader (README, "A per-layer metric"): two entries with
    the same parsed file and the same `layer`, `moves`, `unit`, `better`
    and `source` are one reading under two names, and the second cell joins
    the first entry's `workloads` instead."""
    seen = {}
    for m in BENCH["per_layer"]:
        spec = _load(os.path.join(HERE, "layers", m["name"] + ".json"))
        key = json.dumps([spec.get("reducer"), spec.get("args", {})]
                         + [m[k] for k in ("layer", "moves", "unit",
                                           "better", "source")],
                         sort_keys=True)
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]


def test_no_cell_reports_one_reading_twice():
    """Within a cell no two entries name the same reader with the same
    arguments (entries that differ in `moves` alone never share a cell)."""
    for w in BENCH["workloads"]:
        per = cells.resolve(w["name"])["per_layer"]
        keys = [(fn.__module__, fn.__name__, json.dumps(args, sort_keys=True))
                for fn, args in per.values()]
        assert len(keys) == len(set(keys)), w["name"]


# ----------------------------------------------------------------------
# the whole step's share: the configuration names the module
# ----------------------------------------------------------------------

def test_the_whole_steps_entry_lists_the_cells_that_reported_one():
    entry = ENTRIES["srv_decode_step_roofline_pct"]
    assert entry["workloads"][:len(STEP_CELLS)] == STEP_CELLS
    assert (entry["layer"], entry["moves"], entry["unit"], entry["better"],
            entry["source"]) == ("Kernels", "out_tok_s", "%", "higher",
                                 "device_trace")
    assert _load(os.path.join(
        HERE, "layers", "srv_decode_step_roofline_pct.json")) == {
        "reducer": "step_roofline:decode_step_roofline_pct",
        "args": {"program": "jit_decode_step"}}
    configs = {w["name"]: w["config"] for w in BENCH["workloads"]}
    for cell in entry["workloads"]:
        module = CONFIGS[configs[cell]]["trace"]["roofline"]
        assert callable(cells.named(f"{module}:decode_step_roofline_pct",
                                    "reducers"))
    # the prefill, steady, bursty, OLMoE and pipeline cells report none
    for cell in ("large-chat-steady", "large-prefill-saturated",
                 "large-chat-bursty", "olmoe-chat-saturated",
                 "pipe4-batch-forward"):
        assert cell not in entry["workloads"]


def test_joyais_file_names_its_module_and_its_entry_keeps_its_name():
    """`tests/test_mla.py` (tier-1) holds `joy_decode_step_roofline_pct` by
    name, so that entry waits for a PR that may edit it; the reader the
    configuration names already returns the same number."""
    cell = "joyai-docreport-saturated"
    assert cell not in ENTRIES["srv_decode_step_roofline_pct"]["workloads"]
    assert ENTRIES["joy_decode_step_roofline_pct"]["workloads"] == [cell]
    config = CONFIGS["joyai-llm-flash-ep16-1chip"]
    assert config["trace"]["roofline"] == "mla_roofline"
    _, facts = _facts(cell)
    assert step_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step") == \
        PARENT_READ[cell]["joy_decode_step_roofline_pct"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_configuration_that_names_no_module_reads_none(name):
    """No fallback and no default, as `scopes.known_scopes` since PR 39:
    with the key taken out (or never there: OLMoE's, the pipeline's) the
    reader returns None and raises nothing, on a full run and on an empty
    one."""
    config = dict(CONFIGS[name])
    config["trace"] = {k: v for k, v in config.get("trace", {}).items()
                       if k != "roofline"}
    cell = cells.resolve(next(w["name"] for w in BENCH["workloads"]
                              if w["config"] == name))
    for facts in (_synthetic(dict(cell, config=config)), _empty(config)):
        assert step_roofline.roofline_module(facts) is None
        assert step_roofline.decode_step_roofline_pct(
            facts, program="jit_decode_step") is None
    if name in ("olmoe-1b-7b-1chip", "gpt2-large-pipe4"):
        assert "roofline" not in CONFIGS[name].get("trace", {})


def test_a_run_without_a_capture_reads_none_through_the_named_module():
    """A daemon that predates the counters, a run with no trace: each
    module's own None comes back through the one entry."""
    for name in STEP_CELLS:
        cell = cells.resolve(name)
        fn, args = cell["per_layer"]["srv_decode_step_roofline_pct"]
        assert fn is step_roofline.decode_step_roofline_pct
        assert fn(_empty(cell["config"]), **args) is None, name
