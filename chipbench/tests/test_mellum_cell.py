"""The Mellum2 configuration, its traffic and its per-layer files as cases of
what `test_configs.py` and `test_traffic.py` hold every configuration and
backlog to (a PR that adds a configuration adds files here and edits none:
those two files' literal tables wait for a `benchmark` PR), and the
configuration's own: the catalog row, both `rope_parameters` blocks against
the program's tables, the operations and bytes its rooflines are priced at
through K-EXAONE's readers (`exaone_roofline.py`, no copy)."""

import json
import os

import pytest

from chipbench import cells, exaone_roofline, scopes, step_roofline
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "mellum2-12b-a2.5b-pp4-1chip", "mellum2-repoassist-saturated"
TRAFFIC = "repo-assist-backlog"
KX = "kexaone-reasoning-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["layer_types", "mlp_layer_types", "num_hidden_layers"]
KNOWN = ["moe.experts", "moe.route", "moe.combine", "attn.", "kv_pool.",
         "llama.", "sample", "layers.scan"]
#: the by-kind entries K-EXAONE's cell brought, joined as they are
BY_KIND = [
    "kx_full_decode_roofline_pct", "kx_window_decode_roofline_pct",
    "kx_full_prefill_roofline_pct", "kx_window_prefill_roofline_pct",
    "kx_experts_roofline_pct", "kx_window_roll_ms_per_step",
    "kx_table_flushes_per_step", "kx_full_cache_read_share",
    "srv_window_blocks_share", "srv_window_blocks_freed_per_step",
    "srv_active_experts_per_layer", "moe_expert_load_peak_over_mean",
    "srv_decode_step_roofline_pct"]
NEW = ["srv_experts_chunk_roofline_pct", "scope_window_attn_pct"]


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = _load(os.path.join(HERE, "configs", NAME + ".json"))
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}


def test_the_entry_and_the_file_agree():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for key in ("reduced_why", "published", "deployment", "assumed",
                "memory", "check"):
        assert CONFIG[key]
    # the twelfth configuration and the fifteenth cell, each the last
    assert [len(BENCH["configs"]), len(BENCH["workloads"])] == [12, 15]
    assert BENCH["configs"][-1] is entry and BENCH["workloads"][-1] is cell
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    for key in ("layer_types", "mlp_layer_types"):
        assert CONFIG[key] == row["config"][key][:8]  # two whole periods
    assert CONFIG["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 2
    assert CONFIG["published"]["num_hidden_layers"] == 28
    # no width, expert, head or vocabulary row is cut; both rotation blocks
    # letter for letter
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "sliding_window", "intermediate_size",
                "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                "vocab_size", "rope_parameters", "max_position_embeddings"):
        assert CONFIG[key] == row["config"][key], key
    # what the readers need and the source does not have is said
    said = " ".join(CONFIG["assumed"])
    for key in ("first_k_dense_replace", "num_shared_experts",
                "router_outputs"):
        assert key not in row["config"] and key in said, key


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert run["kv_bytes_per_element"] == 2
    assert (run["driver"], CONFIG["reference"]) == ("serve_dots", "mellum")
    assert run["serve_flags"]["slots"] == 16
    assert run["serve_flags"]["prompt_pad"] == 1024
    assert run["serve_flags"]["max_len"] in (25600, 17408)  # ISSUE 62's rule
    assert max(CONFIG["check"]["prompt_lens"]) > 8192  # past YaRN's original


def test_the_program_serves_the_files_widths_and_both_tables():
    from dnn_tpu.registry import get_model

    cfg = get_model(CONFIG["run"]["model"]).config
    assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
            cfg.n_kv_head, cfg.head_dim, cfg.block_size) == tuple(
        CONFIG[k] for k in (
            "hidden_size", "num_hidden_layers", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "max_position_embeddings"))
    assert cfg.layer_types == tuple(
        t.split("_")[0].replace("sliding", "window")
        for t in CONFIG["layer_types"])
    assert cfg.kv_window.window == CONFIG["sliding_window"]
    assert cfg.kv_full.window is None
    sliding = CONFIG["rope_parameters"]["sliding_attention"]
    full = CONFIG["rope_parameters"]["full_attention"]
    rot = cfg.kv_window.rotation
    assert (rot.scaling, rot.theta, rot.scale) == (
        None, sliding["rope_theta"], 1.0) and sliding["rope_type"] == "default"
    rot = cfg.kv_full.rotation
    assert (rot.scaling, rot.theta, rot.scale, rot.original_len,
            rot.beta_fast, rot.beta_slow, rot.attention_factor,
            rot.truncate) == (
        full["rope_type"], full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"], True)
    assert cfg.first_k_dense == CONFIG["first_k_dense_replace"] == \
        CONFIG["mlp_layer_types"].count("dense") == 0
    assert (cfg.experts_held, cfg.n_expert, cfg.router_top_k, cfg.d_ff,
            cfg.d_shared, cfg.rms_eps, cfg.router.scoring) == (
        None, CONFIG["num_experts"], CONFIG["num_experts_per_tok"],
        CONFIG["moe_intermediate_size"], None, CONFIG["rms_norm_eps"],
        "softmax")
    assert CONFIG["published"]["router_outputs"] == CONFIG["num_experts"]
    assert CONFIG["num_shared_experts"] == 0
    assert cfg.router_norm_topk == CONFIG["norm_topk_prob"]
    assert cfg.qk_norm and cfg.qk_norm_width == "head"
    assert not cfg.tie_word_embeddings and not CONFIG["tie_word_embeddings"]


def test_the_rooflines_widths_are_the_issues_counts():
    """ISSUE 62's arithmetic through K-EXAONE's reader: attention 21.23 M a
    layer, an expert 6.19 M, the router 0.15 M, no dense layer and no shared
    expert — `intermediate_size` 7168 is priced at zero layers."""
    x = exaone_roofline._widths(CONFIG)
    assert x["attn_params"] == 2 * 2304 * 4096 + 2 * 2304 * 512 == 21_233_664
    assert x["expert_params"] == 3 * 2304 * 896 == 6_193_152
    assert x["shared_params"] == 0 and x["dense_layers"] == 0
    assert x["router_params"] == 2304 * 64
    assert x["head_params"] == 98304 * 2304
    assert x["layers"] == {"full": 2, "window": 6}
    assert x["expert_layers"] == 8
    assert x["row_bytes"] == 2048 and x["pair_flops"] == 32 * 4 * 128
    layer = x["attn_params"] + 64 * x["expert_params"] + x["router_params"]
    assert round(layer / 1e6, 1) == 417.7                  # 0.835 GB a layer
    held = 2 * (8 * (layer - x["router_params"]) + x["head_params"]) \
        + 4 * (8 * x["router_params"] + x["head_params"])
    assert round(held / 1e9, 2) == 8.05  # ISSUE 62: 6.68 + 1.36 = 8.04
    whole = 28 * layer + 2 * x["head_params"]
    assert round(whole / 1e9, 2) == 12.15                  # the name's 12 B
    active = 28 * (x["attn_params"] + 8 * x["expert_params"]
                   + x["router_params"]) + 2 * x["head_params"]
    assert round(active / 1e9, 1) == 2.4                   # the name's A2.5B


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    assert step_roofline.roofline_module(cell) == "exaone_roofline"
    assert scopes.known_scopes(cell) == KNOWN  # K-EXAONE's less moe.shared
    # it reads everything K-EXAONE's cell reads but the shared expert's share
    kx = set(cells.resolve(KX)["per_layer"])
    mine = set(cell["per_layer"])
    assert kx - mine == {"scope_shared_pct"}
    assert mine - kx == {"moe_expert_load_peak_over_mean",
                         "scope_window_attn_pct"}
    for name in BY_KIND:
        assert ENTRIES[name]["workloads"][-1] == CELL, name
        assert KX in ENTRIES[name]["workloads"] or name == \
            "moe_expert_load_peak_over_mean"
    # NOT the expert roofline that takes the expert's width from
    # `intermediate_size` (here the unused dense 7168)
    assert CELL not in ENTRIES["moe_experts_roofline_pct"]["workloads"]
    assert CONFIG["intermediate_size"] != CONFIG["moe_intermediate_size"]
    # each declared prefix goes to exactly one share entry and one entry
    # takes the operations with no scope: those shares add up to 100.
    # `scope_window_attn_pct` is a PART of `scope_attn_pct` (the window
    # kind's reads), not a term of the sum
    shares = {n: args["scopes"] for n, (fn, args) in
              cell["per_layer"].items() if fn is scopes.share_pct}
    part = shares.pop("scope_window_attn_pct")
    assert part == ["attn.window_prefill", "attn.window_decode"]
    assert all(p.startswith("attn.") for p in part)
    assert list(shares.values()).count(None) == 1
    given = [p for s in shares.values() if s is not None for p in s]
    assert sorted(given) == sorted(KNOWN)


def test_the_new_entries_name_readers_that_were_there():
    assert len(BENCH["per_layer"]) == 123  # 121 + 2 of the 128
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == NEW
    chunk = ENTRIES["srv_experts_chunk_roofline_pct"]
    assert chunk["workloads"] == [KX, CELL]
    assert (chunk["layer"], chunk["moves"], chunk["unit"]) == (
        "Experts", "out_tok_s", "%")
    assert _load(os.path.join(
        HERE, "layers", "srv_experts_chunk_roofline_pct.json")) == {
        "reducer": "exaone_roofline:experts_roofline_pct",
        "args": {"program": "jit_prefill_chunk",
                 "inside": "jit(prefill_chunk)/", "label": "prefill",
                 "scope": "moe.experts"}}
    # K-EXAONE's cell is NOT listed: `test_configs.py` (not this PR's to
    # edit) holds that cell's `scopes:share_pct` entries to a partition
    window = ENTRIES["scope_window_attn_pct"]
    assert window["workloads"] == [CELL]
    assert (window["layer"], window["moves"], window["unit"]) == (
        "Kernels", "out_tok_s", "%")


def test_a_program_without_the_counters_reads_nothing():
    """On the parent (no such model: the daemon does not boot) or on any run
    without counters and capture, every reader the cell lists returns None
    and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name in BY_KIND or name in NEW:
            assert fn(facts, **args) is None, name


def _facts(steps=100, slots=16, live=14000, active=55):
    m1 = {
        "step_steps_total": steps,
        "step_tokens_advanced_total": steps * slots,
        'moe_layer_calls_total{program="decode"}': steps * 8,
        'moe_active_experts_total{program="decode"}': steps * 8 * active,
        'moe_assignments_total{program="decode"}': steps * 8 * slots * 8,
        'attn_cached_positions_read_total{kind="full",program="decode"}':
            steps * 2 * slots * live,
        'attn_cached_positions_read_total{kind="window",program="decode"}':
            steps * 6 * slots * 1024,
        'moe_layer_calls_total{program="prefill"}': 40 * 8,
        'moe_active_experts_total{program="prefill"}': 40 * 8 * 64,
        'moe_assignments_total{program="prefill"}': 40 * 8 * 1024 * 8}
    return {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
            "metrics1": m1, "client": {},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "trace": {"programs": {
                "jit_decode_step": {"count": 10, "mean_ms": 12.0},
                "jit_prefill_chunk": {"count": 4, "mean_ms": 20.0}}}}


def test_the_step_is_priced_from_the_counters():
    """ISSUE 62's step: 16 rows, ~55 of 64 experts active a layer, ~14 k
    live positions a slot: 6.25 GB of weights (ISSUE 62: 6.3), 0.9 GB of
    full-kind K/V, 0.2 GB of windows; the least time is its bytes over the
    peak."""
    facts = _facts()
    step = exaone_roofline._step(facts)
    assert round(step["weight_bytes"] / 1e9, 2) == 6.25
    assert step["full_bytes"] == 2 * 16 * 14000 * 2048
    assert step["cache_bytes"] == step["full_bytes"] + 6 * 16 * 1024 * 2048
    assert round(step["full_bytes"] / 1e9, 2) == 0.92
    share = exaone_roofline.full_cache_read_share(facts)
    assert share == pytest.approx(
        step["full_bytes"] / (step["weight_bytes"] + step["cache_bytes"]))
    pct = exaone_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step")
    least_ms = 1e3 * (step["weight_bytes"] + step["cache_bytes"]) / 819e9
    assert 8.5 < least_ms < 9.5
    assert pct == pytest.approx(100 * least_ms / 12.0)
    assert facts["notes"][-1]["bound"] == "bandwidth" and 0 < pct < 100


def test_a_chunks_experts_are_priced_at_the_experts_width():
    """A 1024-token chunk gives each of the 64 experts ~128 rows (128 FLOPs a
    byte, under the chip's 240): the experts' least time a chunk is the 6.34
    GB they stream and the rows in and out (bandwidth), priced at
    `moe_intermediate_size` 896 and not at the unused dense 7168."""
    least = scopes.experts_least_s(
        {**CONFIG, "intermediate_size": CONFIG["moe_intermediate_size"]},
        rows=8 * 1024 * 8, active_experts=8 * 64,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "bandwidth"
    assert round(least["bytes"] / 1e9, 2) == 6.95  # 6.34 GB + the rows
    assert 8e-3 < least["least_s"] < 9e-3


def test_the_traffic_is_the_issues_ranges():
    """ISSUE 62's rule (the file's `ranges_why` says which ranges stand and
    what the three runs read)."""
    t = _load(os.path.join(HERE, "traffic", TRAFFIC + ".json"))
    max_len = CONFIG["run"]["serve_flags"]["max_len"]
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    first = max_len == 25600
    assert t["prompt_len"]["knots"] == (
        [[0.0, 8192], [0.5, 12288], [1.0, 24576]] if first else
        [[0.0, 8192], [0.5, 11264], [1.0, 16384]])
    assert t["prompt_len"]["scale"] == t["output_len"]["scale"] == "log"
    assert t["output_len"]["knots"] == [[0.0, 128], [0.5, 256], [1.0, 512]]
    assert (t["max_total"], t["outstanding"], t["strata"], t["group"],
            t["layout_seed"], t["anchor_index"], t["requests"]) == (
        25088 if first else 16896, 32, 16, 4, 62, 15, 4000)
    assert t["max_total"] <= max_len
    assert t["outstanding"] == 2 * CONFIG["run"]["serve_flags"]["slots"]
    assert t["reports"] == {"out_tok_s": "tok_s"} and t["ranges_why"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    top = t["prompt_len"]["knots"][-1][1]
    assert all(8192 <= r.prompt_len <= top and 128 <= r.max_new <= 512
               and r.prompt_len + r.max_new <= t["max_total"] for r in a)
    assert max(int(r.prompt.max()) for r in a[:200]) < CONFIG["vocab_size"]
    # every context is past YaRN's original positions and 8 windows deep
    full = CONFIG["rope_parameters"]["full_attention"]
    assert min(r.prompt_len for r in a) >= \
        full["original_max_position_embeddings"] == 8 * CONFIG[
            "sliding_window"]
