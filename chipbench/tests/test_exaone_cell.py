"""The K-EXAONE configuration, its traffic and its per-layer files as cases
of what `test_configs.py` and `test_traffic.py` hold every configuration
and backlog to (a PR that adds a configuration adds files here and edits
none: those two files' literal tables wait for a `benchmark` PR), and the
configuration's own: the catalog row, the operations and bytes its
rooflines are priced at."""

import json
import os

import pytest

from chipbench import cells, exaone_roofline, scopes
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "k-exaone-236b-a23b-ep8-1chip", "kexaone-reasoning-saturated"
TRAFFIC = "reasoning-backlog-6k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["layer_types", "mlp_layer_types", "num_experts",
           "num_hidden_layers", "sliding_windows", "vocab_size"]


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = _load(os.path.join(HERE, "configs", NAME + ".json"))


def test_the_entry_and_the_file_agree():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for key in ("published", "deployment", "assumed", "memory", "check"):
        assert CONFIG[key]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert CONFIG[key] == row["config"][key][:5]
    for key in ("num_experts", "num_hidden_layers", "vocab_size"):
        assert CONFIG["published"][key] == row["config"][key]
    # no width is reduced
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "sliding_window", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor"):
        assert CONFIG[key] == row["config"][key], key


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert run["kv_bytes_per_element"] == 2
    assert (run["driver"], CONFIG["reference"]) == ("serve_dots", "exaone")
    assert run["serve_flags"] == {"slots": 64, "max_len": 6144,
                                  "prompt_pad": 1024}


def test_the_program_serves_the_files_widths():
    from dnn_tpu.registry import get_model

    cfg = get_model(CONFIG["run"]["model"]).config
    assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
            cfg.n_kv_head, cfg.head_dim) == tuple(CONFIG[k] for k in (
                "hidden_size", "num_hidden_layers", "vocab_size",
                "num_attention_heads", "num_key_value_heads", "head_dim"))
    assert cfg.layer_types == tuple(
        t.split("_")[0].replace("sliding", "window")
        for t in CONFIG["layer_types"])
    assert cfg.kv_window.window == CONFIG["sliding_window"]
    assert [cfg.kv_window.window if t == "window" else 0
            for t in cfg.layer_types] == CONFIG["sliding_windows"]
    assert cfg.kv_window.rope and not cfg.kv_full.rope
    assert cfg.first_k_dense == CONFIG["first_k_dense_replace"] == \
        CONFIG["mlp_layer_types"].count("dense")
    assert (cfg.experts_held, cfg.n_expert, cfg.router_top_k, cfg.d_ff,
            cfg.d_ff_dense, cfg.d_shared, cfg.rope_theta, cfg.rms_eps,
            cfg.router.scale, cfg.router.scoring) == (
        CONFIG["num_experts"], CONFIG["published"]["router_outputs"],
        CONFIG["num_experts_per_tok"], CONFIG["moe_intermediate_size"],
        CONFIG["intermediate_size"],
        CONFIG["num_shared_experts"] * CONFIG["moe_intermediate_size"],
        CONFIG["rope_parameters"]["rope_theta"], CONFIG["rms_norm_eps"],
        CONFIG["routed_scaling_factor"], CONFIG["scoring_func"])
    assert cfg.router_norm_topk == CONFIG["norm_topk_prob"]
    assert not cfg.tie_word_embeddings and not CONFIG["tie_word_embeddings"]


def test_the_rooflines_widths_are_the_issues_counts():
    x = exaone_roofline._widths(CONFIG)
    assert x["attn_params"] == 113_246_208          # 113.25 M
    assert x["expert_params"] == x["shared_params"] == 37_748_736
    assert x["dense_params"] == 339_738_624
    assert x["router_params"] == 6144 * 128
    assert x["head_params"] == 19200 * 6144
    assert x["layers"] == {"full": 1, "window": 4}
    assert (x["dense_layers"], x["expert_layers"]) == (1, 4)
    assert x["row_bytes"] == 4096 and x["pair_flops"] == 64 * 4 * 128
    # ISSUE 43's arithmetic: layer 0 0.906 GB, an expert layer 1.513 GB
    assert 2 * (x["attn_params"] + x["dense_params"]) == 905_969_664
    layer = 2 * (x["attn_params"] + 17 * x["expert_params"]) \
        + 4 * x["router_params"]
    assert round(layer / 1e9, 3) == 1.513


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert len(cell["per_layer"]) >= 45  # 38 at PR 43, 45 since PR 52
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(CONFIG["trace"]["known_scopes"])
    # ten at PR 43; since PR 56 the whole step's share is the shared
    # entry's (its reader named by `trace.roofline`) and `moe.shared`'s
    # share is `scope_shared_pct`
    kx = [m for m in BENCH["per_layer"] if m["name"].startswith("kx_")]
    assert [m["name"] for m in kx] == [
        "kx_full_decode_roofline_pct", "kx_window_decode_roofline_pct",
        "kx_full_prefill_roofline_pct", "kx_window_prefill_roofline_pct",
        "kx_experts_roofline_pct", "kx_window_roll_ms_per_step",
        "kx_table_flushes_per_step", "kx_full_cache_read_share"]
    for m in kx:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(HERE, "layers",
                                           m["name"] + ".json"))
    assert CONFIG["trace"]["roofline"] == "exaone_roofline"
    # it JOINS the two entries whose readers read counters any pool with a
    # window kind writes, and not the expert roofline that divides by every
    # layer
    for m in BENCH["per_layer"]:
        if m["name"] in ("srv_window_blocks_share",
                         "srv_window_blocks_freed_per_step"):
            assert m["workloads"][:2] == ["dots3-longnote-saturated", CELL]
        if m["name"] in ("srv_decode_step_roofline_pct",
                         "scope_shared_pct"):
            assert CELL in m["workloads"]
        if m["name"] == "moe_experts_roofline_pct":
            assert CELL not in m["workloads"]


def test_a_program_without_the_counters_reads_nothing():
    """The parent has no `attn_cached_positions_read_total`, no
    `kv_pool_window_table_flushes_total` and no `step.commit.window` span:
    every new reader returns None and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name.startswith("kx_") or name in (
                "srv_decode_step_roofline_pct", "srv_window_blocks_share",
                "srv_window_blocks_freed_per_step"):
            assert fn(facts, **args) is None, name


def test_the_step_is_priced_from_the_counters():
    """A window of 100 steps at 64 rows, every held expert active, 2600
    live positions a slot: the step's least time is its bytes over the
    peak, the experts' stream first."""
    steps, slots, live = 100, 64, 2600
    m1 = {
        "step_steps_total": steps,
        "step_tokens_advanced_total": steps * slots,
        'moe_layer_calls_total{program="decode"}': steps * 4,
        'moe_active_experts_total{program="decode"}': steps * 4 * 16,
        'moe_assignments_total{program="decode"}': steps * 4 * 64,
        'attn_cached_positions_read_total{kind="full",program="decode"}':
            steps * slots * live,
        'attn_cached_positions_read_total{kind="window",program="decode"}':
            steps * 4 * slots * 128}
    facts = {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
             "metrics1": m1, "client": {},
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
             "trace": {"programs": {"jit_decode_step": {
                 "count": 10, "mean_ms": 16.0}}}}
    step = exaone_roofline._step(facts)
    assert round(step["weight_bytes"] / 1e9, 2) == 7.19  # ISSUE 43: ~7.2
    assert step["full_bytes"] == slots * live * 4096
    assert step["cache_bytes"] == step["full_bytes"] + 4 * slots * 128 * 4096
    share = exaone_roofline.full_cache_read_share(facts)
    assert share == pytest.approx(
        step["full_bytes"] / (step["weight_bytes"] + step["cache_bytes"]))
    pct = exaone_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step")
    least_ms = 1e3 * (step["weight_bytes"] + step["cache_bytes"]) / 819e9
    assert pct == pytest.approx(100 * least_ms / 16.0)
    note = facts["notes"][-1]
    assert note["bound"] == "bandwidth" and 0 < pct < 100


def test_the_traffic_is_the_issues_second_ranges():
    """ISSUE 43's rule turned to its fallback for the outputs (the file's
    `ranges_why`)."""
    t = _load(os.path.join(HERE, "traffic", TRAFFIC + ".json"))
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    assert t["prompt_len"]["knots"] == [[0.0, 512], [0.5, 1536], [1.0, 4096]]
    assert t["output_len"]["knots"] == [[0.0, 256], [0.5, 512], [1.0, 1024]]
    assert (t["max_total"], t["outstanding"], t["strata"], t["group"],
            t["layout_seed"], t["anchor_index"], t["requests"]) == (
        5632, 128, 16, 4, 43, 63, 4000)
    assert t["max_total"] <= CONFIG["run"]["serve_flags"]["max_len"]
    assert t["outstanding"] == 2 * CONFIG["run"]["serve_flags"]["slots"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    assert all(512 <= r.prompt_len <= 4096 and 256 <= r.max_new <= 1024
               and r.prompt_len + r.max_new <= 5632 for r in a)
    assert max(int(r.prompt.max()) for r in a[:200]) < CONFIG["vocab_size"]
    # every context is at least four times the window
    assert min(r.prompt_len for r in a) >= 4 * CONFIG["sliding_window"]
