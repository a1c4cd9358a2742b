"""The dots3 configuration, its traffic and its per-layer files as cases
of what `test_configs.py` and `test_traffic.py` hold every configuration
and backlog to (a PR that adds a configuration adds files here and edits
none: those two files' literal tables wait for a `benchmark` PR), and the
configuration's own: the catalog row, the operations and bytes its
rooflines are priced at."""

import json
import os

import pytest

from chipbench import cells, dots3_roofline, scopes
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "dots3-note-prev-ep8-1chip", "dots3-longnote-saturated"
TRAFFIC = "longnote-backlog-16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = _load(os.path.join(HERE, "configs", NAME + ".json"))


def test_the_entry_and_the_file_agree():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "layer_types", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:5]
    for key in ("n_routed_experts", "num_hidden_layers", "vocab_size"):
        assert CONFIG["published"][key] == row["config"][key]


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert run["kv_bytes_per_element"] == 2


def test_the_program_serves_the_files_widths():
    from dnn_tpu.registry import get_model

    cfg = get_model(CONFIG["run"]["model"]).config
    full, win = cfg.mla, cfg.mla_window
    assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size) == (
        CONFIG["hidden_size"], CONFIG["num_hidden_layers"],
        CONFIG["vocab_size"])
    assert cfg.layer_types == tuple(
        t.split("_")[0].replace("sliding", "window")
        for t in CONFIG["layer_types"])
    assert (cfg.n_head, full.q_lora_rank, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim) \
        == tuple(CONFIG[k] for k in (
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    assert (win.n_head, win.q_lora_rank, win.kv_lora_rank,
            win.qk_nope_head_dim, win.qk_rope_head_dim, win.v_head_dim,
            win.window, win.rope_theta) == tuple(CONFIG[k] for k in (
                "swa_num_attention_heads", "swa_q_lora_rank",
                "swa_kv_lora_rank", "swa_qk_nope_head_dim",
                "swa_qk_rope_head_dim", "swa_v_head_dim",
                "sliding_window_size", "swa_rope_theta"))
    assert (full.index_topk, full.index_n_head, full.index_head_dim) == (
        CONFIG["index_topk"], CONFIG["index_n_heads"],
        CONFIG["index_head_dim"])
    assert (cfg.experts_held, cfg.n_expert, cfg.router_top_k, cfg.d_ff,
            cfg.d_ff_dense, cfg.rope_theta, cfg.rms_eps) == (
        CONFIG["n_routed_experts"], CONFIG["published"]["router_outputs"],
        CONFIG["num_experts_per_tok"], CONFIG["moe_intermediate_size"],
        CONFIG["intermediate_size"], CONFIG["rope_theta"],
        CONFIG["rms_norm_eps"])


def test_the_rooflines_widths_are_the_issues_counts():
    x = dots3_roofline._widths(CONFIG)
    assert x["full"]["attn_params"] + x["index_params"] == 144_048_128
    assert x["window"]["attn_params"] == 90_832_896
    assert (x["full"]["layers"], x["window"]["layers"]) == (2, 3)
    assert x["full"]["row_bytes"] == 1152 and x["window"]["row_bytes"] == 2176
    assert x["index_key_bytes"] == 256 and x["score_flops"] == 16384
    assert x["full"]["decode_flops"] == 128 * 2 * (576 + 512)
    assert x["window"]["pair_flops"] == 64 * 2 * (256 + 128)
    assert x["expert_params"] == 23_592_960


#: what PR 41 brought under `dots_` and PR 56 gave the names of the readers
SHARED = ["srv_decode_step_roofline_pct", "scope_index_pct",
          "scope_select_pct", "scope_mla_project_pct", "scope_mla_absorb_pct",
          "scope_mla_up_project_pct", "scope_shared_pct",
          "srv_selected_share", "srv_window_blocks_share",
          "srv_window_blocks_freed_per_step"]


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert len(cell["per_layer"]) >= 49  # 42 at PR 41, 49 since PR 52
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(CONFIG["trace"]["known_scopes"])
    # sixteen at PR 41; since PR 56 the seven a second cell could read are
    # shared entries with no model's prefix and the whole step's share is
    # the shared entry's, its reader named by `trace.roofline`
    dots = [m for m in BENCH["per_layer"] if m["name"].startswith("dots_")]
    assert [m["name"] for m in dots] == [
        "dots_sparse_decode_roofline_pct", "dots_window_decode_roofline_pct",
        "dots_sparse_prefill_roofline_pct",
        "dots_window_prefill_roofline_pct", "dots_index_roofline_pct",
        "dots_scope_gate_pct"]
    for m in dots:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(HERE, "layers",
                                           m["name"] + ".json"))
    assert CONFIG["trace"]["roofline"] == "dots3_roofline"
    for name in SHARED:
        assert CELL in next(m for m in BENCH["per_layer"]
                            if m["name"] == name)["workloads"], name


def test_a_program_without_the_counters_reads_nothing():
    """The parent has no `mla_cached_positions_read_total` and no
    `kv_pool_*` series: every new reader returns None and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name.startswith("dots_") or name in SHARED:
            assert fn(facts, **args) is None, name


def test_the_traffic_is_the_issues_second_ranges():
    """ISSUE 41's rule turned to its fallback (the file's `ranges_why`)."""
    t = _load(os.path.join(HERE, "traffic", TRAFFIC + ".json"))
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    assert t["prompt_len"]["knots"] == [[0.0, 4096], [0.5, 8192],
                                        [1.0, 12288]]
    assert t["output_len"]["knots"] == [[0.0, 64], [0.5, 128], [1.0, 256]]
    assert (t["max_total"], t["outstanding"], t["strata"], t["group"],
            t["layout_seed"], t["anchor_index"], t["requests"]) == (
        12544, 64, 16, 4, 41, 31, 4000)
    assert t["max_total"] <= CONFIG["run"]["serve_flags"]["max_len"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    assert all(4096 <= r.prompt_len <= 12288 and 64 <= r.max_new <= 256
               and r.prompt_len + r.max_new <= 12544 for r in a)
    assert max(int(r.prompt.max()) for r in a[:200]) < CONFIG["vocab_size"]
