"""The Nemotron-3-Super configuration, its traffic and its per-layer files as
cases of what `test_configs.py` and `test_traffic.py` hold every
configuration and backlog to (a PR that adds a configuration adds files here
and edits none), and the configuration's own: the catalog row, the program's
preset against the file's widths, the operations and bytes its four `nem_*`
entries and the whole step are priced at (`nemotron_roofline.py`)."""

import json
import os

import pytest

from chipbench import cells, nemotron_roofline, scopes, step_roofline
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME = "nemotron-3-super-120b-a12b-ep4-1chip"
CELL, TRAFFIC = "nemotron3-agent-saturated", "agent-backlog-8k"
SOLAR = "solar2-longchat-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["hybrid_override_pattern", "n_routed_experts",
           "num_hidden_layers", "vocab_size"]
KNOWN = ["moe.experts", "moe.route", "moe.combine", "moe.shared",
         "moe.latent_down", "moe.latent_up", "ssm.", "state_pool.", "attn.",
         "kv_pool.", "llama.", "sample", "layers.scan"]
NEW = ["nem_latent_experts_roofline_pct",
       "nem_latent_experts_chunk_roofline_pct", "nem_ssm_step_roofline_pct",
       "nem_scope_latent_pct"]
#: shared entries whose readers take widths from keys this source lacks, or
#: read a kind this model has none of: NOT joined
LEFT_OUT = [
    "moe_expert_load_peak_over_mean", "moe_experts_roofline_pct",
    "kx_experts_roofline_pct", "srv_experts_chunk_roofline_pct",
    "fh1_ssm_step_roofline_pct", "fh1_ssm_chunk_roofline_pct",
    "kx_full_decode_roofline_pct", "kx_full_prefill_roofline_pct",
    "kx_full_cache_read_share", "srv_window_blocks_share",
    "srv_window_blocks_freed_per_step", "sol_full_decode_roofline_pct"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
    assert "quarter of a deployment's rows" in cell["why"]
    for key in ("reduced_why", "published", "deployment", "assumed",
                "memory", "check"):
        assert CONFIG[key]
    # the thirteenth configuration and the sixteenth cell
    assert BENCH["configs"].index(entry) == 12
    assert BENCH["workloads"].index(cell) == 15
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "out_tok_s")["workloads"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    pattern = row["config"]["hybrid_override_pattern"]
    assert CONFIG["hybrid_override_pattern"] == pattern[:11] == "MEMEMEM*EME"
    assert [pattern.count(k) for k in "M*E"] == [40, 8, 40]
    assert CONFIG["published"]["hybrid_override_pattern"] == pattern
    assert CONFIG["published"]["num_hidden_layers"] == 88
    assert CONFIG["published"]["router_outputs"] == 512 == \
        row["config"]["n_routed_experts"]
    assert CONFIG["published"]["vocab_size"] == 131072 == 4 * CONFIG[
        "vocab_size"]
    # no width is cut
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel", "expand",
                "moe_latent_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "intermediate_size"):
        assert CONFIG[key] == row["config"][key], key


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert run["kv_bytes_per_element"] == 2
    assert (run["driver"], CONFIG["reference"]) == ("serve_dots",
                                                    "nemotron_h")
    flags = run["serve_flags"]
    assert flags["slots"] in (64, 48) and flags["prompt_pad"] == 1024
    assert flags["max_len"] in (10240, 6144)  # ISSUE 66's rules (i), (ii)
    lens = CONFIG["check"]["prompt_lens"]
    # inside a chunk, at its edge, one past it, three and eight chunks
    assert lens[:3] == [700, 1024, 1025] and 2048 < lens[3] <= 3072
    assert len(lens) == 5 and CONFIG["check"]["window_streams"] == 4


def test_the_program_serves_the_files_widths():
    from dnn_tpu.models.llama_moe import pattern_types
    from dnn_tpu.registry import get_model

    cfg = get_model(CONFIG["run"]["model"]).config
    assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
            cfg.n_kv_head, cfg.head_dim, cfg.block_size) == tuple(
        CONFIG[k] for k in (
            "hidden_size", "num_hidden_layers", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "max_position_embeddings"))
    assert cfg.layer_types == pattern_types(CONFIG["hybrid_override_pattern"])
    assert cfg.one_mixer and not cfg.kv_full.rope
    m = cfg.mamba
    assert (m.n_head, m.head_dim, m.n_groups, m.d_state, m.conv, m.chunk,
            m.d_ssm, m.beside) == (
        CONFIG["mamba_num_heads"], CONFIG["mamba_head_dim"],
        CONFIG["n_groups"], CONFIG["ssm_state_size"], CONFIG["conv_kernel"],
        CONFIG["chunk_size"], CONFIG["expand"] * CONFIG["hidden_size"],
        False)
    assert (cfg.held, cfg.n_expert, cfg.router_top_k, cfg.d_ff,
            cfg.moe_latent, cfg.d_shared, cfg.rms_eps, cfg.router.scoring,
            cfg.router.scale, cfg.router.select_bias, cfg.mlp_act,
            cfg.expert_gated, cfg.shared_gate, cfg.router_norm_topk) == (
        (0, CONFIG["n_routed_experts"]),
        CONFIG["published"]["router_outputs"], CONFIG["num_experts_per_tok"],
        CONFIG["moe_intermediate_size"], CONFIG["moe_latent_size"],
        CONFIG["moe_shared_expert_intermediate_size"],
        CONFIG["layer_norm_epsilon"], "sigmoid",
        CONFIG["routed_scaling_factor"], True, CONFIG["mlp_hidden_act"],
        False, False, CONFIG["norm_topk_prob"])
    assert not cfg.tie_word_embeddings and not CONFIG["tie_word_embeddings"]
    assert not cfg.attn_bias and not CONFIG["attention_bias"]


def test_the_rooflines_widths_are_the_issues_counts():
    """ISSUE 66's arithmetic: an M block 109.6 M, a * block 35.65 M, an E
    block beside its routed experts 54.5 M, an expert 5.505 M (11.0 MB), a
    slot's state 4.19 MB a layer, K and V 1024 B a position; the cut holds
    9.56 GB."""
    x = nemotron_roofline.widths(CONFIG)
    assert x["blocks"] == {"M": 5, "*": 1, "E": 5}
    assert round(x["ssm_params"] / 1e6, 1) == 109.6
    assert x["attn_params"] == 2 * 4096 * 4096 + 2 * 4096 * 256 == 35_651_584
    assert x["dense_e_params"] == 4096 * 512 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376 == 54_525_952
    assert x["expert_params"] == 2 * 1024 * 2688 == 5_505_024
    assert x["expert_params"] * x["w"] == 11_010_048
    assert x["expert_row_bytes"] == (1024 + 2688 + 1024) * 2
    assert x["expert_row_flops"] == 4 * 1024 * 2688
    assert x["state_bytes"] == 4_194_304 and x["row_bytes"] == 1024
    assert x["head_params"] == 32768 * 4096
    held = 2 * (5 * x["ssm_params"] + x["attn_params"] + 5 * (
        x["dense_e_params"] + 128 * x["expert_params"]) + x["head_params"]) \
        + 4 * x["head_params"]  # wte float32
    assert round(held / 1e9, 2) == 9.56  # ISSUE 66: 9.58 of rounded parts


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    assert step_roofline.roofline_module(cell) == "nemotron_roofline"
    assert scopes.known_scopes(cell) == KNOWN
    mine = set(cell["per_layer"])
    # everything Solar's cell reads that is not Solar's own (a state kind
    # beside a K/V kind, held experts, a shared expert), Falcon-H1's `ssm.`
    # share, and the four new entries
    solar = {n for n in cells.resolve(SOLAR)["per_layer"]
             if not n.startswith("sol_")}
    assert mine == solar | {"fh1_scope_ssm_pct"} | set(NEW)
    for name in mine:
        assert ENTRIES[name]["workloads"][-1] == CELL, name
    for name in LEFT_OUT:
        assert CELL not in ENTRIES[name]["workloads"], name
    # the source has no `num_experts` for the load's peak over mean, and
    # none of its two-matrix latent experts is `3 x hidden x width`
    assert "num_experts" not in CONFIG and "mamba_d_ssm" not in CONFIG
    # each declared prefix goes to exactly one share entry and one entry
    # takes the operations with no scope: the shares add up to 100
    shares = {n: args["scopes"] for n, (fn, args) in
              cell["per_layer"].items() if fn is scopes.share_pct}
    assert list(shares.values()).count(None) == 1
    given = [p for s in shares.values() if s is not None for p in s]
    assert sorted(given) == sorted(KNOWN)
    assert shares["nem_scope_latent_pct"] == ["moe.latent_down",
                                              "moe.latent_up"]


def test_the_new_entries_are_four_and_the_list_stands_at_127():
    assert len(BENCH["per_layer"]) == 127  # 123 + 4 of the 128
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == NEW
    for name in NEW:
        m = ENTRIES[name]
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["unit"], m["better"], m["source"]) == (
            "out_tok_s", "%", "higher", "device_trace")
        assert m["layer"] == ("Kernels" if "ssm" in name else "Experts")
        spec = _load(os.path.join(HERE, "layers", name + ".json"))
        assert spec["reducer"].startswith(
            "scopes:" if "scope" in name else "nemotron_roofline:")
    args = [_load(os.path.join(HERE, "layers", n + ".json"))["args"]
            for n in NEW[:2]]
    assert [(a["program"], a["label"]) for a in args] == [
        ("jit_decode_step", "decode"), ("jit_prefill_chunk", "prefill")]
    assert all(a["scope"] == "moe.experts" for a in args)


def test_a_program_without_the_counters_reads_nothing():
    """On the parent (no such model: the daemon does not boot) or on any run
    without counters and capture, every reader the cell lists returns None
    and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name in NEW or "roofline" in name:
            assert fn(facts, **args) is None, name


def _facts(steps=100, slots=64, live=3500, active=120, chunks=40):
    m1 = {
        "step_steps_total": steps,
        "step_tokens_advanced_total": steps * slots,
        'moe_layer_calls_total{program="decode"}': steps * 5,
        'moe_active_experts_total{program="decode"}': steps * 5 * active,
        'moe_assignments_total{program="decode"}': steps * 5 * slots * 5.5,
        "state_pool_bytes_read_total": steps * 5 * slots * 4_255_744,
        "state_pool_bytes_written_total": steps * 5 * slots * 4_255_744,
        "state_pool_kv_bytes_read_total": steps * slots * live * 1024,
        'moe_layer_calls_total{program="prefill"}': chunks * 5,
        'moe_active_experts_total{program="prefill"}': chunks * 5 * 128,
        'moe_assignments_total{program="prefill"}': chunks * 5 * 5632}
    return {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
            "metrics1": m1, "client": {}, "peaks": PEAKS,
            "trace": {"programs": {
                "jit_decode_step": {"count": 10, "mean_ms": 18.0},
                "jit_prefill_chunk": {"count": 4, "mean_ms": 28.0}}}}


def test_the_experts_are_priced_from_the_counters():
    """ISSUE 66's step: 352 held picks over ~120 active experts a layer: 5 x
    120 x 11.0 MB = 6.6 GB, bandwidth-bound, 8.1 ms; a chunk's 5632 held
    rows a layer over all 128: 7.05 GB + the rows 0.27, 8.9 ms."""
    facts = _facts()
    step = nemotron_roofline._experts(facts, "decode")
    assert step == {"rows": 5 * 64 * 5.5, "active": 5 * 120}
    x = nemotron_roofline.widths(CONFIG)
    nbytes = step["active"] * x["expert_params"] * 2 \
        + step["rows"] * x["expert_row_bytes"]
    assert round(nbytes / 1e9, 2) == 6.62
    chunk = nemotron_roofline._experts(facts, "prefill")
    assert chunk == {"rows": 5 * 5632, "active": 5 * 128}
    cbytes = chunk["active"] * x["expert_params"] * 2 \
        + chunk["rows"] * x["expert_row_bytes"]
    assert round(cbytes / 1e9, 2) == 7.31
    # a chunk's rows: 44 a held expert, 22 FLOPs a weight byte — under the
    # chip's 240: bandwidth
    assert chunk["rows"] * x["expert_row_flops"] / PEAKS[
        "bf16_flops_per_s"] < cbytes / PEAKS["hbm_bytes_per_s"]


def test_the_step_is_priced_from_the_counters(monkeypatch):
    """The whole step's least time: dense weights 1.98 GB (5 M blocks 1.10,
    the * block 0.07, 5 E blocks' router, latent and shared 0.55, the head
    0.27), the active experts 6.6 GB, the live slots' states 2.68 GB, the
    live K and V 0.23 GB: 11.5 GB, 14.1 ms (ISSUE 66: 11.6, 14.1)."""
    from chipbench import spans

    monkeypatch.setattr(spans, "occupancy_win_pct", lambda facts: 100.0)
    facts = _facts()
    pct = nemotron_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step")
    note = facts["notes"][-1]
    assert note["bound"] == "bandwidth"
    assert round(note["state_bytes"] / 1e9, 2) == 2.68
    assert round(note["kv_bytes"] / 1e9, 2) == 0.23
    assert round(note["weight_bytes"] / 1e9, 1) == 8.6
    assert 13.5 < note["least_ms"] < 14.5
    assert pct == pytest.approx(100 * note["least_ms"] / 18.0) and pct < 100


def test_the_traffic_is_the_issues_ranges():
    """ISSUE 66's parameters and rules (the file's `ranges_why` says which
    stand and what the runs read)."""
    t = _load(os.path.join(HERE, "traffic", TRAFFIC + ".json"))
    flags = CONFIG["run"]["serve_flags"]
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    second = flags["max_len"] == 6144   # rule (ii)
    assert t["prompt_len"]["knots"] == (
        [[0.0, 1024], [0.5, 2048], [1.0, 4096]] if second else
        [[0.0, 1024], [0.5, 3072], [1.0, 8192]])
    assert t["prompt_len"]["scale"] == t["output_len"]["scale"] == "log"
    assert t["output_len"]["knots"] in (
        [[0.0, 256], [0.5, 512], [1.0, 1024]],
        [[0.0, 384], [0.5, 640], [1.0, 1024]])   # rule (iii)
    assert (t["max_total"], t["strata"], t["group"], t["layout_seed"],
            t["requests"]) == (5120 if second else 9216, 16, 4, 66, 4000)
    assert (t["outstanding"], t["anchor_index"]) == (
        2 * flags["slots"], flags["slots"] - 1)   # rule (i): 48 slots
    assert t["max_total"] <= flags["max_len"]
    assert t["reports"] == {"out_tok_s": "tok_s"} and t["ranges_why"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    top = t["prompt_len"]["knots"][-1][1]
    assert all(1024 <= r.prompt_len <= top and 256 <= r.max_new <= 1024
               and r.prompt_len + r.max_new <= t["max_total"] for r in a)
    assert max(int(r.prompt.max()) for r in a[:200]) < CONFIG["vocab_size"]
