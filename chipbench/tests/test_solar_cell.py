"""The Solar-Open2 configuration, its traffic and its per-layer files as
cases of what `test_configs.py` and `test_traffic.py` hold every
configuration and backlog to (a PR that adds a configuration adds files
here and edits none: those two files' literal tables wait for a
`benchmark` PR), and the configuration's own: the catalog row, the
operations and bytes its rooflines are priced at."""

import json
import os

import pytest

from chipbench import cells, scopes, solar_roofline
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "solar-open2-250b-ep8-1chip", "solar2-longchat-saturated"
TRAFFIC = "longchat-backlog-9k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["gqa_layers", "n_routed_experts", "num_hidden_layers",
           "vocab_size"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _load(path):
    with open(path) as f:
        return json.load(f)


BENCH = _load(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = _load(os.path.join(HERE, "configs", NAME + ".json"))
TRAFFIC_FILE = _load(os.path.join(HERE, "traffic", TRAFFIC + ".json"))


def test_the_entry_and_the_file_agree():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the eleventh cell and the eighth configuration (PR 47); later PRs add
    assert BENCH["workloads"][10] is cell and BENCH["configs"][7] is entry
    for key in ("published", "deployment", "assumed", "memory", "check"):
        assert CONFIG[key]
    assert CONFIG["memory"]["peak_observed_GB"] >= 4.0  # 25 % of 16 GB


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_the_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    assert CONFIG["gqa_layers"] == row["config"]["gqa_layers"][:1]
    for key in ("n_routed_experts", "num_hidden_layers", "vocab_size"):
        assert CONFIG["published"][key] == row["config"][key]
    # no width is reduced, the nested group is whole
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "linear_attn_config"):
        assert CONFIG[key] == row["config"][key], key


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert run["kv_bytes_per_element"] == 2
    assert (run["driver"], CONFIG["reference"]) == ("serve_dots", "solar")
    assert run["serve_flags"]["slots"] == 64
    assert run["serve_flags"]["prompt_pad"] == 1024
    assert run["serve_flags"]["max_len"] >= TRAFFIC_FILE["max_total"]


def test_the_program_serves_the_files_widths():
    from dnn_tpu.registry import get_model

    cfg = get_model(CONFIG["run"]["model"]).config
    lin = CONFIG["linear_attn_config"]
    assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
            cfg.n_kv_head, cfg.head_dim) == tuple(CONFIG[k] for k in (
                "hidden_size", "num_hidden_layers", "vocab_size",
                "num_attention_heads", "num_key_value_heads", "head_dim"))
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == \
        CONFIG["gqa_layers"]
    assert set(cfg.layer_types) == {"full", "linear"}
    assert (cfg.kda.n_head, cfg.kda.head_dim, cfg.kda.conv) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert cfg.kda.chunk == solar_roofline.CHUNK
    assert not cfg.kv_full.rope and not CONFIG["use_rope"]
    assert cfg.attn_gate and CONFIG["use_gqa_gate"]
    assert cfg.first_k_dense == CONFIG["first_k_dense_replace"] == 0
    assert (cfg.experts_held, cfg.n_expert, cfg.router_top_k, cfg.d_ff,
            cfg.d_shared, cfg.rms_eps, cfg.router.scale) == (
        CONFIG["n_routed_experts"], CONFIG["published"]["router_outputs"],
        CONFIG["num_experts_per_tok"], CONFIG["moe_intermediate_size"],
        CONFIG["n_shared_experts"] * CONFIG["moe_intermediate_size"],
        CONFIG["rms_norm_eps"], CONFIG["routed_scaling_factor"])
    assert cfg.router_norm_topk == CONFIG["norm_topk_prob"]
    assert not cfg.tie_word_embeddings and not CONFIG["tie_word_embeddings"]
    rehearsed = get_model(CONFIG["rehearsal"]["run"]["model"]).config
    assert rehearsed.layer_types == cfg.layer_types


def test_the_rooflines_widths_are_the_issues_counts():
    """By hand, at the published widths (ISSUE 47's arithmetic)."""
    x = solar_roofline.widths(CONFIG)
    assert x["linear_params"] == 137_723_904        # 137.7 M
    assert x["full_params"] == 109_051_904          # 109.1 M
    assert x["expert_params"] == x["shared_params"] == 15_728_640
    assert x["router_params"] == 4096 * 320
    assert x["head_params"] == 24576 * 4096
    assert x["layers"] == {"full": 1, "linear": 3}
    assert x["row_bytes"] == 4096 and x["pair_flops"] == 64 * 4 * 128
    assert x["state_bytes"] == 4_194_304            # 4.19 MB a slot a layer
    assert x["tail_bytes"] == 3 * 24576 * 2         # 0.15 MB
    assert x["step_flops"] == 7 * 64 * 128 * 128
    # a chunk of 64, all heads: 4 c^2 d + 6 c d^2 a head
    assert x["chunk_flops"] == 64 * (4 * 64 * 64 * 128 + 6 * 64 * 128 * 128)
    assert x["chunk_bytes"] == 64 * 5 * 64 * 128 * 4
    # one period G L L L: 755.2 M + 3 x 783.8 M = 3 107 M, 6.21 GB
    common = 40 * x["expert_params"] + x["shared_params"] \
        + x["router_params"]
    period = (x["full_params"] + common) + 3 * (x["linear_params"] + common)
    assert round((x["full_params"] + common) / 1e6, 1) == 755.2
    assert round((x["linear_params"] + common) / 1e6, 1) == 783.9
    assert round(2 * period / 1e9, 2) == 6.21
    # the state: 3 layers x 64 slots; the full layer's pool
    assert round(3 * 64 * (x["state_bytes"] + x["tail_bytes"]) / 1e9, 2) \
        == 0.83
    assert round(64 * 9216 * x["row_bytes"] / 1e9, 2) == 2.42
    # a state outweighs K and V from 1024 positions on
    assert x["state_bytes"] // x["row_bytes"] == 1024


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert len(cell["per_layer"]) >= 45  # 40 at PR 47, 45 since PR 52
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(CONFIG["trace"]["known_scopes"])
    # no declared prefix is a prefix of another: a share counts once
    known = CONFIG["trace"]["known_scopes"]
    assert not [(a, b) for a in known for b in known
                if a != b and b.startswith(a)]
    # eleven at PR 47; since PR 56 the whole step's share is the shared
    # entry's (its reader named by `trace.roofline`), and the three that the
    # next state configurations joined carry no model's prefix
    sol = [m for m in BENCH["per_layer"] if m["name"].startswith("sol_")]
    assert [m["name"] for m in sol] == [
        "sol_kda_step_roofline_pct", "sol_kda_scan_roofline_pct",
        "sol_full_decode_roofline_pct", "sol_scope_kda_project_pct",
        "sol_scope_kda_scan_pct", "sol_scope_kda_out_pct",
        "sol_scope_gate_pct"]
    assert CONFIG["trace"]["roofline"] == "solar_roofline"
    for m in sol:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(HERE, "layers",
                                           m["name"] + ".json"))
    joined = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ()) and m not in sol]
    assert len(joined) >= 38  # 29 at PR 47, 34 since PR 52, 38 since PR 56
    assert {"scope_shared_pct", "srv_decode_step_roofline_pct",
            "scope_state_pool_pct", "srv_state_read_share",
            "srv_pad_positions_share"} <= set(joined)
    for m in BENCH["per_layer"]:
        # the experts' rooflines divide by widths this file names
        # otherwise (`intermediate_size`, `layer_types`): not joined
        if m["name"] in ("moe_experts_roofline_pct",
                         "kx_experts_roofline_pct"):
            assert CELL not in m["workloads"]


def test_a_program_without_the_counters_reads_nothing():
    """The parent has no `state_pool_*` counter and no `kda.*` scope:
    every new reader returns None and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name.startswith("sol_") or name in (
                "srv_decode_step_roofline_pct", "scope_state_pool_pct",
                "srv_state_read_share", "srv_pad_positions_share"):
            assert fn(facts, **args) is None, name


def _window(steps=100, slots=64, live=4600, active=33, rows=103):
    x = solar_roofline.widths(CONFIG)
    state = 3 * slots * (x["state_bytes"] + x["tail_bytes"])
    m1 = {
        "step_steps_total": steps,
        "step_tokens_advanced_total": steps * slots,
        'moe_layer_calls_total{program="decode"}': steps * 4,
        'moe_active_experts_total{program="decode"}': steps * 4 * active,
        'moe_assignments_total{program="decode"}': steps * 4 * rows,
        "state_pool_bytes_read_total": steps * state,
        "state_pool_bytes_written_total": steps * state,
        "state_pool_kv_bytes_read_total": steps * slots * live * 4096,
        "state_pool_prefill_real_positions_total": 4096 * 30,
        "state_pool_prefill_pad_positions_total": 512 * 30}
    return {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
            "metrics1": m1, "client": {}, "peaks": PEAKS,
            "trace": {"programs": {
                "jit_decode_step": {"count": 10, "mean_ms": 20.0},
                "jit_prefill_chunk": {"count": 4, "mean_ms": 60.0}}}}, state


def test_the_step_is_priced_from_the_counters():
    """A window of 100 steps at 64 rows, 33 of the 40 held experts active
    a layer, 4600 live positions a slot: the step's least time is its
    bytes over the peak — ISSUE 47's reckoning: ~4 GB of active experts,
    1.2 GB of other weights, 1.6 GB of state, ~1 GB of K and V."""
    facts, state = _window()
    assert round(2 * state / 1e9, 2) == 1.67
    m = solar_roofline._mixer_step(facts)
    assert m["state_bytes"] == 2 * state
    assert m["kv_bytes"] == 64 * 4600 * 4096        # 1.21 GB
    assert round(m["weight_bytes"] / 1e9, 3) == 0.826
    pct = solar_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step")
    note = facts["notes"][-1]
    experts = 4 * 33 * 15_728_640 * 2
    assert round(experts / 1e9, 2) == 4.15
    assert round((note["weight_bytes"] - experts) / 1e9, 2) == 1.39
    least_ms = 1e3 * note["bytes"] / 819e9
    assert note["bound"] == "bandwidth" and 9.0 < least_ms < 11.0
    assert pct == pytest.approx(100 * least_ms / 20.0) and 0 < pct < 100
    assert solar_roofline.state_read_share(facts) == pytest.approx(
        2 * state / (2 * state + m["kv_bytes"]))
    pads = cells.resolve(CELL)["per_layer"]["srv_pad_positions_share"]
    assert pads[0](facts, **pads[1]) == pytest.approx(512 / (512 + 4096))


def test_a_scoped_share_divides_by_its_scopes_time(monkeypatch):
    """The mixers' decode share, the softmax layer's read and the chunked
    rule: least time over the device time under the scopes the file
    names, per execution of the program."""
    facts, state = _window()
    x = solar_roofline.widths(CONFIG)
    spent = {}
    monkeypatch.setattr(
        solar_roofline, "_spent_ms",
        lambda facts, program, inside, scopes: spent[tuple(scopes)])
    per = cells.resolve(CELL)["per_layer"]

    def read(name, ms):
        fn, args = per[name]
        spent[tuple(args["scopes"])] = ms
        return fn(facts, **args)

    got = read("sol_kda_step_roofline_pct", 8.0)
    least = 1e3 * (2 * state + 3 * x["linear_params"] * 2) / 819e9
    assert got == pytest.approx(100 * least / 8.0) and 30 < got < 40
    got = read("sol_full_decode_roofline_pct", 3.0)
    assert got == pytest.approx(100 * 1e3 * 64 * 4600 * 4096 / 819e9 / 3.0)
    got = read("sol_kda_scan_roofline_pct", 12.0)
    nbytes = 3 * (16 * x["chunk_bytes"] + 2 * x["state_bytes"])
    flops = 3 * 16 * x["chunk_flops"]
    assert round(flops / 1e9, 1) == 25.8
    least = 1e3 * max(nbytes / 819e9, flops / 197e12)
    assert got == pytest.approx(100 * least / 12.0) and 0 < got < 100
    assert facts["notes"][-1]["bound"] == "bandwidth"


def test_the_traffic_is_the_issues():
    t = TRAFFIC_FILE
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    assert t["output_len"]["knots"] == [[0.0, 256], [0.5, 448], [1.0, 768]]
    lo, mid, hi = (k[1] for k in t["prompt_len"]["knots"])
    assert (lo, mid, hi, t["max_total"]) in ((2048, 4096, 8192, 8960),
                                             (1024, 2560, 6144, 6912))
    assert t["prompt_len"]["scale"] == t["output_len"]["scale"] == "log"
    assert (t["outstanding"], t["strata"], t["group"], t["layout_seed"],
            t["anchor_index"], t["requests"]) == (128, 16, 4, 47, 63, 4000)
    assert t["reports"] == {"out_tok_s": "tok_s"} and t["ranges_why"]
    assert t["max_total"] <= CONFIG["run"]["serve_flags"]["max_len"]
    assert t["outstanding"] == 2 * CONFIG["run"]["serve_flags"]["slots"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    assert all(lo <= r.prompt_len <= hi and 256 <= r.max_new <= 768
               and r.prompt_len + r.max_new <= t["max_total"] for r in a)
    assert max(int(r.prompt.max()) for r in a[:200]) < CONFIG["vocab_size"]
    # every context is at least the length at which a state outweighs K, V
    x = solar_roofline.widths(CONFIG)
    assert min(r.prompt_len for r in a) >= x["state_bytes"] // x["row_bytes"]
    # the rehearsal's block fits the test model's pool
    r = {**t, **t["rehearsal"]}
    flags = CONFIG["rehearsal"]["run"]["serve_flags"]
    assert r["max_total"] <= flags["max_len"]
    assert all(q.prompt_len + q.max_new <= r["max_total"]
               for q in tg.make_requests(r, 7, 256)[:400])


def test_the_rehearsal_passes_with_no_compilation_in_its_window(tmp_path):
    """`python3 chipbench/run.py --rehearse` of the new cell on the CPU:
    through the daemon, correct against the reference, zero compilations
    inside the window (`serve.run` raises otherwise)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "3", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    window = next(x for x in lines if x.get("phase") == "window")
    assert window["compilations_in_window"] == 0
    assert window["requests_completed"] >= 10 and not window["errors"]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
