"""The MiniCPM-SALA configuration, its traffic and its per-layer files as
cases of what `test_configs.py` and `test_traffic.py` hold every
configuration and backlog to (a PR that adds a configuration adds files here
and edits none), and the configuration's own: the catalog row, the operations
and bytes its rooflines are priced at."""

import json
import os

import pytest

from chipbench import cells, sala_roofline, scopes
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "minicpm-sala-pp8-1chip", "sala-longdoc-saturated"
TRAFFIC = "longdoc-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["mixer_types", "num_hidden_layers"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["sala_lin_step_roofline_pct", "sala_lin_chunk_roofline_pct",
       "sala_block_decode_roofline_pct", "sala_block_prefill_roofline_pct",
       "sala_bsel_roofline_pct", "scope_lin_pct", "scope_bsel_pct"]


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
    for key in ("published", "deployment", "assumed", "memory", "check",
                "reduced_why", "sparse_config"):
        assert CONFIG[key]
    # over 25 % of the chip's 16 GB, under 93 %
    assert 4.0 <= CONFIG["memory"]["peak_observed_GB"] <= 14.9
    assert len(BENCH["per_layer"]) <= 122  # ISSUE 58's ceiling


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_depth_and_the_kinds():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    assert CONFIG["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"] == 32
    assert CONFIG["published"]["mixer_types"] == row["config"]["mixer_types"]
    # one whole period, the published layers 0-3
    assert CONFIG["mixer_types"] == row["config"]["mixer_types"][:4] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    assert CONFIG["num_hidden_layers"] == 4
    assert row["config"]["mixer_types"].count("minicpm4") == 8


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert (run["driver"], CONFIG["reference"]) == ("serve_fh1",
                                                    "minicpm_sala")
    flags = run["serve_flags"]
    assert (flags["slots"], flags["prompt_pad"], flags["block_len"]) == (
        32, 1024, 64)
    assert flags["max_len"] in (33792, 25600)
    assert flags["max_len"] >= TRAFFIC_FILE["max_total"]
    assert flags["block_len"] == CONFIG["sparse_config"]["block_size"]


def test_the_program_serves_the_files_widths():
    from dnn_tpu.registry import get_model

    for files in (CONFIG, {**CONFIG, **CONFIG["rehearsal"]}):
        cfg = get_model(files["run"]["model"]).config
        assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
                cfg.n_kv_head, cfg.head_dim, cfg.d_ff, cfg.rms_eps,
                cfg.block_size, cfg.rope_theta) == tuple(files[k] for k in (
                    "hidden_size", "num_hidden_layers", "vocab_size",
                    "num_attention_heads", "num_key_value_heads", "head_dim",
                    "intermediate_size", "rms_norm_eps",
                    "max_position_embeddings", "rope_theta"))
        lin, sel, mup = cfg.lightning, cfg.block_select, cfg.mup
        assert (lin.n_head, lin.head_dim) == (files["lightning_nh"],
                                              files["lightning_head_dim"])
        assert files["lightning_nkv"] == files["lightning_nh"]
        s = files["sparse_config"]
        assert (sel.block, sel.topk, sel.window, sel.init_blocks, sel.kernel,
                sel.stride) == tuple(s[k] for k in (
                    "block_size", "topk", "window_size", "init_blocks",
                    "kernel_size", "kernel_stride"))
        assert [{"full": "minicpm4", "linear": "lightning-attn"}[t]
                for t in cfg.layer_types] == files["mixer_types"]
        # the three scalars: scale_emb, r of the PUBLISHED depth on both
        # branches, hidden / dim_model_base dividing the head's input
        r = files["scale_depth"] / 32 ** 0.5
        assert mup.embedding == files["scale_emb"]
        assert mup.attention_out == pytest.approx(r) == mup.mlp[1]
        assert 1 / mup.lm_head == pytest.approx(
            files["hidden_size"] / files["dim_model_base"])
        assert cfg.qk_norm and cfg.attn_gate and not cfg.kv_full.rope
        assert not cfg.tie_word_embeddings and not cfg.attn_bias
    assert CONFIG["qk_norm"] and not CONFIG["attn_use_rope"]
    assert CONFIG["lightning_use_rope"] and CONFIG["use_output_norm"]
    assert CONFIG["use_output_gate"] and CONFIG["attn_use_output_gate"]


def test_the_rooflines_widths_are_the_issues_counts():
    """By hand, at the published widths (ISSUE 58's arithmetic)."""
    x = sala_roofline.widths(CONFIG)
    assert x["full_params"] == 52_428_800
    assert x["linear_params"] == 83_886_080
    assert x["mlp_params"] == 201_326_592
    assert x["layers"] == {"full": 1, "linear": 3}
    params = sala_roofline._params(x)
    # one period 1109.4 M = 2.22 GB, the head 0.60 GB: 2.82 GB a step
    assert round((params - x["head_params"]) / 1e6, 1) == 1109.4
    assert round(params * 2 / 1e9, 2) == 2.82
    assert x["head_params"] == 73448 * 4096
    assert x["state_bytes"] == 32 * 128 * 128 * 4 == 2_097_152
    assert x["row_bytes"] == 1024 and x["pooled_row_bytes"] == 512
    # 32 slots x 3 layers of state, read and written: 0.40 GB a step
    assert round(2 * 32 * 3 * x["state_bytes"] / 1e9, 2) == 0.40
    # a list of 96 blocks a KV head: 6144 positions x 1 KB a slot
    assert 96 * 64 * x["row_bytes"] * 32 / 1e9 == pytest.approx(0.2013, 1e-3)
    # the chunked rule: 4.3 GFLOP a 1024-token chunk a layer (the causal
    # half), under 1 % of the layer's projections
    chunk = 4 * x["chunk_flops"]
    assert 4.0e9 < chunk < 4.6e9
    assert chunk < 0.012 * 2 * 1024 * (x["linear_params"] + x["mlp_params"])


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert len(cell["per_layer"]) >= 40
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(CONFIG["trace"]["known_scopes"])
    known = CONFIG["trace"]["known_scopes"]
    assert not [(a, b) for a in known for b in known
                if a != b and b.startswith(a)]
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == NEW
    assert CONFIG["trace"]["roofline"] == "sala_roofline"
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(HERE, "layers",
                                           m["name"] + ".json"))
    joined = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ()) and m not in new}
    assert {"srv_decode_step_roofline_pct", "scope_state_pool_pct",
            "srv_state_read_share", "srv_pad_positions_share",
            "srv_kv_blocks_peak_pct", "srv_attn_live_blocks_share",
            "scope_attn_pct", "scope_kv_pool_pct", "scope_model_pct",
            "scope_unscoped_pct", "scope_select_pct",
            "srv_selected_share"} <= joined
    for m in BENCH["per_layer"]:
        # entries about experts, routing, windows, latents or an indexer
        if m["name"].startswith((
                "scope_experts", "scope_route", "scope_shared", "scope_mla_",
                "scope_index", "srv_window_blocks", "moe_",
                "srv_active_experts", "kx_", "dots_", "joy_", "keye_",
                "brm_", "fh1_", "sol_")):
            assert CELL not in m["workloads"]
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "out_tok_s")["workloads"]


def test_a_program_without_the_counters_reads_nothing():
    """The parent has no `lin.*` or `bsel.*` scope and, for this model, no
    daemon at all: every new reader returns None and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name in NEW or name in (
                "srv_decode_step_roofline_pct", "scope_state_pool_pct",
                "srv_state_read_share", "srv_pad_positions_share",
                "srv_selected_share", "scope_select_pct"):
            assert fn(facts, **args) is None, name


def _window(steps=100, slots=32, tokens=31.0, live=600_000, picked=190_000):
    x = sala_roofline.widths(CONFIG)
    state = 3 * slots * x["state_bytes"]
    kv = picked * x["row_bytes"] + live / 16 * x["pooled_row_bytes"]
    m1 = {"step_steps_total": steps,
          "step_tokens_advanced_total": steps * tokens,
          "state_pool_bytes_read_total": steps * state,
          "state_pool_bytes_written_total": steps * state,
          "state_pool_kv_bytes_read_total": steps * kv,
          'dsa_layer_calls_total{program="decode"}': steps,
          'dsa_candidate_positions_total{program="decode"}': steps * live,
          'dsa_selected_positions_total{program="decode"}': steps * picked,
          'dsa_layer_calls_total{program="prefill"}': 40,
          'dsa_candidate_positions_total{program="prefill"}':
              40 * 1024 * 12000,
          'dsa_selected_positions_total{program="prefill"}':
              40 * 1024 * 6000,
          "state_pool_prefill_real_positions_total": 900 * 40,
          "state_pool_prefill_pad_positions_total": 124 * 40}
    return {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
            "metrics1": m1, "client": {}, "peaks": PEAKS,
            "trace": {"programs": {
                "jit_decode_step": {"count": 10, "mean_ms": 6.0},
                "jit_prefill_chunk": {"count": 4, "mean_ms": 25.0}}}}, state


def test_the_step_is_priced_from_the_counters():
    """A window of 100 steps at 32 slots, 600 k live positions of which 190 k
    are read: the step's least time is its bytes over the peak — 2.82 GB of
    layers and head, 0.40 GB of state, 0.21 GB of chosen blocks and pooled
    rows: 4.2 ms."""
    facts, state = _window()
    pct = sala_roofline.decode_step_roofline_pct(facts,
                                                 program="jit_decode_step")
    note = facts["notes"][-1]
    assert note["state_bytes"] == 2 * state
    assert round(note["weight_bytes"] / 1e9, 2) == 2.82
    assert round(note["kv_bytes"] / 1e9, 2) == 0.21
    least_ms = 1e3 * note["bytes"] / 819e9
    assert note["bound"] == "bandwidth" and 4.1 < least_ms < 4.3
    assert pct == pytest.approx(100 * least_ms / 6.0) and 0 < pct < 100
    per = cells.resolve(CELL)["per_layer"]
    fn, args = per["srv_state_read_share"]
    assert 0.6 < fn(facts, **args) < 0.7
    fn, args = per["srv_selected_share"]
    assert fn(facts, **args) == pytest.approx(190 / 600)
    fn, args = per["srv_pad_positions_share"]
    assert fn(facts, **args) == pytest.approx(124 / 1024)


def test_a_scoped_share_divides_by_its_scopes_time(monkeypatch):
    """The five rooflines: least time over the device time under the scopes
    the file names, per execution of the program."""
    facts, state = _window()
    x = sala_roofline.widths(CONFIG)
    spent = {}
    monkeypatch.setattr(
        sala_roofline, "_spent_ms",
        lambda facts, program, inside, scopes: spent[tuple(scopes)])
    per = cells.resolve(CELL)["per_layer"]

    def read(name, ms):
        fn, args = per[name]
        spent[tuple(args["scopes"])] = ms
        return fn(facts, **args)

    got = read("sala_lin_step_roofline_pct", 0.8)
    assert got == pytest.approx(100 * 1e3 * 2 * state / 819e9 / 0.8)
    assert 55 < got < 65 and facts["notes"][-1]["bound"] == "bandwidth"
    got = read("sala_lin_chunk_roofline_pct", 3.0)
    # q, k, v, o in float32 and the state in and out: 0.21 GB a chunk, four
    # times its FLOPs' time
    assert facts["notes"][-1]["bound"] == "bandwidth"
    nbytes = 3 * (4 * x["chunk_bytes"] + 2 * x["state_bytes"])
    assert got == pytest.approx(100 * 1e3 * nbytes / 819e9 / 3.0)
    got = read("sala_block_decode_roofline_pct", 0.5)
    assert got == pytest.approx(100 * 1e3 * 190_000 * 1024 / 819e9 / 0.5)
    assert 45 < got < 50
    got = read("sala_block_prefill_roofline_pct", 5.0)
    assert facts["notes"][-1]["bound"] == "compute"
    assert got == pytest.approx(
        100 * 1e3 * 1024 * 6000 * x["pair_flops"] / 197e12 / 5.0)
    got = read("sala_bsel_roofline_pct", 0.4)
    assert got == pytest.approx(
        100 * 1e3 * 600_000 / 16 * 512 / 819e9 / 0.4)
    assert 0 < got < 100


def test_the_traffic_is_the_issues():
    t = TRAFFIC_FILE
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    knots = [k[1] for k in t["prompt_len"]["knots"]]
    assert knots in ([16384, 24576, 32768], [12288, 16384, 24576])
    lo, mid, hi = (k[1] for k in t["output_len"]["knots"])
    assert (lo, mid, hi) == (128, 256, 512)
    assert t["max_total"] == knots[-1] + 512
    assert t["prompt_len"]["scale"] == t["output_len"]["scale"] == "log"
    assert (t["strata"], t["group"], t["layout_seed"], t["requests"]) == (
        16, 4, 58, 4000)
    slots = CONFIG["run"]["serve_flags"]["slots"]
    assert (t["outstanding"], t["anchor_index"]) == (2 * slots, slots - 1)
    assert t["reports"] == {"out_tok_s": "tok_s"} and t["ranges_why"]
    assert t["max_total"] <= CONFIG["run"]["serve_flags"]["max_len"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:8], b[:8]))
    assert all(knots[0] <= r.prompt_len <= knots[-1] and lo <= r.max_new <= hi
               and r.prompt_len + r.max_new <= t["max_total"] for r in a)
    # every context is at least twice the 6144 positions a query may read
    assert knots[0] >= 2 * (64 + 32) * 64
    # the rehearsal's contexts drop blocks at the test model's sizes (a
    # query may read 4 blocks of 8) and fit its positions
    r = {**t, **t["rehearsal"]}
    flags = CONFIG["rehearsal"]["run"]["serve_flags"]
    assert r["max_total"] <= flags["max_len"]
    assert r["prompt_len"]["knots"][0][1] >= 2 * 32
    assert all(q.prompt_len + q.max_new <= r["max_total"]
               for q in tg.make_requests(r, 7, 256)[:400])


def test_the_rehearsal_passes_with_no_compilation_in_its_window(tmp_path):
    """`python3 chipbench/run.py --rehearse --trace 1` of the new cell on the
    CPU: through the daemon, correct against the reference, zero compilations
    inside the window (`serve.run` raises otherwise), no reader's error
    row."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    assert not [x for x in lines if x.get("phase") == "reader_error"]
    check = next(x for x in lines if x.get("phase") == "check")
    assert check["longest_context"] > 150  # blocks were dropped
