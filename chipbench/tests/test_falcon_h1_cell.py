"""The Falcon-H1 configuration, its traffic and its per-layer files as cases
of what `test_configs.py` and `test_traffic.py` hold every configuration and
backlog to (a PR that adds a configuration adds files here and edits none:
those two files' literal tables wait for a `benchmark` PR), and the
configuration's own: the catalog row, the operations and bytes its rooflines
are priced at."""

import json
import os

import pytest

from chipbench import cells, falcon_h1_roofline, scopes
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "falcon-h1-34b-pp8-1chip", "falconh1-chat-saturated"
TRAFFIC = "chat-backlog-1k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "vocab_size"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: four at PR 54; since PR 56 the whole step's share is the shared entry's
#: (`srv_decode_step_roofline_pct`, its reader named by `trace.roofline`)
NEW = ["fh1_ssm_step_roofline_pct", "fh1_ssm_chunk_roofline_pct",
       "fh1_scope_ssm_pct"]


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
    # the thirteenth cell and the tenth configuration (PR 54); later PRs add
    assert BENCH["workloads"][12] is cell and BENCH["configs"][9] is entry
    for key in ("published", "deployment", "assumed", "memory", "check",
                "reduced_why"):
        assert CONFIG[key]
    # between 25 % and 93 % of the chip's 16 GB
    assert 4.0 <= CONFIG["memory"]["peak_observed_GB"] <= 14.9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_depth_and_vocabulary():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    assert CONFIG["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"] == 72
    assert CONFIG["published"]["vocab_size"] == \
        row["config"]["vocab_size"] == 261120
    # a period of one and eight more; the guide's floor of an eighth
    assert CONFIG["num_hidden_layers"] == 9 and CONFIG["vocab_size"] == 32640


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert (run["driver"], CONFIG["reference"]) == ("serve_fh1", "falcon_h1")
    assert run["serve_flags"] in (
        {"slots": 64, "max_len": 1536, "prompt_pad": 256},
        {"slots": 48, "max_len": 1536, "prompt_pad": 256})
    assert run["serve_flags"]["max_len"] >= TRAFFIC_FILE["max_total"]


def test_the_program_serves_the_files_widths():
    from dnn_tpu.registry import get_model

    for files in (CONFIG, {**CONFIG, **CONFIG["rehearsal"]}):
        cfg = get_model(files["run"]["model"]).config
        assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
                cfg.n_kv_head, cfg.head_dim, cfg.d_ff, cfg.rms_eps,
                cfg.block_size) == tuple(files[k] for k in (
                    "hidden_size", "num_hidden_layers", "vocab_size",
                    "num_attention_heads", "num_key_value_heads", "head_dim",
                    "intermediate_size", "rms_norm_eps",
                    "max_position_embeddings"))
        m, mup = cfg.mamba, cfg.mup
        assert (m.d_ssm, m.n_head, m.head_dim, m.d_state, m.n_groups, m.conv,
                m.chunk, m.ssm_in, m.ssm_out, list(m.ssm_multipliers)) == \
            tuple(files[k] for k in (
                "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_chunk_size", "ssm_in_multiplier", "ssm_out_multiplier",
                "ssm_multipliers"))
        assert (mup.embedding, mup.lm_head, mup.attention_in,
                mup.attention_out, mup.key, list(mup.mlp)) == tuple(
                    files[k] for k in (
                        "embedding_multiplier", "lm_head_multiplier",
                        "attention_in_multiplier", "attention_out_multiplier",
                        "key_multiplier", "mlp_multipliers"))
        assert not cfg.tie_word_embeddings and not cfg.attn_bias
    assert get_model(CONFIG["run"]["model"]).config.rope_theta == \
        CONFIG["rope_theta"]
    assert CONFIG["mamba_rms_norm"] and not CONFIG["mamba_norm_before_gate"]
    assert CONFIG["mamba_conv_bias"] and not CONFIG["mamba_proj_bias"]


def test_the_rooflines_widths_are_the_issues_counts():
    """By hand, at the published widths (ISSUE 54's arithmetic)."""
    x = falcon_h1_roofline.widths(CONFIG)
    assert x["attn_params"] == 31_457_280 and x["ssm_params"] == 68_351_072
    assert x["mlp_params"] == 330_301_440
    layer = falcon_h1_roofline.layer_params(x)
    assert round(layer / 1e6, 1) == 430.1 and round(layer * 2 / 1e9, 3) == 0.86
    assert x["head_params"] == 32640 * 5120
    assert x["state_bytes"] == 32 * 128 * 256 * 4 == 4_194_304
    assert x["row_bytes"] == 2048
    assert x["state_bytes"] // x["row_bytes"] == 2048  # positions it weighs
    assert x["tail_bytes"] == 3 * 5120 * 2
    # 64 slots x 9 layers of state, read and written: 4.83 GB a step, beside
    # 8.08 GB of layers and head
    state = 2 * 64 * 9 * x["state_bytes"]
    weights = (9 * layer + x["head_params"]) * 2
    assert round(state / 1e9, 2) == 4.83 and round(weights / 1e9, 2) == 8.08
    # the one-token rule a layer at 64 slots: 0.537 GB, 0.66 ms at the peak
    assert round(2 * 64 * x["state_bytes"] / 819e9 * 1e3, 2) == 0.66
    # the chunked rule: 4.8-5.4 MFLOP a token a layer, beside 860 of weights
    assert 4.5e6 < x["chunk_flops"] / x["chunk"] < 5.8e6  # the causal half
    assert x["chunk_flops"] / x["chunk"] < 0.01 * 2 * layer


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert len(cell["per_layer"]) >= 37
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(CONFIG["trace"]["known_scopes"])
    # no declared prefix is a prefix of another: none is swallowed
    known = CONFIG["trace"]["known_scopes"]
    assert not [(a, b) for a in known for b in known
                if a != b and b.startswith(a)]
    new = [m for m in BENCH["per_layer"] if m["name"].startswith("fh1_")]
    assert [m["name"] for m in new] == NEW
    assert CONFIG["trace"]["roofline"] == "falcon_h1_roofline"
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(HERE, "layers",
                                           m["name"] + ".json"))
    joined = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ()) and m not in new}
    assert len(joined) >= 34  # 33 at PR 54 and the whole step's share
    assert {"srv_decode_step_roofline_pct", "scope_state_pool_pct",
            "srv_state_read_share", "srv_pad_positions_share",
            "srv_kv_blocks_peak_pct",
            "srv_attn_live_blocks_share", "scope_attn_pct",
            "scope_kv_pool_pct", "scope_model_pct",
            "scope_unscoped_pct"} <= joined
    for m in BENCH["per_layer"]:
        # entries about experts, routing, windows or latents are not joined
        if m["name"].startswith((
                "scope_experts", "scope_route", "scope_shared", "scope_mla_",
                "scope_index", "scope_select", "srv_selected_share",
                "srv_window_blocks", "moe_", "srv_active_experts", "kx_",
                "dots_", "joy_", "keye_", "brm_")):
            assert CELL not in m["workloads"]
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "out_tok_s")["workloads"]


def test_a_program_without_the_counters_reads_nothing():
    """The parent has no `ssm.*` scope and, for this model, no daemon at
    all: every new reader returns None and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name.startswith("fh1_") or name in (
                "srv_decode_step_roofline_pct", "scope_state_pool_pct",
                "srv_state_read_share", "srv_pad_positions_share"):
            assert fn(facts, **args) is None, name


def _window(steps=100, slots=64, tokens=63.5, live=32000):
    x = falcon_h1_roofline.widths(CONFIG)
    state = 9 * slots * (x["state_bytes"] + x["tail_bytes"])
    m1 = {"step_steps_total": steps,
          "step_tokens_advanced_total": steps * tokens,
          "state_pool_bytes_read_total": steps * state,
          "state_pool_bytes_written_total": steps * state,
          "state_pool_kv_bytes_read_total": steps * 9 * live * 2048,
          "state_pool_installs_total": 9 * 30,
          "state_pool_prefill_real_positions_total": 346 * 30,
          "state_pool_prefill_pad_positions_total": 128 * 30}
    return {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
            "metrics1": m1, "client": {}, "peaks": PEAKS,
            "trace": {"programs": {
                "jit_decode_step": {"count": 10, "mean_ms": 24.0},
                "jit_prefill_chunk": {"count": 4, "mean_ms": 12.0}}}}, state


def test_the_step_is_priced_from_the_counters():
    """A window of 100 steps at 64 slots and 32 k live positions: the step's
    least time is its bytes over the peak — 8.08 GB of layers and head, 4.87
    GB of state and tail, 0.59 GB of K and V: 16.5 ms, at most ~3 900
    tokens/s."""
    facts, state = _window()
    pct = falcon_h1_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step")
    note = facts["notes"][-1]
    assert note["state_bytes"] == 2 * state
    assert round(note["weight_bytes"] / 1e9, 2) == 8.08
    assert round(note["kv_bytes"] / 1e9, 2) == 0.59
    least_ms = 1e3 * note["bytes"] / 819e9
    assert note["bound"] == "bandwidth" and 16.3 < least_ms < 16.7
    assert pct == pytest.approx(100 * least_ms / 24.0) and 0 < pct < 100
    per = cells.resolve(CELL)["per_layer"]
    fn, args = per["srv_state_read_share"]
    assert 0.85 < fn(facts, **args) < 0.95  # the state, not K and V
    fn, args = per["srv_pad_positions_share"]
    assert fn(facts, **args) == pytest.approx(128 / (128 + 346))


def test_a_scoped_share_divides_by_its_scopes_time(monkeypatch):
    """The one-token rule and the chunked rule: least time over the device
    time under the scopes the file names, per execution of the program."""
    facts, state = _window()
    x = falcon_h1_roofline.widths(CONFIG)
    spent = {}
    monkeypatch.setattr(
        falcon_h1_roofline, "_spent_ms",
        lambda facts, program, inside, scopes: spent[tuple(scopes)])
    per = cells.resolve(CELL)["per_layer"]

    def read(name, ms):
        fn, args = per[name]
        spent[tuple(args["scopes"])] = ms
        return fn(facts, **args)

    got = read("fh1_ssm_step_roofline_pct", 10.8)
    assert got == pytest.approx(100 * 1e3 * 2 * state / 819e9 / 10.8)
    assert 50 < got < 60 and facts["notes"][-1]["bound"] == "bandwidth"
    got = read("fh1_ssm_chunk_roofline_pct", 1.0)
    nbytes = 9 * (2 * x["chunk_bytes"] + 2 * x["state_bytes"])
    assert got == pytest.approx(100 * 1e3 * nbytes / 819e9 / 1.0)
    assert 0 < got < 100 and facts["notes"][-1]["bound"] == "bandwidth"


def test_the_traffic_is_the_issues():
    t = TRAFFIC_FILE
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    assert t["prompt_len"]["knots"] == [[0.0, 64], [0.5, 256], [1.0, 1024]]
    lo, mid, hi = (k[1] for k in t["output_len"]["knots"])
    assert (lo, mid, hi) in ((128, 256, 512), (192, 320, 512))
    assert t["max_total"] == 1536
    assert t["prompt_len"]["scale"] == t["output_len"]["scale"] == "log"
    assert (t["strata"], t["group"], t["layout_seed"], t["requests"]) == (
        16, 4, 54, 4000)
    slots = CONFIG["run"]["serve_flags"]["slots"]
    assert (t["outstanding"], t["anchor_index"]) == (2 * slots, slots - 1)
    assert t["reports"] == {"out_tok_s": "tok_s"} and t["ranges_why"]
    assert t["max_total"] <= CONFIG["run"]["serve_flags"]["max_len"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    assert all(64 <= r.prompt_len <= 1024 and lo <= r.max_new <= hi
               and r.prompt_len + r.max_new <= t["max_total"] for r in a)
    # ids from the slice's rows
    top = max(int(r.prompt.max()) for r in a[:200])
    assert 0.99 * CONFIG["vocab_size"] < top < CONFIG["vocab_size"] == 32640
    # every context is under three quarters of the length at which K and V
    # would weigh what the state does
    x = falcon_h1_roofline.widths(CONFIG)
    assert t["max_total"] <= 0.75 * x["state_bytes"] // x["row_bytes"]
    # the rehearsal's block fits the test model's positions
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
