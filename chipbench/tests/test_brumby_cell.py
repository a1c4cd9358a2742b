"""The Brumby configuration, its traffic and its per-layer files as cases
of what `test_configs.py` and `test_traffic.py` hold every configuration and
backlog to (a PR that adds a configuration adds files here and edits none:
those two files' literal tables wait for a `benchmark` PR), and the
configuration's own: the catalog row, the operations and bytes its rooflines
are priced at."""

import json
import os

import pytest

from chipbench import brumby_roofline, cells, scopes
from chipbench import traffic as tg

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
NAME, CELL = "brumby-14b-pp8-1chip", "brumby-fewshot-saturated"
TRAFFIC = "fewshot-backlog-2k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers"]
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
    # the twelfth cell and the ninth configuration (PR 50); later PRs add
    assert BENCH["workloads"][11] is cell and BENCH["configs"][8] is entry
    for key in ("published", "deployment", "assumed", "memory", "check",
                "reduced_why", "retention"):
        assert CONFIG[key]
    # between 25 % and 93 % of the chip's 16 GB
    assert 4.0 <= CONFIG["memory"]["peak_observed_GB"] <= 14.9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_no_key_differs_from_the_catalog_row_but_the_depth():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(REDUCED)
    assert CONFIG["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"] == 40
    assert CONFIG["num_hidden_layers"] == 5  # a period of one and four more


def test_weights_are_priced_at_the_served_dtype():
    run = CONFIG["run"]
    assert run["weight_bytes_per_param"] == scopes.OPERAND_BYTES[run["dtype"]]
    assert (run["driver"], CONFIG["reference"]) == ("serve_dots", "brumby")
    assert run["serve_flags"] in (
        {"slots": 16, "max_len": 4096, "prompt_pad": 1024},
        {"slots": 12, "max_len": 4096, "prompt_pad": 1024})
    assert run["serve_flags"]["max_len"] >= TRAFFIC_FILE["max_total"]


def test_the_program_serves_the_files_widths():
    from dnn_tpu.models import retention
    from dnn_tpu.registry import get_model

    cfg = get_model(CONFIG["run"]["model"]).config
    assert (cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.n_head,
            cfg.n_kv_head, cfg.head_dim, cfg.d_ff, cfg.rope_theta,
            cfg.rms_eps, cfg.block_size) == tuple(CONFIG[k] for k in (
                "hidden_size", "num_hidden_layers", "vocab_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "intermediate_size", "rope_theta", "rms_norm_eps",
                "max_position_embeddings"))
    assert not cfg.tie_word_embeddings and not CONFIG["tie_word_embeddings"]
    assert cfg.sliding_window is None and CONFIG["sliding_window"] is None
    assert not cfg.attn_bias and not CONFIG["attention_bias"]
    for files, m, c in ((CONFIG, cfg.retention, cfg), (
            CONFIG["rehearsal"], get_model(
                CONFIG["rehearsal"]["run"]["model"]).config.retention,
            get_model(CONFIG["rehearsal"]["run"]["model"]).config)):
        ret = files["retention"]
        assert (ret["degree"], ret["tile"], ret["chunk"], ret["eps"],
                tuple(ret["gate_range"])) == (2, m.tile, m.chunk, m.eps,
                                              m.gate_range)
        # the D the file states is the program's, and a symmetric form
        assert ret["state_width"] == retention.state_width(
            c.head_dim, m.tile) < c.head_dim ** 2  # not the outer product
    assert CONFIG["retention"]["state_width"] <= 9216


def test_the_rooflines_widths_are_the_issues_counts():
    """By hand, at the published widths (ISSUE 50's arithmetic, at D 8 704)."""
    x = brumby_roofline.widths(CONFIG)
    assert x["layer_params"] == 330_342_408            # 330.3 M
    assert round(x["layer_params"] * 2 / 1e9, 3) == 0.661
    assert x["head_params"] == 151936 * 5120
    assert x["state_bytes"] == 8 * (128 * 8704 + 8704) * 4 == 35_930_112
    assert x["state_bytes"] // 4096 == 8772  # positions of K and V it weighs
    assert x["step_flops"] == 13 * 8 * 128 * 8704
    # 16 slots x 5 layers of state, read and written: 5.75 GB a step,
    # beside 4.86 GB of layers and head
    state = 2 * 16 * 5 * x["state_bytes"]
    weights = (5 * x["layer_params"] + x["head_params"]) * 2
    assert round(state / 1e9, 2) == 5.75 and round(weights / 1e9, 2) == 4.86
    assert state / (state + weights) > 0.5
    # the chunked rule, a 1024-chunk a layer: 0.03 TFLOP from an empty
    # state, 0.09 more from one: ~18 % of the layer's weights' 0.68
    assert round(x["chunk_flops"] / 1e9) == 29
    assert round(x["chunk_state_flops"] / 1e9) == 92
    assert 0.15 < (x["chunk_flops"] + x["chunk_state_flops"]) / (
        2 * 1024 * x["layer_params"]) < 0.25


def test_the_cell_resolves_to_its_readers():
    cell = cells.resolve(CELL)
    assert len(cell["per_layer"]) >= 37  # 32 at PR 50, 37 since PR 52
    assert cell["end_to_end"] == ["out_tok_s", "setup_s"]
    shares = [args["scopes"] for fn, args in cell["per_layer"].values()
              if fn is scopes.share_pct]
    assert shares.count(None) == 1
    given = [p for s in shares if s is not None for p in s]
    assert sorted(given) == sorted(CONFIG["trace"]["known_scopes"])
    # no declared prefix is a prefix of another, and none is swallowed by a
    # prefix an accepted entry takes elsewhere (`attn.`, `kv_pool.`, `moe.`)
    known = CONFIG["trace"]["known_scopes"]
    assert not [(a, b) for a in known for b in known
                if a != b and b.startswith(a)]
    assert not [k for k in known if k.startswith(("attn", "kv_pool", "moe"))]
    # eight at PR 50; since PR 56 the whole step's share is the shared
    # entry's, whose reader the configuration's `trace.roofline` names
    brm = [m for m in BENCH["per_layer"] if m["name"].startswith("brm_")]
    assert [m["name"] for m in brm] == [
        "brm_ret_step_roofline_pct", "brm_ret_chunk_roofline_pct",
        "brm_scope_ret_project_pct", "brm_scope_ret_chunk_pct",
        "brm_scope_ret_step_pct", "brm_scope_ret_out_pct",
        "brm_state_bytes_per_token"]
    assert CONFIG["trace"]["roofline"] == "brumby_roofline"
    for m in brm:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(HERE, "layers",
                                           m["name"] + ".json"))
    joined = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ()) and m not in brm]
    assert len(joined) >= 30  # 24 at PR 50, 29 since PR 52, 30 since PR 56
    assert {"srv_decode_step_roofline_pct", "scope_state_pool_pct",
            "srv_state_read_share", "srv_pad_positions_share"} <= set(joined)
    for m in BENCH["per_layer"]:
        # entries about K/V blocks, experts or routing are not joined
        if m["name"] in ("srv_kv_blocks_peak_pct", "scope_attn_pct",
                         "srv_attn_live_blocks_share", "scope_kv_pool_pct",
                         "scope_experts_pct", "scope_route_pct",
                         "srv_active_experts_per_layer"):
            assert CELL not in m["workloads"]


def test_a_program_without_the_counters_reads_nothing():
    """The parent has no `ret.*` scope and, for this model, no daemon at
    all: every new reader returns None and raises nothing."""
    facts = {"config": CONFIG, "metrics0": {}, "metrics1": {}, "trace": None,
             "peaks": None, "trace_capture": None, "client": {}}
    for name, (fn, args) in cells.resolve(CELL)["per_layer"].items():
        if name.startswith("brm_") or name in (
                "srv_decode_step_roofline_pct", "scope_state_pool_pct",
                "srv_state_read_share", "srv_pad_positions_share"):
            assert fn(facts, **args) is None, name


def _window(steps=100, slots=16, tokens=15.5):
    x = brumby_roofline.widths(CONFIG)
    state = 5 * slots * x["state_bytes"]
    m1 = {"step_steps_total": steps,
          "step_tokens_advanced_total": steps * tokens,
          "state_pool_bytes_read_total": steps * state,
          "state_pool_bytes_written_total": steps * state,
          "state_pool_kv_bytes_read_total": 0,
          "state_pool_installs_total": 5 * 30,  # 30 admissions, 45 chunks
          "state_pool_prefill_real_positions_total": 1100 * 30,
          "state_pool_prefill_pad_positions_total": 436 * 30}
    return {"config": CONFIG, "metrics0": dict.fromkeys(m1, 0.0),
            "metrics1": m1, "client": {}, "peaks": PEAKS,
            "trace": {"programs": {
                "jit_decode_step": {"count": 10, "mean_ms": 19.0},
                "jit_prefill_chunk": {"count": 4, "mean_ms": 40.0}}}}, state


def test_the_step_is_priced_from_the_counters():
    """A window of 100 steps at 16 slots: the step's least time is its
    bytes over the peak — 4.86 GB of layers and head, 5.75 GB of state:
    12.96 ms, at most ~1 235 tokens/s."""
    facts, state = _window()
    pct = brumby_roofline.decode_step_roofline_pct(
        facts, program="jit_decode_step")
    note = facts["notes"][-1]
    assert note["state_bytes"] == 2 * state
    assert round(note["weight_bytes"] / 1e9, 2) == 4.86
    least_ms = 1e3 * note["bytes"] / 819e9
    assert note["bound"] == "bandwidth" and 12.9 < least_ms < 13.0
    assert pct == pytest.approx(100 * least_ms / 19.0) and 0 < pct < 100
    assert note["state_bytes"] > note["weight_bytes"]  # the largest part
    per = cells.resolve(CELL)["per_layer"]
    fn, args = per["brm_state_bytes_per_token"]
    assert fn(facts, **args) == pytest.approx(2 * state / 15.5)
    fn, args = per["srv_state_read_share"]
    assert fn(facts, **args) == 1.0  # no K or V beside it
    fn, args = per["srv_pad_positions_share"]
    assert fn(facts, **args) == pytest.approx(436 / (436 + 1100))


def test_a_scoped_share_divides_by_its_scopes_time(monkeypatch):
    """The one-token rule and the chunked rule: least time over the device
    time under the scopes the file names, per execution of the program."""
    facts, state = _window()
    x = brumby_roofline.widths(CONFIG)
    spent = {}
    monkeypatch.setattr(
        brumby_roofline, "_spent_ms",
        lambda facts, program, inside, scopes: spent[tuple(scopes)])
    per = cells.resolve(CELL)["per_layer"]

    def read(name, ms):
        fn, args = per[name]
        spent[tuple(args["scopes"])] = ms
        return fn(facts, **args)

    got = read("brm_ret_step_roofline_pct", 10.0)
    assert got == pytest.approx(100 * 1e3 * 2 * state / 819e9 / 10.0)
    assert 65 < got < 75 and facts["notes"][-1]["bound"] == "bandwidth"
    got = read("brm_ret_chunk_roofline_pct", 12.0)
    assert facts["notes"][-1]["later_chunks_share"] == pytest.approx(1 / 3)
    least = 1e3 * 5 * (x["chunk_flops"] + x["chunk_state_flops"] / 3) / 197e12
    assert got == pytest.approx(100 * least / 12.0) and 0 < got < 100
    assert facts["notes"][-1]["bound"] == "compute"


def test_the_traffic_is_the_issues():
    t = TRAFFIC_FILE
    assert (t["kind"], t["generator"]) == ("backlog", "loadgen:Backlog")
    assert t["prompt_len"]["knots"] == [[0.0, 512], [0.5, 1024], [1.0, 2048]]
    lo, mid, hi = (k[1] for k in t["output_len"]["knots"])
    assert (lo, mid, hi, t["max_total"]) in ((192, 336, 576, 2624),
                                             (128, 224, 384, 2432))
    assert t["prompt_len"]["scale"] == t["output_len"]["scale"] == "log"
    assert (t["strata"], t["group"], t["layout_seed"], t["requests"]) == (
        16, 4, 50, 4000)
    slots = CONFIG["run"]["serve_flags"]["slots"]
    assert (t["outstanding"], t["anchor_index"]) == (2 * slots, slots - 1)
    assert t["reports"] == {"out_tok_s": "tok_s"} and t["ranges_why"]
    assert t["max_total"] <= CONFIG["run"]["serve_flags"]["max_len"]
    big = 2 ** 31 + 12345
    a = tg.make_requests(t, big, CONFIG["vocab_size"])
    b = tg.make_requests(t, big, CONFIG["vocab_size"])
    assert len(a) == t["requests"]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a[:40], b[:40]))
    assert all(512 <= r.prompt_len <= 2048 and lo <= r.max_new <= hi
               and r.prompt_len + r.max_new <= t["max_total"] for r in a)
    # ids from the whole vocabulary
    top = max(int(r.prompt.max()) for r in a[:200])
    assert 0.99 * CONFIG["vocab_size"] < top < CONFIG["vocab_size"]
    # every context is under a third of the length at which K and V would
    # weigh what the state does
    x = brumby_roofline.widths(CONFIG)
    assert t["max_total"] < x["state_bytes"] // 4096 / 3
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
