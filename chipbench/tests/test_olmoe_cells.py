"""What PR 26 added beside the files that were there: the scope reader
with prefixes a family brings (since PR 39 in its configuration's file),
the one-sequence-at-a-time check, the counter ratios and the experts'
roofline arithmetic, and the two new cells end to end at rehearsal size
(these two start a daemon on the CPU; `rehearse.sh`, which cannot be
edited, runs them as well)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import cells, peaks, scopes, spans
from chipbench.tests.test_spans import recorded

REPO = cells.REPO


def test_scopes_reproduce_spans_shares_for_gpt2s_prefixes():
    """On the recorded GPT-2 capture, with `spans.SCOPES` as the
    recognised prefixes, each of the benchmark's `sat_scope_*` shares
    comes out the same from `scopes.share_pct`; they add up to 100."""
    config = {"trace": {"known_scopes": list(spans.SCOPES)}}
    total = 0.0
    for name in ("attn", "kv_pool", "layer_scan", "model", "unscoped"):
        with open(os.path.join(cells.HERE, "layers",
                               f"sat_scope_{name}_pct.json")) as f:
            want_scopes = json.load(f)["args"]["scopes"]
        want = spans.scope_share_pct({"spans_capture": recorded()},
                                     scopes=want_scopes)
        got = scopes.share_pct(
            {"scopes_capture": recorded(), "config": config},
            scopes=want_scopes)
        assert got == pytest.approx(want, rel=1e-12)
        total += got
    assert total == pytest.approx(100.0)


def test_scope_is_the_innermost_recognised_component():
    known = ["moe.experts", "moe.route", "llama.", "layers.scan"]
    name = ("jit(decode_step)/layers.scan/while/body/llama.block.mlp/"
            "moe.experts/ragged_dot")
    assert scopes.scope_of(name, known) == "moe.experts"
    assert scopes.scope_of(name, ["llama.", "layers.scan"]) == \
        "llama.block.mlp"
    assert scopes.scope_of(name, list(spans.SCOPES)) == "layers.scan"
    assert scopes.scope_of("ragged-dot-metadata", known) is None
    assert scopes.scope_of(None, known) is None


def _moe_facts():
    ops = [[0, 1000, "jit(decode_step)/layers.scan/while"],
           [100, 300, "jit(decode_step)/layers.scan/while/body/"
                      "llama.block.mlp/moe.experts/ragged_dot"],
           [400, 100, "jit(decode_step)/layers.scan/while/body/"
                      "llama.block.mlp/moe.route/top_k"],
           [1200, 500, "jit(prefill_chunk)/while/body/llama.block.mlp/"
                       "moe.experts/ragged_dot"],
           [1700, 100, None]]
    series = {name: f'moe_{name}_total{{program="decode"}}' for name in
              ("layer_calls", "assignments", "active_experts",
               "peak_expert_rows")}
    m1 = {series["layer_calls"]: 6.0, series["assignments"]: 768.0,
          series["active_experts"]: 330.0, series["peak_expert_rows"]: 36.0}
    with open(os.path.join(
            REPO, "chipbench/configs/olmoe-1b-7b-1chip.json")) as f:
        config = json.load(f)
    return {"scopes_capture": {"devices": [{"name": "/device:TPU:0",
                                            "ops": ops}]},
            "trace": {"programs": {"jit_decode_step": {"count": 2}}},
            "peaks": peaks.PEAKS["TPU v5e"], "config": config,
            "metrics0": dict.fromkeys(m1, 0.0), "metrics1": m1}


def test_counter_ratios_and_roofline_arithmetic():
    facts = _moe_facts()  # the prefixes: the configuration's own
    assert scopes.share_pct(facts, scopes=["moe.experts"]) == \
        pytest.approx(100 * 800 / 1600)
    assert scopes.share_pct(facts, scopes=None) == \
        pytest.approx(100 * 100 / 1600)
    assert scopes.counter_ratio(
        facts, num='moe_active_experts_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}') == 55.0
    assert scopes.counter_ratio(
        facts, num='moe_peak_expert_rows_total{program="decode"}',
        den='moe_assignments_total{program="decode"}',
        times_config="num_experts") == pytest.approx(3.0)
    # two steps of three layers: 384 rows and 165 active experts a step
    least = scopes.experts_least_s(facts["config"], rows=384.0,
                                   active_experts=165.0,
                                   peaks=facts["peaks"])
    assert least["flops"] == 384 * 6 * 2048 * 1024
    assert least["bytes"] == 165 * 3 * 2048 * 1024 * 2 + 2 * 384 * 2048 * 2
    assert least["bound"] == "bandwidth"
    # 300 ns under moe.experts inside jit(decode_step), two executions
    assert scopes.experts_roofline_pct(
        facts, program="jit_decode_step", inside="jit(decode_step)/",
        scope="moe.experts", label="decode") == pytest.approx(
        100 * least["least_s"] / 150e-9)
    # a program that predates the counters: nothing to read, no error
    facts["metrics1"] = facts["metrics0"] = {}
    assert scopes.experts_roofline_pct(
        facts, program="jit_decode_step", inside="jit(decode_step)/",
        scope="moe.experts", label="decode") is None
    assert scopes.counter_ratio(facts, num="a", den="b") is None


def test_check_served_one_sequence_at_a_time_equals_the_batched_check():
    import jax

    from chipbench import check, serve_rows

    cfg, params = check.init_params("olmoe-test", 3)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 17, 30)]
    tokens = [list(rng.randint(0, cfg.vocab_size, n)) for n in (4, 6, 3)]
    want = check.served_margins("olmoe", cfg, params, prompts, tokens)
    got = serve_rows.served_margins("olmoe", cfg, params, prompts, tokens)
    assert got.keys() == want.keys()
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-5, abs=1e-6), k
    assert got["positions"] == 13 and got["longest_context"] == 33
    jax.clear_caches()


@pytest.mark.parametrize("workload,metric,driver", [
    ("large-chat-bursty", "out_tok_s", "serve"),
    ("olmoe-chat-saturated", "out_tok_s", "serve_rows")])
def test_new_cells_resolve_and_rehearse(workload, metric, driver):
    cell = cells.resolve(workload)
    assert cell["config"]["run"]["driver"] == driver
    assert metric in cell["end_to_end"] and "setup_s" in cell["end_to_end"]
    assert cell["traffic"]["reports"].keys() == {metric}
    assert cell["per_layer"], "the cell reports per-layer metrics"
    for trace in ("0", "1"):
        done = subprocess.run(
            [sys.executable, "chipbench/run.py", "--workload", workload,
             "--seed", "2147483659", "--seconds", "4", "--trace", trace,
             "--rehearse"], cwd=REPO, capture_output=True, text=True,
            timeout=600)
        assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
        last = json.loads(done.stdout.strip().splitlines()[-1])
        assert last["rehearsal"] and last["correct"] and not last["failed"]
