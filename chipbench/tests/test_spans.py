import gzip
import json
import os

import pytest

from chipbench import spans, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def recorded():
    """0.78 s of a `large-chat-saturated` capture on the v5e (six decode
    steps and one admission), in the plain lists of `spans.load_capture`."""
    with gzip.open(os.path.join(HERE, "recorded_spans.json.gz"), "rt") as f:
        return json.load(f)


def as_trace(capture):
    """The same operations in `tracered.reduce_trace`'s form."""
    return {"planes": [
        {"name": d["name"], "lines": [{"name": tracered.OPS_LINE, "events": [
            [str(scope), start, dur] for start, dur, scope in d["ops"]]}]}
        for d in capture["devices"]]}


def synthetic():
    """Device busy [0,100) [150,200) [260,300); idle [100,150) [200,260)."""
    ops = [[0, 100, "layers.scan"], [10, 40, "attn.paged_decode"],
           [60, 30, None], [150, 50, "kv_pool.write"], [260, 40, None]]
    host = [["step", 0, 120, {"step": 7}],
            ["step.dispatch", 0, 90, {"step": 7}],
            ["step.wait", 90, 20, {"step": 7}],      # idle 100..110
            # 110..120 is the step's own; 120..130 is outside
            ["admit", 130, 100, {"prompt_len": 9}],  # idle 130..150, 200..230
            ["admit.install", 205, 15, {"rid": 3}],  # idle 205..220
            ["step", 240, 60, {"step": 8}],          # idle 240..260
            ["step.host", 240, 10, {"step": 8}]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops}], "spans": host}


def test_a_gap_straddling_two_spans_is_divided_by_overlap():
    idle = spans.idle_by_span(synthetic())
    assert idle["window_s"] == pytest.approx(300e-9)
    assert idle["idle_s"] == pytest.approx(110e-9)
    by = {k: round(v * 1e9) for k, v in idle["by"].items() if v}
    # gap [100,150): wait 10, the step's own 10, outside 10, admit 20
    # gap [200,260): admit 5 + 10 (around its child), install 15,
    #                outside 10 (230..240), step.host 10, step 10
    assert by == {"step.wait": 10, "step": 20, "outside": 20,
                  "admit": 35, "admit.install": 15, "step.host": 10}
    assert sum(by.values()) == 110
    facts = {"spans_capture": synthetic()}
    assert spans.idle_pct(facts, under="admit") == pytest.approx(100 * 50 / 300)
    assert spans.idle_pct(facts, under="step") == pytest.approx(100 * 40 / 300)
    assert spans.idle_pct(facts, under="outside") == pytest.approx(
        100 * 20 / 300)
    assert spans.idle_pct(facts, under="admit.install") == pytest.approx(5.0)


def test_idle_shares_sum_to_the_idle_share_of_reduce_trace():
    cap = recorded()
    t = tracered.reduce_trace(as_trace(cap))
    old = 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    facts = {"spans_capture": cap}
    parts = [spans.idle_pct(facts, under=u)
             for u in ("admit", "step", "outside")]
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(old, abs=1e-9)
    assert 5.0 < old < 15.0          # a real capture: ~10 % idle
    assert parts[0] > parts[2]       # admission leaves more idle than
    # the worker between calls does
    by = spans.idle_by_span(cap)["by"]
    assert by["admit.install"] > by["admit.prefill"]


def test_recorded_spans_nest_and_carry_their_stats():
    cap = recorded()
    names = [s[0] for s in cap["spans"]]
    assert names.count("step") == 6 and names.count("admit") == 1
    steps = [s for s in cap["spans"] if s[0] == "step"]
    for st in steps:
        kids = [s for s in cap["spans"] if s[0].startswith("step.")
                and st[1] <= s[1] and s[1] + s[2] <= st[1] + st[2]]
        assert [k[0] for k in kids] == ["step.host", "step.dispatch",
                                        "step.wait", "step.commit",
                                        "step.obs"]
        assert {k[3]["step"] for k in kids} == {st[3]["step"]}
    (admit,) = [s for s in cap["spans"] if s[0] == "admit"]
    parts = [s for s in cap["spans"] if s[0].startswith("admit.")]
    assert [p[0] for p in parts] == ["admit.prefill", "admit.first_token",
                                     "admit.install"]
    assert len({p[3]["rid"] for p in parts}) == 1
    assert admit[3]["prompt_len"] > 0


def test_scope_shares_sum_to_100_with_the_unscoped_share():
    for cap in (synthetic(), recorded()):
        facts = {"spans_capture": cap}
        groups = (["attn."], ["kv_pool."], ["layers.scan"],
                  ["gpt.", "sample"], None)
        shares = [spans.scope_share_pct(facts, scopes=g) for g in groups]
        assert sum(shares) == pytest.approx(100.0)
    # synthetic: the loop keeps 100 - 40 - 30 of its own; busy 190
    facts = {"spans_capture": synthetic()}
    assert spans.scope_share_pct(facts, scopes=["layers.scan"]) == \
        pytest.approx(100 * 30 / 190)
    assert spans.scope_share_pct(facts, scopes=["attn."]) == \
        pytest.approx(100 * 40 / 190)
    assert spans.scope_share_pct(facts, scopes=None) == \
        pytest.approx(100 * 70 / 190)
    # the recorded step: the pool's slices in the layer loop lead
    facts = {"spans_capture": recorded()}
    assert spans.scope_share_pct(facts, scopes=["layers.scan"]) > 40.0
    assert 5.0 < spans.scope_share_pct(facts, scopes=["attn."]) < 15.0


def test_scope_of_takes_the_innermost_known_component():
    assert spans.scope_of(
        "jit(decode_step)/layers.scan/while/body/closed_call/gpt.block.attn/"
        "attn.paged_decode/paged_decode_attention/pallas_call:") == \
        "attn.paged_decode"
    # a name no cell's program writes (`weights.cast`, since PR 27) is not
    # a scope of its own: the operation belongs to the block it serves
    assert spans.scope_of(
        "jit(f)/layers.scan/while/body/closed_call/gpt.block.mlp/"
        "weights.cast/convert_element_type:") == "gpt.block.mlp"
    assert spans.scope_of("jit(decode_step)/layers.scan/while/body/"
                          "dynamic_slice:") == "layers.scan"
    assert spans.scope_of("jit(decode_step)/while/body/dynamic_slice:") is None
    assert spans.scope_of(None) is None


def test_a_capture_without_spans_or_operations_reads_none():
    """A program that predates the spans (the parent commit): the idle
    readers return None and the harness leaves the metrics out. Without a
    scope name its operations are unscoped, which is what they are."""
    cap = synthetic()
    bare = {"devices": [{"name": d["name"],
                         "ops": [[s, n, None] for s, n, _ in d["ops"]]}
                        for d in cap["devices"]], "spans": []}
    facts = {"spans_capture": bare}
    assert spans.idle_pct(facts, under="admit") is None
    assert spans.scope_share_pct(facts, scopes=None) == pytest.approx(100.0)
    assert spans.scope_share_pct(facts, scopes=["attn."]) == 0.0
    assert spans.idle_pct({"trace_capture": None}, under="step") is None
    assert spans.scope_share_pct({}, scopes=["attn."]) is None
    assert spans.scope_share_pct(
        {"spans_capture": {"devices": [{"name": "/device:TPU:0", "ops": []}],
                           "spans": []}}, scopes=None) is None


def test_only_the_worker_threads_line_is_nested():
    """Spans nest by time within one thread: of the host lines the one
    with the most `step` spans is kept, whole, and no other."""
    worker = synthetic()["spans"]
    other = [["admit", 100, 50, {"prompt_len": 4}],   # a control-path submit
             ["step", 110, 20, {"step": 0}]]
    assert spans.worker_line([other, worker, []]) == worker
    assert spans.worker_line([[], []]) == []
    assert spans.worker_line([]) == []


def _metrics(steps, tokens, phases, parts, wait_sum, wait_n):
    m = {"step_steps_total": steps, "step_tokens_advanced_total": tokens,
         "serving_queue_wait_seconds_sum": wait_sum,
         "serving_queue_wait_seconds_count": wait_n}
    m.update({f'step_phase_seconds_total{{phase="{p}"}}': v
              for p, v in phases.items()})
    m.update({f'step_admit_seconds_total{{part="{p}"}}': v
              for p, v in parts.items()})
    return m


def counter_facts():
    phases0 = dict(admit=10.0, host=1.0, dispatch=2.0, wait=50.0,
                   commit=3.0, obs=1.0)
    phases1 = dict(admit=16.0, host=1.5, dispatch=3.0, wait=80.0,
                   commit=4.0, obs=1.5)
    parts0 = dict(self=1.0, prefill=2.0, first_token=6.0, install=1.0)
    parts1 = dict(self=1.5, prefill=3.0, first_token=10.0, install=1.5)
    return {
        "metrics0": _metrics(100, 1500, phases0, parts0, 20.0, 10),
        "metrics1": dict(_metrics(500, 7100, phases1, parts1, 50.0, 25),
                         dnn_tpu_boot_ready_total_seconds=19.4),
        "config": {"run": {"serve_flags": {"slots": 16}}}}


def test_counter_readers_take_window_differences():
    facts = counter_facts()
    # host 0.5 + commit 1 + obs 0.5 + admit self 0.5 + install 0.5 = 3
    # over 6 + 0.5 + 1 + 30 + 1 + 0.5 = 39
    assert spans.pure_host_share_pct(facts) == pytest.approx(100 * 3 / 39)
    assert spans.occupancy_win_pct(facts) == pytest.approx(
        100 * 5600 / (400 * 16))
    assert spans.queue_wait_ms(facts) == pytest.approx(2000.0)
    assert spans.gauge_at_end(
        facts, series="dnn_tpu_boot_ready_total_seconds") == 19.4


@pytest.mark.parametrize("reader,series", [
    (spans.pure_host_share_pct, 'step_admit_seconds_total{part="install"}'),
    (spans.pure_host_share_pct, 'step_phase_seconds_total{phase="obs"}'),
    (spans.occupancy_win_pct, "step_tokens_advanced_total"),
    (spans.occupancy_win_pct, "step_steps_total"),
    (spans.queue_wait_ms, "serving_queue_wait_seconds_sum"),
    (spans.queue_wait_ms, "serving_queue_wait_seconds_count"),
])
def test_a_counter_reader_returns_none_when_a_series_is_missing(reader,
                                                                series):
    for page in ("metrics0", "metrics1"):
        facts = counter_facts()
        del facts[page][series]
        assert reader(facts) is None
    assert spans.gauge_at_end(counter_facts(), series="absent") is None
    assert reader({"metrics0": None, "metrics1": None,
                   "config": counter_facts()["config"]}) is None


def test_every_new_layer_file_names_a_reader_here():
    import inspect

    layers = os.path.join(os.path.dirname(HERE), "layers")
    named, grouped = 0, []
    for name in sorted(os.listdir(layers)):
        with open(os.path.join(layers, name)) as f:
            spec = json.load(f)
        module, _, fn = spec["reducer"].rpartition(":")
        if module != "spans":
            continue
        named += 1
        reader = getattr(spans, fn)
        accepted = set(inspect.signature(reader).parameters) - {"facts"}
        assert set(spec.get("args", {})) == accepted, name
        if fn == "scope_share_pct":
            grouped += spec["args"]["scopes"] or ["unscoped"]
    assert named >= 21
    # each scope name is in one group, so the scope shares sum to 100
    assert sorted(grouped) == sorted(spans.SCOPES + ("unscoped",))
