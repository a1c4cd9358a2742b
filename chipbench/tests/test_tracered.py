import gzip
import json
import os

import pytest

from chipbench import reducers, tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def synthetic():
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_decode_step(11)", 0, 100], ["jit_decode_step(11)", 150, 110],
            ["jit_prefill_chunk(12)", 300, 40]]},
        {"name": "XLA Ops", "events": [
            ["while.1", 0, 100],              # encloses the next two
            ["fusion.2", 0, 60], ["copy.3", 50, 50],
            ["fusion.2", 150, 110],
            ["collective-permute-start.4", 300, 10],
            ["collective-permute-done.5", 320, 20]]}]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [["jit_decode_step(11)", 0, 340]]},
        {"name": "XLA Ops", "events": [["fusion.2", 0, 340]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "worker", "events": [
        ["outer_loop", 0, 1000], ["jit_scatter", 95, 60]]}]}
    return {"planes": [dev0, dev1, host,
                       {"name": "/host:metadata", "lines": []}]}


def test_busy_is_the_union_of_operation_intervals():
    assert tracered.union_length([[0, 60], [50, 100], [150, 260]]) == 210
    t = tracered.reduce_trace(synthetic())
    assert t["n_devices"] == 2
    assert t["window_s"] == pytest.approx(340e-9)
    # device 0: [0,100) + [150,260) + [300,310) + [320,340) = 240; device 1: 340
    assert t["busy_s"] == pytest.approx((240 + 340) / 2 * 1e-9)


def test_per_program_means_and_counts():
    t = tracered.reduce_trace(synthetic())
    p = t["programs"]["jit_decode_step"]
    assert p["count"] == pytest.approx(3 / 2)       # mean over two devices
    assert p["mean_ms"] == pytest.approx((100 + 110 + 340) / 3 * 1e-6)
    assert t["programs"]["jit_prefill_chunk"]["mean_ms"] == pytest.approx(40e-6)
    facts = {"trace": t}
    assert reducers.trace_program_mean_ms(
        facts, program="jit_prefill_chunk") == pytest.approx(40e-6)
    assert reducers.trace_program_mean_ms(facts, program="absent") is None


def test_collective_permute_sum_and_idle_share():
    t = tracered.reduce_trace(synthetic())
    facts = {"trace": t}
    # 10 + 20 ns on one of two devices, per execution of jit_prefill_chunk
    # (0.5 a device): 15 ns / 0.5
    assert reducers.trace_ops_ms_per(
        facts, contains="collective-permute",
        per="jit_prefill_chunk") == pytest.approx(30e-6)
    assert reducers.trace_idle_pct(facts) == pytest.approx(
        100 * (1 - 290 / 340))


def test_enclosing_operations_keep_only_their_own_time():
    t = tracered.reduce_trace(synthetic())
    ops = dict((k, v) for k, v in t["device_ops"])
    assert ops["jit_decode_step|fusion.2"] == pytest.approx((60 + 110 + 340) / 2 * 1e-9)
    assert ops["jit_decode_step|copy.3"] == pytest.approx(50 / 2 * 1e-9)
    # the loop's 100 ns are all its children's (they overlap: never below 0)
    assert ops["jit_decode_step|while.1"] == 0.0


def test_idle_gaps_name_their_neighbours_and_the_host():
    t = tracered.reduce_trace(synthetic())
    gaps = dict((k, v) for k, v in t["idle_gaps"])
    assert gaps["jit_decode_step->jit_decode_step|jit_scatter"] == \
        pytest.approx(50 / 2 * 1e-9)


def test_no_device_operation_means_no_summary():
    assert tracered.reduce_trace({"planes": [
        {"name": "/host:CPU", "lines": []}]}) is None


def test_recorded_trace_from_the_chip():
    """250 ms of large-chat-saturated on the v5e (PR 23): five prefill
    chunks, two prefill finishes and the eager scatters between them."""
    # the fixture is `tracered.load_xplane(<capture>)` cut to 250 ms and
    # written with gzip + json.dump
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz"), "rt") as f:
        t = tracered.reduce_trace(json.load(f))
    assert t["n_devices"] == 1
    assert t["window_s"] == pytest.approx(0.249489067)
    assert t["busy_s"] == pytest.approx(0.179499464)
    p = t["programs"]
    assert p["jit_prefill_finish"]["count"] == 2
    assert p["jit_prefill_finish"]["mean_ms"] == pytest.approx(39.568, abs=1e-3)
    assert p["jit_prefill_chunk"]["count"] == 5
    assert p["jit_prefill_chunk"]["mean_ms"] == pytest.approx(12.732, abs=1e-3)
    facts = {"trace": t}
    assert reducers.trace_programs_ms_per(
        facts, programs=["jit_prefill_chunk", "jit_prefill_finish"],
        per="jit_prefill_finish") == pytest.approx(
            (5 * 12.732 + 2 * 39.568) / 2, abs=2e-3)
    assert len(t["device_ops"]) == 10 and len(t["idle_gaps"]) == 10
    # the dearest operations are whole-pool copies inside prefill_finish
    assert t["device_ops"][0][0].startswith("jit_prefill_finish|copy.")
    assert "bf16[36,1025,20,16,64]" in t["device_ops"][0][0]
    # and the longest idle time sits between eagerly dispatched scatters
    assert t["idle_gaps"][0][0].startswith("jit_scatter->jit_scatter|")


def test_op_name_keeps_name_and_shape_only():
    assert tracered.op_name(
        "%copy.53 = bf16[1,1025,20,16,64]{4,2,3,1,0:T(8,128)} copy(bf16[1] "
        "%collective-permute-done.1)") == "copy.53 bf16[1,1025,20,16,64]"
    assert tracered.op_name("jit_decode_step(12)") == "jit_decode_step(12)"
    assert tracered.program_name("jit_decode_step(12)") == "jit_decode_step"
