"""ISSUE 14: the SLO observatory — workload suite, verdict engine,
incident forensics, perf ledger.

Covers: seeded arrival-process determinism (golden schedules — same
seed must yield bit-identical times on any host), scenario-script
determinism and the chat scenario's shared-prefix property, SLO-verdict
arithmetic goldens, incident-bundle write + CLI render + rejection of
non-bundles, ledger parsing against the REAL checked-in BENCH_r*.json
files and the committed RESULTS.md, centralized ratchet arithmetic,
one green end-to-end scenario (chat, with the new prefix hit/miss
counters live), the chaos-injected breach whose bundle is asserted by
READING IT BACK off disk, and the prefix-cache counter/gauge satellite
in serving.py.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dnn_tpu.workloads.arrivals import (
    bursty_arrivals,
    diurnal_envelope,
    poisson_arrivals,
    uniform,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
# arrival processes: determinism is the contract
# ----------------------------------------------------------------------

def test_poisson_golden_schedule():
    """Same seed -> bit-identical arrival times, pinned against golden
    values (blake2s is stable across hosts and Python builds — a drift
    here means the determinism contract broke, not 'noise')."""
    a = poisson_arrivals(5.0, 4.0, seed=0)
    assert a == poisson_arrivals(5.0, 4.0, seed=0)
    assert len(a) == 21
    assert a[:4] == pytest.approx(
        [0.025864017292173185, 0.24144824739888726,
         0.3994130287519928, 0.48353397469164183], rel=1e-12)
    assert a == sorted(a) and all(0 <= t < 4.0 for t in a)
    assert poisson_arrivals(5.0, 4.0, seed=1) != a
    # distinct stream names never collide on one seed
    assert poisson_arrivals(5.0, 4.0, seed=0, name="other") != a


def test_poisson_validation():
    with pytest.raises(ValueError, match="rate_hz"):
        poisson_arrivals(0.0, 1.0, seed=0)
    with pytest.raises(ValueError, match="duration_s"):
        poisson_arrivals(1.0, -1.0, seed=0)


def test_bursty_golden_and_envelope_shape():
    b = bursty_arrivals(2.0, 10.0, seed=3, burst_factor=4.0,
                        period_s=10.0)
    assert b == bursty_arrivals(2.0, 10.0, seed=3, burst_factor=4.0,
                                period_s=10.0)
    assert len(b) == 32
    assert b[:3] == pytest.approx(
        [0.369734448695517, 0.4842764264016752, 0.809700084941519],
        rel=1e-12)
    assert b == sorted(b) and all(0 <= t < 10.0 for t in b)
    # the raised-cosine envelope peaks mid-period: the peak quarter
    # must be denser than the trough quarter (deterministic, so this
    # is a schedule property, not a statistical hope)
    trough = sum(1 for t in b if t < 2.5)
    peak = sum(1 for t in b if 3.75 <= t < 6.25)
    assert peak > trough, (peak, trough)


def test_diurnal_envelope_bounds():
    assert diurnal_envelope(0.0, 20.0, burst_factor=4.0) == \
        pytest.approx(1.0)
    assert diurnal_envelope(10.0, 20.0, burst_factor=4.0) == \
        pytest.approx(4.0)
    with pytest.raises(ValueError, match="period_s"):
        diurnal_envelope(1.0, 0.0)
    with pytest.raises(ValueError, match="burst_factor"):
        diurnal_envelope(1.0, 10.0, burst_factor=0.5)


def test_uniform_is_pure():
    assert uniform(7, "x", 0) == pytest.approx(0.8111295317148418,
                                               rel=1e-15)
    assert uniform(7, "x", 0) == uniform(7, "x", 0)
    assert uniform(7, "x", 1) != uniform(7, "x", 0)
    assert 0.0 <= uniform(7, "x", 1) < 1.0


# ----------------------------------------------------------------------
# scenario scripts: pure functions of the seed
# ----------------------------------------------------------------------

def _script_fingerprint(reqs):
    """Comparable view of a script (constraint objects are fresh
    instances per call — compare their presence, not identity)."""
    return [(round(r.at, 9), r.prompt.tobytes(), r.max_new, r.client,
             r.seed, sorted((r.opts or {}).keys()))
            for r in reqs]


def test_scenario_scripts_deterministic():
    from dnn_tpu.workloads.scenarios import SCENARIOS, get_scenario

    for name in sorted(SCENARIOS):
        sc = get_scenario(name, light=True)
        a = _script_fingerprint(sc.script(0))
        assert a == _script_fingerprint(sc.script(0)), name
        assert a != _script_fingerprint(sc.script(1)), name
        assert a, f"{name} produced an empty script"


def test_chat_script_shares_system_prefix():
    """The chat scenario's whole point: same-tenant turns share a
    chunk-aligned system prefix (the prefix cache's hit traffic),
    different tenants don't."""
    from dnn_tpu.workloads.scenarios import (
        PROMPT_PAD,
        _SYSTEM_CHUNKS,
        get_scenario,
    )

    sc = get_scenario("chat", light=True)
    reqs = sc.script(0)
    sys_len = _SYSTEM_CHUNKS * PROMPT_PAD
    by_tenant = {}
    for r in reqs:
        tenant = int(r.client[1:]) % 2
        by_tenant.setdefault(tenant, []).append(
            r.prompt[:sys_len].tobytes())
    for tenant, prefixes in by_tenant.items():
        assert len(set(prefixes)) == 1, f"tenant {tenant} prefix drifted"
    assert len(by_tenant) == 2
    t0, t1 = (v[0] for v in by_tenant.values())
    assert t0 != t1, "tenants must have distinct system prompts"
    for r in reqs:
        assert len(r.prompt) > sys_len  # every turn adds its own tail


def test_unknown_scenario_fails_loud():
    from dnn_tpu.workloads.scenarios import get_scenario

    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("nope")


# ----------------------------------------------------------------------
# SLO verdict arithmetic
# ----------------------------------------------------------------------

def _recs():
    return [
        {"i": 0, "t": 0.0, "outcome": "ok", "tokens": 4,
         "ttft_s": 0.1, "itl_s": [0.05, 0.05, 0.05], "t_done": 0.3},
        {"i": 1, "t": 0.5, "outcome": "ok", "tokens": 4,
         "ttft_s": 0.9, "itl_s": [0.2], "t_done": 1.4},
        {"i": 2, "t": 1.0, "outcome": "rejected", "tokens": 0,
         "ttft_s": None, "itl_s": [], "t_done": 1.1},
    ]


def test_slo_verdict_golden():
    from dnn_tpu.obs.slo import SLOSpec, evaluate

    rep = evaluate("g", _recs(),
                   SLOSpec(ttft_s=1.0, itl_s=0.5, availability=0.9),
                   wall_s=2.0)
    by = {o["name"]: o for o in rep.objectives}
    # nearest-rank p95 of [0.1, 0.9] is 0.9; of the 4 itl samples, 0.2
    assert by["ttft_p95"]["measured"] == pytest.approx(0.9)
    assert by["ttft_p95"]["ok"]
    assert by["itl_p95"]["measured"] == pytest.approx(0.2)
    assert by["itl_p95"]["ok"]
    assert by["availability"]["measured"] == pytest.approx(2 / 3)
    assert not by["availability"]["ok"]
    assert by["lost"]["ok"]
    assert rep.goodput_tps == pytest.approx(8 / 2.0)
    assert not rep.ok
    # the breach window anchors on the bad records' completion times,
    # mapped onto the epoch axis when t0 is given
    rep2 = evaluate("g", _recs(), SLOSpec(availability=0.9),
                    wall_s=2.0, t0_epoch=1000.0)
    assert rep2.breach_window == pytest.approx((1001.1, 1001.1))


def test_slo_declared_ttft_with_no_completions_fails():
    from dnn_tpu.obs.slo import SLOSpec, evaluate

    recs = [{"i": 0, "t": 0.0, "outcome": "rejected", "tokens": 0,
             "ttft_s": None, "itl_s": [], "t_done": 0.1}]
    rep = evaluate("g", recs, SLOSpec(ttft_s=1.0), wall_s=1.0)
    by = {o["name"]: o for o in rep.objectives}
    assert not by["ttft_p95"]["ok"]   # declared objective, zero data
    assert not rep.ok


def test_slo_lost_asserts_zero_even_without_availability():
    from dnn_tpu.obs.slo import SLOSpec, evaluate

    recs = [{"i": 0, "t": 0.0, "outcome": None, "tokens": 0,
             "ttft_s": None, "itl_s": [], "t_done": None}]
    rep = evaluate("g", recs, SLOSpec(), wall_s=1.0)
    assert not rep.ok
    assert {o["name"]: o["ok"] for o in rep.objectives}["lost"] is False


def test_slo_goodput_floor():
    from dnn_tpu.obs.slo import SLOSpec, evaluate

    rep = evaluate("g", _recs(), SLOSpec(goodput_floor_tps=10.0),
                   wall_s=2.0)
    by = {o["name"]: o for o in rep.objectives}
    assert by["goodput_tps"]["measured"] == pytest.approx(4.0)
    assert not by["goodput_tps"]["ok"] and not rep.ok
    assert evaluate("g", _recs(), SLOSpec(goodput_floor_tps=3.0),
                    wall_s=2.0).ok
    with pytest.raises(ValueError, match="wall_s"):
        evaluate("g", _recs(), SLOSpec(), wall_s=0.0)


# ----------------------------------------------------------------------
# incident bundles: write, read BACK, render, reject garbage
# ----------------------------------------------------------------------

def test_incident_bundle_roundtrip_and_cli(tmp_path, capsys):
    from dnn_tpu.obs.flight import FlightRecorder
    from dnn_tpu.obs.slo import (
        SLOSpec,
        evaluate,
        load_incident,
        render_incident,
        write_incident_bundle,
    )

    fr = FlightRecorder(capacity=64)
    import time as _t

    now = _t.time()
    fr.record("chaos_inject", fault="step_fault", n=2)
    fr.record("worker_died", requeue=True)
    rep = evaluate("synthetic", _recs(), SLOSpec(availability=0.99),
                   wall_s=2.0, t0_epoch=now - 1.1)  # bad t_done -> now
    assert not rep.ok and rep.breach_window is not None
    d = str(tmp_path / "bundle")
    write_incident_bundle(d, rep, flight=fr, records=_recs())
    # read the ARTIFACT back — the assertion the acceptance demands
    b = load_incident(d)
    assert b["manifest"]["report"]["ok"] is False
    kinds = [e["kind"] for e in b["flight"]]
    assert "chaos_inject" in kinds and "worker_died" in kinds
    text = render_incident(b)
    assert "SLO BREACH" in text and "chaos_inject" in text
    assert "availability" in text
    # the CLI renders the same bundle
    from dnn_tpu.obs.__main__ import main as obs_main

    rc = obs_main(["incident", d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "SLO BREACH" in out and "worker_died" in out
    rc = obs_main(["incident", d, "--json"])
    assert rc == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["manifest"]["report"]["scenario"] == "synthetic"


def test_incident_bundle_rejects_non_bundle(tmp_path):
    from dnn_tpu.obs.slo import load_incident

    with pytest.raises(ValueError, match="not an incident bundle"):
        load_incident(str(tmp_path))
    (tmp_path / "manifest.json").write_text('{"kind": "other"}')
    with pytest.raises(ValueError, match="not an incident manifest"):
        load_incident(str(tmp_path))


def test_incident_bundle_ok_report_snapshot(tmp_path):
    """A non-breach report still snapshots (the runner only writes on
    breach, but the writer itself must not assume one — the whole ring
    lands when there is no window to filter to)."""
    from dnn_tpu.obs.flight import FlightRecorder
    from dnn_tpu.obs.slo import (
        SLOSpec,
        evaluate,
        load_incident,
        write_incident_bundle,
    )

    fr = FlightRecorder(capacity=8)
    fr.record("admit", rid=1)
    rep = evaluate("ok-case", _recs()[:2], SLOSpec(availability=0.5),
                   wall_s=2.0)
    assert rep.ok
    d = str(tmp_path / "b2")
    write_incident_bundle(d, rep, flight=fr)
    b = load_incident(d)
    assert b["manifest"]["report"]["ok"] is True
    assert [e["kind"] for e in b["flight"]] == ["admit"]


# ----------------------------------------------------------------------
# ledger: the real checked-in artifacts parse
# ----------------------------------------------------------------------

def test_ledger_parses_real_bench_rounds(tmp_path):
    import shutil

    from benchmarks.ledger import bench_rounds

    rounds = bench_rounds(REPO)
    nums = [e["round"] for e in rounds]
    assert nums == sorted(nums) and len(nums) >= 3
    r3 = next(e for e in rounds if e["round"] == 3)
    assert isinstance(r3["value"], (int, float))
    assert r3["substrate"] == "cpu"
    r5 = next(e for e in rounds if e["round"] == 5)
    assert r5["substrate"] == "cpu" and r5["stale_tpu_reference"]
    # a round that crashed before printing a row (the driver's shape for
    # it: rc != 0, no parseable line in `tail`) stays in the trajectory,
    # honestly marked — beside a real committed round
    shutil.copy(os.path.join(REPO, "BENCH_r05.json"), tmp_path)
    (tmp_path / "BENCH_r06.json").write_text(json.dumps(
        {"n": 6, "cmd": "python bench.py", "rc": 1,
         "tail": "RuntimeError: backend failed to initialise\n",
         "parsed": None}))
    r5b, r6 = bench_rounds(str(tmp_path))
    assert r5b == r5
    assert r6["metric"] is None and r6["substrate"] == "no row (rc=1)"


def test_ledger_run_rows_parse_results_md():
    from benchmarks.ledger import run_rows

    rows = run_rows(state_path=os.path.join(REPO, "does-not-exist"),
                    results_path=os.path.join(REPO, "benchmarks",
                                              "RESULTS.md"))
    by = {r["config"]: r for r in rows}
    assert "gpt2_fwd" in by
    assert isinstance(by["gpt2_fwd"]["value"], float)
    # the detail-cell k=v extraction the ratchets read
    assert by["obs_overhead"]["ok"] is True


def test_ledger_ratchet_arithmetic():
    from benchmarks.ledger import Ratchet, check_ratchets

    rows = [{"config": "decode_mbu", "value": 27.0},
            {"config": "step_timeline", "value": 12.0},
            {"config": "workload_chat", "ok": True}]
    by = {v["ratchet"]: v for v in check_ratchets(rows)}
    assert by["decode_mbu_floor"]["status"] == "ok"
    assert by["decode_mbu_floor"]["threshold"] == pytest.approx(10.0)
    assert by["host_fraction_ceiling"]["status"] == "ok"
    assert by["workload_chat"]["status"] == "ok"
    assert by["chaos_availability_floor"]["status"] == "missing"
    # a regression FAILS — the centralized assert is real
    assert Ratchet(
        "x", "decode_mbu", "value", ">=", lambda: 10.0).evaluate(
        [{"config": "decode_mbu", "value": 5.0}])["status"] == "FAIL"
    assert Ratchet(
        "x", "step_timeline", "value", "<=", lambda: 40.0).evaluate(
        [{"config": "step_timeline", "value": 55.0}])["status"] == "FAIL"


def test_ledger_cli_runs_green_on_checked_in_artifacts():
    """The CLI over the REAL repo state: parses, renders, exits 0
    (missing rows are reported, not failed, without --strict)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "ledger.py"),
         "--assert"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "Perf trajectory" in proc.stdout
    assert "| r03 " in proc.stdout


def test_run_all_scenarios_filter_rejects_unknown():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run_all.py"),
         "--scenarios", "not_a_scenario"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode != 0
    assert "unknown scenario" in (proc.stderr + proc.stdout)


# ----------------------------------------------------------------------
# prefix-cache counters + gauge (the serving.py satellite)
# ----------------------------------------------------------------------

def test_prefix_counters_and_hit_ratio_gauge():
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=24,
                            prompt_pad=4, prefix_cache=2)
    p = np.arange(1, 9, dtype=np.int32)  # 2 full chunks
    srv.submit(p, max_new_tokens=2)
    srv.drain()
    assert (srv.prefix_hits, srv.prefix_misses) == (0, 1)
    assert srv._prefix_ratio_read() == 0.0
    srv.submit(p, max_new_tokens=2)  # identical prompt: full-chunk hit
    srv.drain()
    assert (srv.prefix_hits, srv.prefix_misses) == (1, 1)
    assert srv._prefix_ratio_read() == pytest.approx(0.5)
    # the gauge is registered (weakly) under the public name
    assert "dnn_tpu_prefix_hit_ratio" in srv._obs_gauges
    assert srv._obs_gauges["dnn_tpu_prefix_hit_ratio"]() == \
        pytest.approx(0.5)
    # capacity 2: a different 2-chunk prompt's inserts evict
    before = srv.prefix_evictions
    srv.submit(np.arange(20, 28, dtype=np.int32), max_new_tokens=2)
    srv.drain()
    assert srv.prefix_evictions > before
    # the registry counters moved with the attrs
    from dnn_tpu import obs

    m = obs.metrics()
    if m is not None:
        snap = m.snapshot()["counters"]
        assert snap.get("serving.prefix_misses_total", 0) >= 1
        assert snap.get("serving.prefix_evictions_total", 0) >= 1


def test_prefix_ratio_gauge_absent_without_cache():
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=1, max_len=16,
                            prompt_pad=4)
    assert "dnn_tpu_prefix_hit_ratio" not in srv._obs_gauges


# ----------------------------------------------------------------------
# end to end: one green scenario, one asserted breach
# ----------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_chat_scenario_green_end_to_end():
    """The light chat scenario through the real runner + in-process
    LMServer: verdict ok, nothing lost, prefix cache measurably hit,
    live burn-rate gauges ride the report."""
    from dnn_tpu import obs
    from dnn_tpu.workloads import get_scenario, run_scenario

    obs.set_enabled(True)  # flight/burn-rate surfaces are part of what
    # this test asserts — an earlier module's gate flip must not leak in
    res = run_scenario(get_scenario("chat", light=True), seed=0)
    rep = res["report"]
    assert rep.ok, rep.to_dict()
    assert rep.lost == 0 and rep.completed == rep.requests
    assert rep.goodput_tps > 0
    assert res["bundle"] is None  # no breach, no bundle
    assert res["extras"]["prefix_hit_ratio"] > 0.5, res["extras"]
    assert rep.burn_rates is not None \
        and "availability" in rep.burn_rates
    # every record resolved with timing data
    for r in res["records"]:
        assert r["outcome"] == "ok"
        assert r["ttft_s"] is not None and r["ttft_s"] >= 0


@pytest.mark.timeout(300)
def test_scenario_against_real_grpc_daemon():
    """The router-fleet path: the same chat script fired at a LIVE
    gRPC daemon (`target="host:port"`) instead of the scenario's own
    in-process server — per-request GenerateStream clients, wire-true
    TTFT/ITL, same verdict machinery. This is how a scenario points at
    a PR-12 router front door."""
    import jax

    from dnn_tpu import obs
    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.lm_server import start_lm_server_in_background
    from dnn_tpu.workloads import get_scenario, run_scenario
    from dnn_tpu.workloads.scenarios import PROMPT_PAD, _cfg

    obs.set_enabled(True)
    cfg = _cfg()
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    port = 59941  # distinct from the chaos/relay/fleet probe ranges
    _t, stop = start_lm_server_in_background(
        cfg, prepared, port=port, slots=4, max_len=64,
        prompt_pad=PROMPT_PAD, prefix_cache=8, temperature=0.0)
    try:
        sc = get_scenario("chat", light=True)
        res = run_scenario(sc, seed=0, target=f"127.0.0.1:{port}")
        rep = res["report"]
        assert rep.lost == 0
        assert rep.completed == rep.requests, rep.to_dict()
        assert rep.ok, rep.to_dict()
        for r in res["records"]:
            assert r["ttft_s"] is not None  # streaming gave real TTFT
    finally:
        stop()


@pytest.mark.timeout(300)
def test_breach_scenario_bundle_asserted_from_artifact():
    """The chaos-injected breach end to end via the PROBE (the same
    path the run_all row takes): the verdict is a breach, and `ok`
    comes from reading the bundle back off disk — manifest verdict,
    chaos_inject events in the dumped timeline, CLI render."""
    from benchmarks.workload_probe import measure

    from dnn_tpu import obs

    obs.set_enabled(True)  # the bundle reads the flight ring back
    row = measure("breach_chaos", light=True)
    assert row["expect_breach"] is True
    assert row["slo_verdict"] == "breach"
    assert row["ok"] is True, row
    assert row["reconstructed"] is True
    assert row["chaos_events_in_bundle"] >= 1
    assert row["lost"] == 0  # failures are EXPLICIT even mid-storm
    # and the CLI renders the artifact the probe verified
    proc = subprocess.run(
        [sys.executable, "-m", "dnn_tpu.obs", "incident",
         row["bundle"]],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "SLO BREACH" in proc.stdout
    assert "chaos_inject" in proc.stdout
