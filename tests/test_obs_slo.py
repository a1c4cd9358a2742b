"""obs/slo.py: the after-the-fact SLO judge and its incident bundles.

SLO-verdict arithmetic goldens; incident-bundle write, read BACK off
disk, CLI render, and rejection of non-bundles.
"""

import json

import pytest


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
