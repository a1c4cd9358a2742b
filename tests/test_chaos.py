"""dnn_tpu.chaos + self-healing serving (ISSUE 8).

Covers the injection side (deterministic seeded schedules, the seams)
and every recovery behavior it forces: supervised restart with backoff
and a crash-loop cap, request requeue on worker death (token parity vs
an uninterrupted run), connection draining under load (nothing lost,
nothing newly admitted), the client circuit breaker's
open/half-open/close cycle plus the fresh-channel rebuild, deadline
propagation plumbing, exactly-once admission dedup, and
corrupted-checkpoint restore that fails loud then falls back to the
previous good artifact.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import grpc
import jax
import numpy as np
import pytest

from dnn_tpu import chaos
from dnn_tpu.chaos import inject as chaos_inject
from dnn_tpu.chaos.plan import Fault, FaultPlan, decide
from dnn_tpu.comm import transport as tx
from dnn_tpu.io.serialization import PayloadCorruptError
from dnn_tpu.models import gpt
from dnn_tpu.obs import flight
from dnn_tpu.runtime.lm_server import (
    DrainingError,
    LMServer,
    _BatcherWorker,
    parse_gen_options,
)
from dnn_tpu.runtime.serving import ContinuousBatcher

CFG = gpt.PRESETS["gpt2-test"]


def _prepared(seed=0):
    return gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(seed), CFG), CFG)


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Every test leaves the process injector-free — chaos is
    process-global state."""
    yield
    chaos_inject.uninstall()


# ----------------------------------------------------------------------
# plan + injector determinism
# ----------------------------------------------------------------------

def test_fault_plan_parse_and_validation(tmp_path):
    plan = FaultPlan.from_json(json.dumps({
        "seed": 3,
        "faults": [
            {"kind": "kill_stage", "target": "node2", "at_s": 5},
            {"kind": "rpc_drop", "seam": "client", "p": 0.5, "count": 2},
        ]}))
    assert plan.seed == 3
    assert [f.kind for f in plan.process_faults()] == ["kill_stage"]
    assert [f.kind for f in plan.inprocess_faults()] == ["rpc_drop"]
    # file form + the --chaos CLI dual (path or inline)
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan.to_dict()))
    assert FaultPlan.from_cli(str(p)).to_dict() == plan.to_dict()
    assert FaultPlan.from_cli(json.dumps(plan.to_dict())).seed == 3
    # a typo'd plan fails LOUD — silently injecting nothing would "pass"
    # every chaos assertion
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault(kind="kill_stge")
    with pytest.raises(ValueError, match="unknown fault fields"):
        FaultPlan.from_dict({"faults": [{"kind": "rpc_drop", "pp": 1}]})
    with pytest.raises(ValueError):
        FaultPlan.from_cli("/nonexistent/plan.json")


def test_injection_schedule_deterministic_golden():
    """Same plan + seed -> bit-identical decision sequence, across
    injector instances (the replay contract: no wall-clock randomness
    in any consulted seam)."""
    plan = FaultPlan.from_dict({
        "seed": 7,
        "faults": [{"kind": "rpc_drop", "seam": "client", "p": 0.5,
                    "count": 3}]})

    def decisions(inj):
        out = []
        for _ in range(10):
            try:
                inj.perturb_rpc("client", "t:1")
                out.append(".")
            except grpc.RpcError:
                out.append("D")
        return "".join(out)

    a = decisions(chaos_inject.Injector(plan))
    b = decisions(chaos_inject.Injector(plan))
    assert a == b
    # GOLDEN for seed 7: blake2s is stable across platforms/runs, so
    # this exact firing pattern (3 drops, budget-capped) is pinned
    assert a == ".D.D..D..."
    # pure decision function is stable
    assert decide(7, "a", 1) == decide(7, "a", 1)
    assert decide(7, "a", 1) != decide(7, "a", 2)


def test_rpc_and_relay_seams():
    chaos.install({"seed": 0, "faults": [
        {"kind": "rpc_corrupt", "seam": "stage", "p": 1.0, "count": 1},
        {"kind": "rpc_delay", "seam": "stage", "p": 1.0, "count": 1,
         "delay_s": 0.01},
        {"kind": "relay_drop", "p": 1.0, "count": 1},
        {"kind": "relay_corrupt", "p": 1.0, "count": 1},
        {"kind": "kv_exhaust", "from_n": 0, "count": 2},
    ]})
    # corrupt fires first (listed first), then delay, then nothing
    with pytest.raises(PayloadCorruptError, match="chaos"):
        chaos_inject.perturb_rpc("stage", "x")
    chaos_inject.perturb_rpc("stage", "x")  # delay: sleeps, no raise
    chaos_inject.perturb_rpc("stage", "x")  # budgets exhausted
    # relay seam: drop -> frame vanishes (assembler returns None)
    from dnn_tpu.comm import wirecodec as wc

    asm = tx.ChunkAssembler()
    req = wc.TensorRequest(request_id=tx.tag_seq("r", 0),
                           tensor=wc.make_tensor(np.arange(4.0)))
    assert asm.add(req) is None           # relay_drop ate it
    with pytest.raises(PayloadCorruptError):   # relay_corrupt
        asm.add(req)
    out = asm.add(req)                    # budgets exhausted: delivers
    assert out is not None and out[1] == 0
    # kv seam: two scheduled exhaustions then clear
    assert chaos_inject.kv_exhaust() is True
    assert chaos_inject.kv_exhaust() is True
    assert chaos_inject.kv_exhaust() is False
    # every firing left a flight event
    kinds = [e["fault"] for e in flight.recorder().events(
        kind="chaos_inject")]
    for k in ("rpc_corrupt", "rpc_delay", "relay_drop", "relay_corrupt",
              "kv_exhaust"):
        assert k in kinds
    # uninstalled: all seams are no-ops
    chaos_inject.uninstall()
    chaos_inject.perturb_rpc("stage", "x")
    assert chaos_inject.perturb_relay() is False
    assert chaos_inject.kv_exhaust() is False


def test_train_fault_seam_golden():
    # the training-loop seam (ISSUE 19): exact step counters, a
    # DIRECTIVE dict instead of a raise — train.fit executes it inside
    # its data window so the injected cost lands where the fault claims
    chaos.install({"seed": 0, "faults": [
        {"kind": "train_fault", "target": "sleep", "at_n": 1,
         "count": 2, "delay_s": 0.25},
        {"kind": "train_fault", "target": "nan", "at_n": 4, "count": 1},
    ]})
    try:
        got = [chaos_inject.train_fault() for _ in range(6)]
    finally:
        chaos_inject.uninstall()
    # GOLDEN firing pattern over the seeded "train" counter: n=0 clear,
    # n∈[1,3) sleep with the configured delay, n=3 clear, n=4 nan
    assert got[0] is None and got[3] is None and got[5] is None
    assert got[1] == {"mode": "sleep", "delay_s": 0.25} == got[2]
    assert got[4]["mode"] == "nan"
    # every firing left a flight event carrying its mode
    fired = [(e["n"], e["mode"]) for e in flight.recorder().events(
        kind="chaos_inject") if e.get("fault") == "train_fault"]
    assert fired[-3:] == [(1, "sleep"), (2, "sleep"), (4, "nan")]
    # uninstalled: the seam is one None check
    assert chaos_inject.train_fault() is None


def test_poison_batch_floats_only():
    # the nan directive's executor: float leaves drown, int leaves
    # (token batches) pass through untouched — the documented contract
    # that puts the sentinel test on a float toy model
    from dnn_tpu.train import poison_batch

    batch = {"tokens": np.arange(6, dtype=np.int32).reshape(2, 3),
             "x": np.ones((2, 2), dtype=np.float32)}
    out = poison_batch(batch)
    assert np.isnan(out["x"]).all()
    assert out["tokens"].dtype == np.int32
    assert (out["tokens"] == batch["tokens"]).all()


def test_deadline_propagation_plumbing():
    rid = tx.tag_deadline("gen:8:tr=ab.cd", 12.5)
    assert tx.extract_deadline(rid) == 12.5
    assert tx.strip_deadline(rid) == "gen:8:tr=ab.cd"
    # re-tagging replaces, never stacks
    rid2 = tx.tag_deadline(rid, 3.0)
    assert rid2.count("dl=") == 1 and tx.extract_deadline(rid2) == 3.0
    # the LM daemon's option parser skips dl= (wire-compat: transport
    # metadata, not a generation option) and parses d= as the dedup key
    max_new, seed, opts = parse_gen_options(tx.tag_deadline("gen:8", 5),
                                            32)
    assert (max_new, seed) == (8, None) and "dl" not in str(opts)
    _, _, opts = parse_gen_options("gen:4:d=key1", 32)
    assert opts["dedup"] == "key1"
    # reference rids pass through untouched
    assert tx.extract_deadline("req:1234") is None
    assert tx.strip_deadline("req:1234") == "req:1234"


# ----------------------------------------------------------------------
# watchdog: injected wedge + escalation hook
# ----------------------------------------------------------------------

def test_watchdog_injected_wedge_and_escalation():
    from dnn_tpu.obs.watchdog import Watchdog

    fired = []
    wd = Watchdog(period_s=0.1, probe_deadline_s=0.5,
                  device_probe=lambda d: (True, "stub ok"),
                  on_wedged=fired.append)
    inj = chaos.install({"seed": 0, "faults": []})
    wd.start()
    try:
        time.sleep(0.35)
        assert wd.state() == "ok"
        inj.activate_wedge()
        t0 = time.monotonic()
        while wd.state() != "wedged" and time.monotonic() - t0 < 5:
            time.sleep(0.05)
        st = wd.status()
        assert st["state"] == "wedged"
        assert "chaos" in st["components"]["device"]["detail"]
        # escalation fired ONCE per episode, not once per probe round
        time.sleep(0.5)
        assert len(fired) == 1 and "chaos" in fired[0]
        # recovery re-arms the latch; a second episode fires again
        inj.clear_wedge()
        t0 = time.monotonic()
        while wd.state() != "ok" and time.monotonic() - t0 < 5:
            time.sleep(0.05)
        assert wd.state() == "ok"
        inj.activate_wedge()
        t0 = time.monotonic()
        while len(fired) < 2 and time.monotonic() - t0 < 5:
            time.sleep(0.05)
        assert len(fired) == 2
        # the injection itself is in the ring (reconstructable incident)
        assert any(e["fault"] == "wedge_device"
                   for e in flight.recorder().events(kind="chaos_inject"))
    finally:
        wd.close()


# ----------------------------------------------------------------------
# supervisor
# ----------------------------------------------------------------------

def test_supervisor_restart_backoff_and_crash_loop():
    from dnn_tpu.chaos.supervisor import Supervisor

    # a child that dies instantly: restarts walk the backoff ladder and
    # the crash-loop cap gives up instead of kill-9ing forever
    sup = Supervisor(
        lambda: subprocess.Popen([sys.executable, "-c",
                                  "raise SystemExit(3)"]),
        name="crashy", backoff_s=0.05, backoff_max_s=0.4,
        health_interval_s=0.05, crash_loop_max=3,
        crash_loop_window_s=60.0, stable_after_s=60.0)
    sup.start()
    t0 = time.monotonic()
    while sup.state != "crashloop" and time.monotonic() - t0 < 30:
        time.sleep(0.05)
    sup.stop()
    assert sup.state in ("crashloop", "stopped")
    assert sup.restarts == 3
    backoffs = [e for e in flight.recorder().events(
        kind="supervisor_backoff") if e["stage"] == "crashy"]
    assert len(backoffs) >= 3
    # exponential: each recorded delay doubles (0.05, 0.1, 0.2, ...)
    delays = [e["delay_s"] for e in backoffs[:3]]
    assert delays == [0.05, 0.1, 0.2]
    assert any(e["stage"] == "crashy" for e in flight.recorder().events(
        kind="crash_loop"))


def test_supervisor_recovers_killed_child():
    from dnn_tpu.chaos.supervisor import Supervisor

    sup = Supervisor(
        lambda: subprocess.Popen([sys.executable, "-c",
                                  "import time; time.sleep(120)"]),
        name="healthy", backoff_s=0.05, health_interval_s=0.05)
    sup.start()
    try:
        # start() launches synchronously: the first child is alive
        first = sup.proc
        assert first is not None and first.poll() is None
        sup.inject_kill()

        def recovered():
            # restarts is bumped BEFORE the replacement is spawned and
            # the event recorded, so wait on what recovery means: a
            # live process that is not the one that was killed, and
            # the supervisor's own record of having restarted it
            p = sup.proc
            return (sup.restarts >= 1 and p.pid != first.pid
                    and p.poll() is None
                    and any(e["stage"] == "healthy" and e["pid"] == p.pid
                            for e in flight.recorder().events(
                                kind="supervisor_restart")))

        deadline = time.monotonic() + 30
        while not recovered() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert recovered(), (sup.restarts, sup.state, first.pid,
                             sup.proc.pid, sup.proc.poll())
        assert first.poll() is not None  # the killed child is gone
    finally:
        sup.stop()


def test_corrupted_checkpoint_restore_fails_loud_then_falls_back(
        tmp_path):
    from dnn_tpu.chaos.supervisor import restore_latest_good
    from dnn_tpu.io.train_ckpt import save_train_state

    state1 = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    state2 = {"w": np.full((2, 3), 7.0, dtype=np.float32)}
    ckpt_dir = str(tmp_path / "ckpts")
    save_train_state(ckpt_dir, 1, state1)
    p2 = save_train_state(ckpt_dir, 2, state2)
    chaos.corrupt_file(p2, seed=1)
    like = {"w": np.zeros((2, 3), np.float32)}
    state, step, path = restore_latest_good(ckpt_dir, like)
    assert step == 1 and path.endswith("step_00000001.npz")
    np.testing.assert_array_equal(np.asarray(state["w"]), state1["w"])
    # the failure is LOUD in the ring, and the fallback is recorded
    fails = flight.recorder().events(kind="ckpt_restore_failed")
    assert any(e["path"].endswith("step_00000002.npz") for e in fails)
    assert any(e["step"] == 1 for e in
               flight.recorder().events(kind="ckpt_restore_recovered"))
    # nothing loadable -> explicit error naming the failures
    chaos.corrupt_file(os.path.join(ckpt_dir, "step_00000001.npz"),
                       seed=2)
    with pytest.raises(RuntimeError, match="no loadable checkpoint"):
        restore_latest_good(ckpt_dir, like)


# ----------------------------------------------------------------------
# LM server: requeue on worker death
# ----------------------------------------------------------------------

def test_requeue_on_worker_death_token_parity():
    """An injected device-step fault kills the batcher worker mid-run;
    the requeue path restarts the worker and resubmits — final tokens
    equal an uninterrupted run of the same seeded requests."""
    srv = LMServer(CFG, _prepared(), slots=2, max_len=32, prompt_pad=8,
                   default_max_new=6, worker_restarts=2)
    try:
        prompts = [np.array([1, 2, 3], np.int32),
                   np.array([4, 5], np.int32)]
        # baseline: uninterrupted (no injector installed)
        base = [srv.worker.submit(p, 6, 100 + i).result(timeout=120)
                for i, p in enumerate(prompts)]
        first_worker = srv.worker
        # now kill the NEXT device step; the requeued rerun must match
        chaos.install({"seed": 0, "faults": [
            {"kind": "step_fault", "at_n": 0, "count": 1}]})
        futs = [srv.worker.submit(p, 6, 100 + i)
                for i, p in enumerate(prompts)]
        out = [f.result(timeout=120) for f in futs]
        for got, want in zip(out, base):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
        assert srv.worker is not first_worker, "worker was not replaced"
        assert not first_worker.is_alive()
        assert srv.worker.is_alive()
        ring = flight.recorder()
        assert any(e.get("requeue") for e in ring.events(
            kind="worker_died"))
        restarts = ring.events(kind="worker_restart")
        assert restarts and restarts[-1]["requeued"] >= 1
    finally:
        chaos_inject.uninstall()
        srv.close()


def test_requeue_budget_exhausted_fails_fast():
    """A fault that kills EVERY step exhausts the restart budget and
    degrades to the pre-ISSUE-8 fail-fast shape (bounded, visible) —
    never a requeue loop."""
    srv = LMServer(CFG, _prepared(seed=1), slots=2, max_len=32,
                   prompt_pad=8, worker_restarts=1)
    try:
        chaos.install({"seed": 0, "faults": [
            {"kind": "step_fault", "at_n": 0, "count": 10_000}]})
        fut = srv.worker.submit(np.array([1, 2, 3], np.int32), 4, 7)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="worker died"):
            fut.result(timeout=60)
        assert time.monotonic() - t0 < 30
        assert flight.recorder().events(kind="worker_restart_exhausted")
    finally:
        chaos_inject.uninstall()
        srv.close()


# ----------------------------------------------------------------------
# draining
# ----------------------------------------------------------------------

def test_drain_under_load_no_loss_no_new_admits():
    srv = ContinuousBatcher(CFG, _prepared(seed=2), slots=1, max_len=64,
                            prompt_pad=8)
    worker = _BatcherWorker(srv)
    worker.start()
    # slots=1: the first request decodes while the others queue
    in_flight = worker.submit(np.array([1, 2, 3], np.int32), 24, 1)
    queued = [worker.submit(np.array([4, 5], np.int32), 8, 2),
              worker.submit(np.array([6], np.int32), 8, 3)]
    worker.begin_drain()
    # the admitted request FINISHES (its caller paid for the decode)
    assert in_flight.result(timeout=120).shape == (24,)
    # queued work hands back RETRIABLE — never silently lost
    for f in queued:
        with pytest.raises(DrainingError, match="retry against"):
            f.result(timeout=30)
    # no new admissions once draining
    late = worker.submit(np.array([7], np.int32), 4, 4)
    with pytest.raises(DrainingError):
        late.result(timeout=5)
    worker.join(timeout=30)
    assert not worker.is_alive()
    ring = flight.recorder()
    assert ring.events(kind="drain_begin")
    assert ring.events(kind="drain_done")
    assert any(e["requests"] >= 2 for e in ring.events(
        kind="drain_handback"))


def test_drainz_http_endpoint_and_healthz():
    srv = LMServer(CFG, _prepared(seed=3), slots=1, max_len=32,
                   prompt_pad=8, metrics_port=0, drain_grace_s=30.0)
    try:
        port = srv.metrics_server.port
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert r.status == 200
        req = urllib.request.Request(base + "/drainz", method="POST",
                                     data=b"")
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.status == 202
            body = json.loads(r.read())
            assert body["draining"] is True
        # idempotent second POST
        with urllib.request.urlopen(req, timeout=5) as r:
            assert json.loads(r.read())["draining"] is True
        # readiness flips: healthz 503 while draining/drained
        t0 = time.monotonic()
        code = 200
        while time.monotonic() - t0 < 10:
            try:
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=5) as r:
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            if code == 503:
                break
            time.sleep(0.1)
        assert code == 503
        # drain completes: worker exits, escalation latch set (the
        # serve_lm loop would exit now), statusz carries the drain
        # component while the watchdog-less fallback applies
        assert srv._escalated.wait(timeout=30)
        st = srv._statusz()
        assert st["components"]["drain"]["state"] == "draining"
    finally:
        srv.close()


def test_preflight_rejects_unavailable_while_draining():
    """Over the wire: a draining daemon answers UNAVAILABLE (the
    retriable status the client ladder honors) and HealthCheck goes
    unhealthy — the hand-back contract end to end."""
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.runtime.lm_server import start_lm_server_in_background

    port = 59315
    t, stop = start_lm_server_in_background(
        CFG, _prepared(seed=4), port=port, slots=2, max_len=32,
        prompt_pad=8, default_max_new=4)
    try:
        c = NodeClient(f"127.0.0.1:{port}", breaker=False)
        assert c.generate(np.array([1, 2], np.int32),
                          max_new_tokens=3).shape == (3,)
        stop.servicer._drainz()
        t0 = time.monotonic()
        with pytest.raises(grpc.RpcError) as ei:
            # retries=0: surface the first status, no ladder
            c.send_tensor(np.array([1, 2], np.int32),
                          request_id="gen:3", timeout=10, retries=0)
        assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
        assert "drain" in (ei.value.details() or "").lower()
        assert time.monotonic() - t0 < 5
        assert not c.health_check()
        c.close()
    finally:
        stop()


# ----------------------------------------------------------------------
# exactly-once dedup at admission
# ----------------------------------------------------------------------

def test_dedup_joins_identical_key_over_grpc():
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.runtime.lm_server import start_lm_server_in_background

    port = 59316
    t, stop = start_lm_server_in_background(
        CFG, _prepared(seed=5), port=port, slots=2, max_len=32,
        prompt_pad=8, default_max_new=4)
    try:
        c = NodeClient(f"127.0.0.1:{port}", breaker=False)
        p = np.array([1, 2, 3], np.int32)
        a = c.generate(p, max_new_tokens=4, seed=10, dedup="k1")
        # same dedup key, DIFFERENT seed: a non-deduped server would
        # generate a different stream — the join returns the original
        b = c.generate(p, max_new_tokens=4, seed=999, dedup="k1")
        np.testing.assert_array_equal(a, b)
        # a different key generates independently
        d = c.generate(p, max_new_tokens=4, seed=999, dedup="k2")
        assert not np.array_equal(a, d) or True  # streams may collide;
        # the CONTRACT is the join event below, not inequality
        joins = flight.recorder().events(kind="dedup_join")
        assert any(e["key"] == "k1" for e in joins)
        assert not any(e["key"] == "k2" for e in joins)
        # review regression: a STREAMING request carrying a d= key must
        # serve (the key is dropped — streams can't join), never reach
        # batcher.submit as an unknown kwarg
        toks = list(c.generate_stream(p, max_new_tokens=3, seed=1,
                                      dedup="k3"))
        assert len(toks) == 3
        c.close()
    finally:
        stop()


# ----------------------------------------------------------------------
# circuit breaker + channel rebuild
# ----------------------------------------------------------------------

def test_circuit_breaker_open_half_open_close_cycle():
    from dnn_tpu.comm.client import CircuitBreaker

    b = CircuitBreaker("t:1", threshold=2, cooldown_s=0.15,
                       max_cooldown_s=1.0)
    assert b.allow() and b.state == "closed"
    b.record(False)
    b.record(False)
    assert b.state == "open" and not b.allow()
    time.sleep(0.2)
    assert b.allow() and b.state == "half_open"  # ONE probe
    assert not b.allow()                         # second probe blocked
    # review regression: a DELEGATED call releases the probe slot
    # instead of judging it — the next allow() re-issues it instantly
    # (an unsettled half_open slot would shed traffic forever)
    b.release()
    assert b.state == "open" and b.allow() and b.state == "half_open"
    b.record(False)                              # probe failed
    assert b.state == "open"
    assert b._cooldown == pytest.approx(0.3)     # doubled
    time.sleep(0.35)
    assert b.allow()
    b.record(True)
    assert b.state == "closed" and b.allow()
    assert b._cooldown == pytest.approx(0.15)    # reset
    kinds = [e["kind"] for e in flight.recorder().events()
             if e.get("target") == "t:1"]
    for k in ("circuit_open", "circuit_half_open", "circuit_reopen",
              "circuit_close"):
        assert k in kinds


def test_client_sheds_fast_when_open_and_rebuilds_channel():
    from dnn_tpu.comm.client import CircuitBreaker, CircuitOpenError, \
        NodeClient

    # nothing listens here: every call is a connect failure
    c = NodeClient("127.0.0.1:59399",
                   breaker=CircuitBreaker("127.0.0.1:59399", threshold=2,
                                          cooldown_s=5.0))
    x = np.arange(4.0)
    for _ in range(2):
        with pytest.raises(grpc.RpcError):
            c.send_tensor(x, request_id="r", timeout=2.0, retries=0)
    # breaker open: fail is O(1), no connect timeout paid
    t0 = time.monotonic()
    with pytest.raises(CircuitOpenError):
        c.send_tensor(x, request_id="r", timeout=2.0, retries=0)
    assert time.monotonic() - t0 < 0.2
    # the two consecutive UNAVAILABLEs also crossed the rebuild
    # threshold: the wedged-backoff channel was replaced (PR 7 lesson,
    # fixed in the client proper)
    assert c.channel_rebuilds >= 1
    assert any(e["target"] == "127.0.0.1:59399" for e in
               flight.recorder().events(kind="channel_rebuild"))
    c.close()


def test_channel_rebuild_spares_the_call_in_flight():
    """A draining server answers UNAVAILABLE to what it will not admit;
    two of those in a row make NodeClient replace its channel. The call
    still running on the old channel finishes there — closing the
    channel under it would cancel a request the server is serving."""
    from concurrent import futures

    from dnn_tpu.comm import wirecodec as wc
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.comm.service import _handlers

    admitted, release = threading.Event(), threading.Event()

    class _Draining:
        def SendTensor(self, request, context):
            if not request.request_id.startswith("slow"):  # dl= / tr= tags follow
                context.abort(grpc.StatusCode.UNAVAILABLE, "draining")
            admitted.set()
            assert release.wait(30)
            return wc.TensorResponse(status="done")

        HealthCheck = SendMessage = None  # never called here

    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((_handlers(_Draining()),))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    c = NodeClient(f"127.0.0.1:{port}", transport="grpc", breaker=False)
    x, out = np.arange(4.0), {}

    def slow():
        try:
            out["status"], _ = c.send_tensor(
                x, request_id="slow", timeout=60.0, retries=0)
        except grpc.RpcError as e:
            out["error"] = e.code()

    t = threading.Thread(target=slow)
    t.start()
    try:
        assert admitted.wait(30)
        for _ in range(c.rebuild_after):
            with pytest.raises(grpc.RpcError) as ei:
                c.send_tensor(x, request_id="late", timeout=10.0, retries=0)
            assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
        assert c.channel_rebuilds == 1
    finally:
        release.set()
        t.join(30)
        c.close()
        server.stop(0)
    assert out == {"status": "done"}


def test_wait_healthy_rides_channel_rebuild_to_late_server():
    """The PR 7 stale-channel scenario, solved inside the client: a
    server that binds AFTER the first failed connects is still found by
    the same NodeClient instance (no fresh-client workaround)."""
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.runtime.lm_server import start_lm_server_in_background

    port = 59317
    c = NodeClient(f"127.0.0.1:{port}", breaker=False)
    # burn a few failed probes first — the old behavior parked the
    # channel in reconnect backoff here
    for _ in range(3):
        assert not c.health_check(timeout=0.5)
    assert c.channel_rebuilds >= 1
    t, stop = start_lm_server_in_background(
        CFG, _prepared(seed=6), port=port, slots=1, max_len=32,
        prompt_pad=8)
    try:
        assert c.wait_healthy(deadline=30.0, interval=0.3)
        c.close()
    finally:
        stop()
