"""dnn_tpu.control — fleet front door: policies, replica lifecycle,
KV handoff, and the router end to end.

The e2e legs run REAL gRPC through an in-process router over
in-process LM servers (start_lm_server_in_background) — the same wire
path `node --route` serves, minus subprocesses
(`python -m dnn_tpu.control` owns the real-subprocess shape). Policy,
admission, autoscaling and protocol checks are pure host goldens with
injected signals."""

import threading
import time

import jax
import numpy as np
import pytest

from dnn_tpu.control import handoff
from dnn_tpu.control.policy import (
    POLICIES,
    ReplicaView,
    get_policy,
    shed_reason,
    wanted_replicas,
)
from dnn_tpu.models import gpt

CFG = gpt.PRESETS["gpt2-test"]

# distinct from every other module's port ranges
_PORTS = iter(range(59730, 59790))


def _prompt(n=8):
    return (np.arange(1, n + 1) % CFG.vocab_size).astype(np.int32)


@pytest.fixture(scope="module")
def prepared():
    params = gpt.init(jax.random.PRNGKey(0), CFG)
    return gpt.prepare_stacked(params, CFG)


# ----------------------------------------------------------------------
# policies (pure goldens, injected signals)
# ----------------------------------------------------------------------

def _v(name, **kw):
    return ReplicaView(name=name, **kw)


def test_round_robin_cycles_by_name():
    p = get_policy("round_robin")
    cands = [_v("b"), _v("a"), _v("c")]
    picks = [p.pick(cands).name for _ in range(6)]
    assert picks == ["a", "b", "c", "a", "b", "c"]


def test_least_queue_golden_and_inflight_fallback():
    p = get_policy("least_queue")
    # scraped queue depth dominates
    assert p.pick([_v("a", queue_depth=5), _v("b", queue_depth=1)]
                  ).name == "b"
    # local inflight covers the scrape lag (and is the whole signal
    # when scraping is off)
    assert p.pick([_v("a", queue_depth=1, inflight=4),
                   _v("b", queue_depth=2, inflight=0)]).name == "b"
    assert p.pick([_v("a", inflight=3), _v("b", inflight=1)]).name == "b"


def test_slo_burn_golden_burn_dominates_queue():
    p = get_policy("slo_burn")
    # replica a: empty queue but burning budget at 2x; replica b: a few
    # queued requests, quiet burn -> b wins (burn outranks ~8 queued)
    a = _v("a", queue_depth=0, burn={"ttft": 2.0})
    b = _v("b", queue_depth=4, burn={"ttft": 0.1})
    assert p.pick([a, b]).name == "b"
    # with burns equal, load decides; ttft p99 breaks the last tie
    assert p.pick([_v("a", queue_depth=3), _v("b", queue_depth=1)]
                  ).name == "b"
    assert p.pick([_v("a", ttft_p99_ms=500.0), _v("b", ttft_p99_ms=5.0)]
                  ).name == "b"


def test_policy_registry():
    assert set(POLICIES) == {"round_robin", "least_queue", "slo_burn"}
    with pytest.raises(ValueError, match="unknown routing policy"):
        get_policy("fastest")


def test_shed_reason_golden():
    assert shed_reason([], max_inflight=4) == "no_serving_replica"
    sat = [_v("a", inflight=4), _v("b", inflight=9)]
    assert shed_reason(sat, max_inflight=4) == "saturated"
    ok = [_v("a", inflight=4), _v("b", inflight=1)]
    assert shed_reason(ok, max_inflight=4) is None
    burning = [_v("a", burn={"availability": 3.0}),
               _v("b", burn={"ttft": 1.5})]
    assert shed_reason(burning, max_inflight=4, shed_burn=1.0) \
        == "slo_burn"
    # one quiet candidate admits
    assert shed_reason(burning + [_v("c", burn={"ttft": 0.2})],
                       max_inflight=4, shed_burn=1.0) is None
    # burn gate off by default
    assert shed_reason(burning, max_inflight=4) is None


def test_wanted_replicas_arithmetic():
    # pressure ~1: hold
    calm = [_v("a", state="serving", queue_depth=2),
            _v("b", state="serving", queue_depth=2)]
    assert wanted_replicas(calm, slots_hint=4) == 2
    # queue 3x capacity: scale toward pressure 1
    hot = [_v("a", state="serving", queue_depth=12, inflight=0),
           _v("b", state="serving", queue_depth=12, inflight=0)]
    assert wanted_replicas(hot, slots_hint=4) == 6
    # burn >= 1 adds one even with short queues
    burn = [_v("a", state="serving", queue_depth=0,
               burn={"ttft": 1.4})]
    assert wanted_replicas(burn, slots_hint=4) == 2
    # idle fleet gives one back, never below 1
    idle = [_v("a", state="serving", queue_depth=0),
            _v("b", state="serving", queue_depth=0)]
    assert wanted_replicas(idle, slots_hint=4) == 1
    assert wanted_replicas([_v("a", state="serving", queue_depth=0)],
                           slots_hint=4) == 1
    # only SERVING replicas count
    assert wanted_replicas([_v("a", state="dead")]) == 1
    # ACTIVE SHEDDING wants one more whatever the queues say: admission
    # control keeps replica queues short precisely when demand exceeds
    # the fleet — queue depth alone is blind to shed pressure
    assert wanted_replicas(idle, slots_hint=4, shedding=True) == 3
    assert wanted_replicas(calm, slots_hint=4, shedding=True) == 3


# ----------------------------------------------------------------------
# protocol tables (model check both directions + buggy fixtures)
# ----------------------------------------------------------------------

def test_control_machines_registered_and_clean():
    import dataclasses

    from dnn_tpu.analysis.protocol import (
        MACHINES,
        REPLICA,
        ROUTER,
        check_machine,
        check_machine_sites,
    )

    assert REPLICA in MACHINES and ROUTER in MACHINES
    for m in (REPLICA, ROUTER):
        assert check_machine(m) == []
        assert check_machine_sites(m, ".") == []
    # drop the respawn edge: dead becomes absorbing -> the "fleet
    # shrinks forever" bug reproduces as a PRO002 model failure
    buggy = dataclasses.replace(
        REPLICA, edges=tuple(e for e in REPLICA.edges
                             if e.event != "replica_respawn"))
    rules = {f.rule for f in check_machine(buggy)}
    assert "PRO002" in rules


def test_router_fixture_flagged_by_site_check():
    from dnn_tpu.analysis.protocol import ROUTER, check_machine_sites

    # a Router that invents an undeclared state and records an event no
    # edge declares: both directions must flag
    src = (
        "from dnn_tpu.obs import flight\n"
        "class Router:\n"
        "    def __init__(self):\n"
        "        self._state = 'init'\n"
        "    def start(self):\n"
        "        self._state = 'serving'\n"
        "        flight.record('router_start')\n"
        "    def explode(self):\n"
        "        self._state = 'on_fire'\n"
        "        flight.record('router_meltdown')\n")
    found = check_machine_sites(ROUTER, ".", src=src)
    rules = [f.rule for f in found]
    assert "PRO003" in rules  # undeclared state + unmapped event
    assert "PRO004" in rules  # declared edges with no site in fixture


# ----------------------------------------------------------------------
# KV handoff: wire format + batcher-level export/adopt parity
# ----------------------------------------------------------------------

def test_handoff_pack_roundtrip_including_bf16():
    import ml_dtypes

    payload = {
        "row": [np.arange(24, dtype=np.float32).reshape(2, 3, 4),
                np.arange(6, dtype=np.int8).reshape(2, 3),
                np.ones((2, 2), ml_dtypes.bfloat16)],
        "hidden_row": np.linspace(0, 1, 7, dtype=np.float32),
        "prompt_len": 5,
        "fingerprint": {"vocab_size": 7, "row_len": 4},
    }
    buf = handoff.pack(payload)
    assert buf.dtype == np.uint8 and buf.ndim == 1
    back = handoff.unpack(buf)
    assert back["prompt_len"] == 5
    assert back["fingerprint"] == payload["fingerprint"]
    for a, b in zip(payload["row"], back["row"]):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(payload["hidden_row"],
                                  back["hidden_row"])


def test_handoff_malformed_payloads_fail_loud():
    buf = handoff.pack({"row": [np.zeros((2,), np.float32)],
                        "hidden_row": np.zeros((3,), np.float32),
                        "prompt_len": 1, "fingerprint": {}})
    with pytest.raises(ValueError, match="bad magic"):
        handoff.unpack(np.zeros(16, np.uint8))
    with pytest.raises(ValueError, match="truncated"):
        handoff.unpack(buf[: buf.size - 4])
    # a version-1 payload (a logits row where the hidden row is)
    raw = buf.tobytes()
    at = raw.index(b'"hidden"')
    old = np.frombuffer(raw[:at] + b'"logits"' + raw[at + 8:], np.uint8)
    with pytest.raises(ValueError, match="no hidden row"):
        handoff.unpack(old)


def test_export_adopt_parity_and_rejections(prepared):
    from dnn_tpu.runtime.serving import ContinuousBatcher

    kw = dict(slots=2, max_len=CFG.block_size, prompt_pad=16)
    prompt = _prompt(9)
    pre = ContinuousBatcher(CFG, prepared, **kw)
    pay = handoff.unpack(handoff.pack(pre.export_prefill(prompt)))
    # greedy parity vs a locally-prefilled pool
    dec = ContinuousBatcher(CFG, prepared, **kw)
    rid = dec.submit(prompt, 8, prefilled=pay)
    got = dec.drain()[rid]
    ref = ContinuousBatcher(CFG, prepared, **kw)
    rid = ref.submit(prompt, 8)
    want = ref.drain()[rid]
    np.testing.assert_array_equal(got, want)
    # sampled parity, draw-for-draw (same seed -> same rng derivation)
    skw = dict(kw, temperature=0.8, top_k=32)
    pre_s = ContinuousBatcher(CFG, prepared, **skw)
    pay_s = handoff.unpack(handoff.pack(pre_s.export_prefill(prompt)))
    dec_s = ContinuousBatcher(CFG, prepared, **skw)
    rid = dec_s.submit(prompt, 8, seed=7, prefilled=pay_s)
    got_s = dec_s.drain()[rid]
    ref_s = ContinuousBatcher(CFG, prepared, **skw)
    rid = ref_s.submit(prompt, 8, seed=7)
    np.testing.assert_array_equal(got_s, ref_s.drain()[rid])
    # PAGED pool adopts the same dense row (install_row routes it into
    # the pool blocks the admission allocated)
    pg = ContinuousBatcher(CFG, prepared, kv="paged", **kw)
    rid = pg.submit(prompt, 8, prefilled=pay)
    np.testing.assert_array_equal(pg.drain()[rid], want)
    # geometry mismatch fails loud at admission
    other = ContinuousBatcher(CFG, prepared, slots=2, max_len=32,
                              prompt_pad=8)
    with pytest.raises(ValueError, match="must share model config"):
        other.submit(_prompt(5), 4, prefilled=pay)
    # interleaved admission rejects adoption
    ilv = ContinuousBatcher(CFG, prepared, prefill_chunk_tokens=16, **kw)
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ilv.submit(prompt, 4, prefilled=pay)
    # fingerprints match between same-geometry pools, differ otherwise
    assert pre.handoff_fingerprint() == dec.handoff_fingerprint()
    assert pre.handoff_fingerprint() != other.handoff_fingerprint()


# ----------------------------------------------------------------------
# Supervisor: injectable ready-probe endpoint/port (satellite bugfix)
# ----------------------------------------------------------------------

def test_supervisor_health_endpoint_injectable():
    import http.server
    import subprocess
    import sys

    from dnn_tpu.chaos.supervisor import Supervisor

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            ok = self.path == "/replicaz"
            self.send_response(200 if ok else 404)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        spawn = lambda: subprocess.Popen(  # noqa: E731
            [sys.executable, "-c", "import time; time.sleep(30)"])
        # CALLABLE url resolved per poll + custom path — the fleet
        # spawner's shape: distinct metrics ports, no subclassing
        sup = Supervisor(spawn, name="probe-test",
                         health_url=lambda: f"http://127.0.0.1:{port}",
                         health_path="/replicaz")
        try:
            sup.proc = spawn()
            assert sup._healthy_once() is True
            sup.health_path = "/healthz"  # the old fixed path 404s here
            assert sup._healthy_once() is False
            # a callable that cannot resolve yet reads not-healthy
            sup.health_url = lambda: None
            assert sup._healthy_once() is False
        finally:
            if sup.proc is not None:
                sup.proc.kill()
                sup.proc.wait(timeout=10)
    finally:
        srv.shutdown()
        srv.server_close()


# ----------------------------------------------------------------------
# router e2e over real gRPC (in-process replicas)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet(prepared):
    """Two in-process LM replicas + an attach-mode ReplicaSet + router.
    Torn down at module end; the drain test (LAST in this file) drains
    replica r0 and leaves it drained."""
    from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
    from dnn_tpu.control.router import start_router_in_background
    from dnn_tpu.runtime.lm_server import start_lm_server_in_background

    p1, p2, pr = next(_PORTS), next(_PORTS), next(_PORTS)
    _t1, stop1 = start_lm_server_in_background(
        CFG, prepared, port=p1, slots=2, seed=0, kv="dense")
    _t2, stop2 = start_lm_server_in_background(
        CFG, prepared, port=p2, slots=2, seed=0, kv="dense")
    rset = ReplicaSet(
        [ReplicaHandle("r0", f"127.0.0.1:{p1}"),
         ReplicaHandle("r1", f"127.0.0.1:{p2}")],
        interval_s=0.3).start()
    assert rset.wait_serving(2, 60)
    router, rstop = start_router_in_background(
        rset, port=pr, policy="round_robin")
    yield {"router_port": pr, "p1": p1, "p2": p2, "rset": rset,
           "router": router, "stops": (stop1, stop2),
           "servers": (stop1.servicer, stop2.servicer)}
    rstop()
    rset.stop()
    stop1()
    stop2()


@pytest.fixture()
def client(fleet):
    from dnn_tpu.comm.client import NodeClient

    c = NodeClient(f"127.0.0.1:{fleet['router_port']}", transport="grpc")
    yield c
    c.close()


def test_router_roundtrip_matches_direct(fleet, client):
    from dnn_tpu.comm.client import NodeClient

    prompt = _prompt()
    got = client.generate(prompt, max_new_tokens=8, seed=3)
    direct = NodeClient(f"127.0.0.1:{fleet['p1']}", transport="grpc")
    try:
        want = direct.generate(prompt, max_new_tokens=8, seed=3)
    finally:
        direct.close()
    np.testing.assert_array_equal(got, want)


def test_router_spreads_load_round_robin(fleet, client):
    s1, s2 = fleet["servers"]
    before = (s1.batcher._next_rid, s2.batcher._next_rid)
    for i in range(4):
        client.generate(_prompt(), max_new_tokens=3, seed=i)
    d1 = s1.batcher._next_rid - before[0]
    d2 = s2.batcher._next_rid - before[1]
    assert d1 + d2 == 4 and d1 == d2 == 2, (d1, d2)


def test_router_affinity_and_dedup_join(fleet, client):
    s1, s2 = fleet["servers"]
    before = s1.batcher._next_rid + s2.batcher._next_rid
    a = client.generate(_prompt(), max_new_tokens=6, seed=5,
                        dedup="ctrl-key-1")
    b = client.generate(_prompt(), max_new_tokens=6, seed=5,
                        dedup="ctrl-key-1")
    np.testing.assert_array_equal(a, b)
    # affinity landed both on ONE replica, where the second JOINED the
    # first's future — exactly one admission total
    after = s1.batcher._next_rid + s2.batcher._next_rid
    assert after - before == 1, (before, after)


def test_router_streaming_passthrough(fleet, client):
    toks = list(client.generate_stream(_prompt(), max_new_tokens=5,
                                       seed=2))
    assert len(toks) == 5
    want = client.generate(_prompt(), max_new_tokens=5, seed=2)
    np.testing.assert_array_equal(np.asarray(toks, np.int32), want)


def test_router_disagg_parity_and_zero_decode_prefill(fleet, prepared):
    """Same two servers attached under prefill/decode roles: the gen
    path runs the handoff (prefill replica computes the KV, decode
    replica adopts) and tokens match the role=both route exactly."""
    from dnn_tpu import obs
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
    from dnn_tpu.control.router import start_router_in_background

    s1, s2 = fleet["servers"]
    pr = next(_PORTS)
    rset = ReplicaSet(
        [ReplicaHandle("pre", f"127.0.0.1:{fleet['p1']}",
                       role="prefill"),
         ReplicaHandle("dec", f"127.0.0.1:{fleet['p2']}",
                       role="decode")],
        interval_s=0.3).start()
    assert rset.wait_serving(2, 30)
    _router, rstop = start_router_in_background(rset, port=pr)
    c = NodeClient(f"127.0.0.1:{pr}", transport="grpc")
    try:
        chunks_before = s2.batcher.prefill_chunks_run
        prompt = _prompt(19)
        got = c.generate(prompt, max_new_tokens=8, seed=4)
        # reference: the same request through the plain (role=both)
        # router of the module fixture
        ref = NodeClient(f"127.0.0.1:{fleet['router_port']}",
                         transport="grpc")
        try:
            want = ref.generate(prompt, max_new_tokens=8, seed=4)
        finally:
            ref.close()
        np.testing.assert_array_equal(got, want)
        # the decode replica adopted — it ran ZERO new prefill chunks
        assert s2.batcher.prefill_chunks_run == chunks_before
        assert obs.flight.recorder().events(kind="kv_handoff")
    finally:
        c.close()
        rstop()
        rset.stop()


def test_router_budget_and_disagg_decision_units():
    """`dl=` budgets are trusted AS-IS (never clamped down to the
    router default — the client already re-tags remaining budget per
    attempt), and the disagg decision skips `h=`/`a=`-tagged gens."""
    from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
    from dnn_tpu.control.router import Router

    def _rset():
        return ReplicaSet([ReplicaHandle("u0", "127.0.0.1:1")])

    r = Router(_rset(), default_deadline_s=30.0)
    assert r._budget("gen:4:1") == 30.0
    assert r._budget("gen:4:1:dl=120.000") == 120.0  # > default: kept
    assert r._budget("gen:4:1:dl=2.500") == 2.5      # < default: kept
    assert r._budget("gen:4:1:dl=0.000") == 0.001    # floored
    assert r._wants_disagg("gen:4:1")
    assert r._wants_disagg("gen:4:1:t=0.5:d=key")
    assert not r._wants_disagg("gen:4:1:h=abc")      # handle present
    assert not r._wants_disagg("gen:4:1:a=0")        # adapter: the
    # decode-side submit(prefilled=) adoption rejects adapters
    assert not r._wants_disagg("kvput:abc")
    assert not r._wants_disagg("embed:mean")
    r2 = Router(_rset(), disagg="off")
    assert not r2._wants_disagg("gen:4:1")


def test_router_kvput_then_generate_lands_on_staging_replica(
        fleet, client):
    """Client-driven kvput-then-generate through the router: the
    kvput forward must BIND `h=<key>` affinity so the follow-up
    generate re-routes to the replica actually holding the staged KV
    (unbound, round-robin would miss ~50% per request)."""
    ref = client.generate(_prompt(11), max_new_tokens=6, seed=7)
    for i in range(4):  # 4 fresh keys: P(pass unbound) = 1/16
        key = f"kvaff{i}"
        payload = client.prefill_kv(_prompt(11))
        client.put_kv(key, payload)
        status, result = client.send_tensor(
            _prompt(11), request_id=f"gen:6:7:h={key}",
            timeout=30.0, retries=0)
        assert result is not None, status
        np.testing.assert_array_equal(np.asarray(result, np.int32), ref)


def test_router_pinned_handoff_failure_falls_back_to_plain_rid():
    """The disagg generate leg failing on the pinned decode replica
    (adoption rejected / drain after put_kv) must retry siblings with
    the PLAIN rid — no sibling ever staged the router-minted handle —
    instead of surfacing INVALID_ARGUMENT for a valid request."""
    import asyncio

    import grpc

    from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
    from dnn_tpu.control.router import Router

    h0, h1 = (ReplicaHandle("f0", "127.0.0.1:1"),
              ReplicaHandle("f1", "127.0.0.1:2"))
    h0.state = h1.state = "serving"
    router = Router(ReplicaSet([h0, h1]), policy="round_robin")

    class _Rpc(grpc.RpcError):
        def __init__(self, code):
            self._code = code

        def code(self):
            return self._code

        def details(self):
            return "adoption rejected"

    class _FakeClient:
        def __init__(self):
            self.rids = []

        def send_tensor(self, arr, *, request_id, timeout, retries):
            self.rids.append(request_id)
            if "h=" in request_id:
                raise _Rpc(grpc.StatusCode.INVALID_ARGUMENT)
            return "ok", np.arange(3, dtype=np.int32)

    fakes = {"f0": _FakeClient(), "f1": _FakeClient()}
    router._clients.update(fakes)

    class _Ctx:
        async def abort(self, code, details):
            raise AssertionError(f"aborted: {code} {details}")

    resp = asyncio.run(router._forward_unary(
        _prompt(), "gen:3:1:h=rt0", _Ctx(), pinned=h0,
        fallback_rid="gen:3:1"))
    assert resp.result_tensor is not None
    all_rids = fakes["f0"].rids + fakes["f1"].rids
    # exactly one handle-tagged attempt (the pinned one), then the
    # plain-rid fallback that succeeded
    assert [r for r in all_rids if "h=" in r] == ["gen:3:1:h=rt0"]
    assert "gen:3:1" in all_rids


def test_router_sheds_unavailable_when_no_replica(prepared):
    import grpc

    from dnn_tpu import obs
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
    from dnn_tpu.control.router import start_router_in_background

    pr, dead_port = next(_PORTS), next(_PORTS)
    rset = ReplicaSet(
        [ReplicaHandle("gone", f"127.0.0.1:{dead_port}")],
        interval_s=0.2).start()
    router, rstop = start_router_in_background(rset, port=pr)
    c = NodeClient(f"127.0.0.1:{pr}", transport="grpc")
    try:
        with pytest.raises(grpc.RpcError) as ei:
            c.send_tensor(_prompt(), request_id="gen:4:1", timeout=6.0,
                          retries=0)
        assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
        assert "shedding" in (ei.value.details() or "")
        assert router.shed_total >= 1
        assert any(e["kind"] == "router_shed"
                   for e in obs.flight.recorder().events(
                       kind="router_shed"))
    finally:
        c.close()
        rstop()
        rset.stop()


def test_node_route_and_role_cli_validation(tmp_path):
    import json

    from dnn_tpu.node import main

    cfg = {"nodes": [{"id": "n0", "address": "127.0.0.1:59788",
                      "part_index": 0}],
           "num_parts": 1, "model": "gpt2-test", "device_type": "cpu"}
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(cfg))
    base = ["--node_id", "n0", "--config", str(path)]
    # --role needs --serve_lm
    assert main(base + ["--role", "prefill"]) == 1
    # --route needs --route_targets
    assert main(base + ["--route"]) == 1
    # --route_targets needs --route
    assert main(base + ["--route_targets", "127.0.0.1:1"]) == 1
    # --route excludes the model-serving modes
    assert main(base + ["--route", "--route_targets", "127.0.0.1:1",
                        "--serve_lm"]) == 1
    # mismatched signals list
    assert main(base + ["--route",
                        "--route_targets", "127.0.0.1:1,127.0.0.1:2",
                        "--route_signals", "http://127.0.0.1:3"]) == 1


def test_router_fleet_rollup_shows_roles_and_wanted(fleet):
    """FleetCollector treats the router as a first-class target: role
    columns, the wanted_replicas gauge, ?format=prom re-export."""
    import urllib.request

    from dnn_tpu import obs
    from dnn_tpu.obs.fleet import FleetCollector
    from dnn_tpu.runtime.lm_server import start_lm_server_in_background

    router = fleet["router"]
    # router obs endpoint: its statusz (role=router) + the shared
    # registry (which carries the router gauges)
    srv = obs.serve_metrics(0, status=router.statusz)
    try:
        col = FleetCollector({"router": f"http://127.0.0.1:{srv.port}"},
                             interval_s=30.0)
        col.poll_once()
        z = col.fleetz()
        row = z["stages"]["router"]
        assert row["role"] == "router"
        assert row.get("wanted_replicas") is not None
        assert z["fleet"]["wanted_replicas"] is not None
        prom = col.render_prom()
        assert "dnn_tpu_fleet_stage_role" in prom
        assert "dnn_tpu_wanted_replicas" in prom
        # raw endpoint carries the router series for any plain scraper
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read()
        assert b"dnn_tpu_router_queue_depth" in raw
        col.close()
    finally:
        srv.close()


def test_router_drain_hands_queued_work_to_sibling(fleet, client):
    """LAST test in the module (it drains r0 for good): draining one
    replica mid-traffic loses nothing — its rejections are retried on
    the sibling by the ROUTER, invisibly to the client."""
    from dnn_tpu import obs

    s1, s2 = fleet["servers"]
    rid_before = s2.batcher._next_rid
    errors = []

    def pound(i):
        try:
            client.generate(_prompt(), max_new_tokens=4, seed=100 + i,
                            timeout=30.0)
        except Exception as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    threads = [threading.Thread(target=pound, args=(i,))
               for i in range(6)]
    for t in threads[:2]:
        t.start()
    s1._drainz()  # drain r0 while traffic is in flight
    for t in threads[2:]:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    # everything that r0 turned away landed on r1
    assert s2.batcher._next_rid > rid_before
    # the replica set noticed the drain (healthz 503s) — r0 leaves the
    # serving set within a few monitor ticks
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if fleet["rset"].replicas["r0"].state != "serving":
            break
        time.sleep(0.1)
    assert fleet["rset"].replicas["r0"].state in ("draining", "dead")
    # ...and the router recorded sibling retries for the handed-back work
    assert obs.flight.recorder().events(kind="router_retry_sibling") \
        or s2.batcher._next_rid - rid_before >= 4
