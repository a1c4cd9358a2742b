"""Fixture suite for the concurrency-hazard analyzer (ISSUE 10).

One known-bad / known-good pair per CON rule, the THREE historical
shipped bugs (PR 7's ShmRing event-loop deadlock, PR 4's unguarded
set_result worker-killer, PR 7 r2's cancelled-handler ticket-slot
leak) reintroduced as fixtures and each flagged by its rule, the
protocol state-machine goldens (including the "unsettled half-open
probe slot sheds traffic forever" bug as a PRO002 model-check
failure), the loop-lag sanitizer's unit + endpoint-readback behavior,
self-lint over the serving stack modulo baseline, and the CLI's
--diff / --format sarif contracts.
"""

import asyncio
import json
import os
import textwrap
import time

import pytest

from dnn_tpu.analysis.lint import lint_paths, lint_source

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO_ROOT, "dnn_tpu")
ACCEPTED = os.path.join(PKG_DIR, "analysis", "baseline.json")


def rules_of(src):
    return sorted({f.rule for f in lint_source(textwrap.dedent(src), "t")})


# ----------------------------------------------------------------------
# rule fixtures: (rule, known-bad, known-good twin)
# ----------------------------------------------------------------------

FIXTURES = {
    "CON001": (
        """
        import time
        async def handler(x):
            time.sleep(0.5)
            return x
        """,
        """
        import asyncio
        import time
        async def handler(x):
            await asyncio.to_thread(time.sleep, 0.5)
            return x
        """,
    ),
    "CON002": (
        """
        def publish(fut, tokens):
            fut.set_result(tokens)
        """,
        """
        def publish(fut, tokens):
            if not fut.done():
                fut.set_result(tokens)
        """,
    ),
    "CON003": (
        """
        async def forward(sender, call, y, rid):
            request = sender.make_request_nowait(y, rid)
            resp = await call(request)
            sender.sent_ok(request)
            return resp
        """,
        """
        async def forward(sender, call, y, rid):
            request = sender.make_request_nowait(y, rid)
            ok = False
            try:
                resp = await call(request)
                ok = True
                return resp
            finally:
                if ok:
                    sender.sent_ok(request)
                else:
                    sender.cleanup(request)
        """,
    ),
    "CON004": (
        """
        import threading
        A = threading.Lock()
        B = threading.Lock()
        def f():
            with A:
                with B:
                    pass
        def g():
            with B:
                with A:
                    pass
        """,
        """
        import threading
        A = threading.Lock()
        B = threading.Lock()
        def f():
            with A:
                with B:
                    pass
        def g():
            with A:
                with B:
                    pass
        """,
    ),
    "CON005": (
        """
        import threading
        class Worker:
            def __init__(self):
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)
            def _run(self):
                self.state = "running"
            async def handle(self):
                self.state = "served"
        """,
        """
        import threading
        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)
            def _run(self):
                with self._lock:
                    self.state = "running"
            async def handle(self):
                with self._lock:
                    self.state = "served"
        """,
    ),
    "CON006": (
        """
        import threading
        class Ring:
            def __init__(self):
                self._cond = threading.Condition()
            def release(self):
                self._cond.notify_all()
        """,
        """
        import threading
        class Ring:
            def __init__(self):
                self._cond = threading.Condition()
            def release(self):
                with self._cond:
                    self._cond.notify_all()
        """,
    ),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fixture_pair(rule):
    bad, good = FIXTURES[rule]
    assert rule in rules_of(bad), f"{rule} must flag its bad fixture"
    assert rules_of(good) == [], \
        f"{rule} good twin must be clean, got {rules_of(good)}"


# extra per-rule behaviors beyond the canonical pair -------------------

def test_con001_awaited_and_referenced_forms_clean():
    # awaiting an asyncio primitive and PASSING a blocking function by
    # reference to to_thread are the two sanctioned forms
    src = """
    import asyncio
    import queue
    q = queue.Queue()
    async def f(evt):
        await asyncio.wait_for(evt.wait(), 1.0)
        item = await asyncio.to_thread(q.get)
        return item
    """
    assert rules_of(src) == []


def test_con001_typed_receiver_and_nonblocking_forms():
    bad = """
    import queue
    q = queue.Queue()
    async def f():
        return q.get()
    """
    assert "CON001" in rules_of(bad)
    good = """
    import queue
    q = queue.Queue()
    async def f():
        return q.get(block=False)
    """
    assert rules_of(good) == []


def test_con002_try_except_guard_accepted():
    src = """
    def publish(fut, tokens):
        try:
            fut.set_result(tokens)
        except Exception:
            pass
    """
    assert rules_of(src) == []


def test_con002_settle_inside_except_handler_not_guarded():
    # a handler does not catch exceptions raised in its OWN body — a
    # cleanup-path settle inside `except:` is exactly where the PR 4
    # bug class hides (review-round find on this rule's first cut)
    src = """
    def run(fut, step):
        try:
            fut.set_result(step())
        except Exception as e:
            fut.set_exception(e)
    """
    assert "CON002" in rules_of(src)
    good = """
    def run(fut, step):
        try:
            fut.set_result(step())
        except Exception as e:
            if not fut.done():
                fut.set_exception(e)
    """
    assert rules_of(good) == []


def test_con001_to_thread_closure_fix_accepted():
    # the sanctioned fix written as a LOCAL closure must not flag
    # (review-round find: only the async def's own body is loop
    # context)...
    src = """
    import asyncio
    import queue
    q = queue.Queue()
    async def handler():
        def work():
            return q.get()
        return await asyncio.to_thread(work)
    """
    assert rules_of(src) == []
    # ...but CALLING the blocking closure directly on the loop still
    # flags, through the blocking-closure propagation
    bad = """
    import queue
    q = queue.Queue()
    async def handler():
        def work():
            return q.get()
        return work()
    """
    assert "CON001" in rules_of(bad)


def test_con005_single_writer_annotation():
    bad, _good = FIXTURES["CON005"]
    annotated = bad.replace('self.state = "running"',
                            'self.state = "running"  # conc: single-writer')
    annotated = annotated.replace(
        'self.state = "served"',
        'self.state = "served"  # conc: single-writer')
    assert rules_of(annotated) == []


def test_con006_nondaemon_thread_without_join():
    bad = """
    import threading
    def work():
        pass
    def start():
        t = threading.Thread(target=work)
        t.start()
    """
    assert "CON006" in rules_of(bad)
    good = """
    import threading
    def work():
        pass
    def start():
        t = threading.Thread(target=work)
        t.start()
        t.join()
    """
    assert rules_of(good) == []


# ----------------------------------------------------------------------
# the three historical shipped bugs, reintroduced as fixtures
# ----------------------------------------------------------------------

# PR 7 e2e-verify find: ShmRing.write (a blocking Condition wait) ran on
# the server event loop that processes the very acks that free slots —
# a deadlock until the 30 s ring timeout. Through one level of
# indirection, exactly what the per-module call chain resolves.
HIST_SHMRING_DEADLOCK = """
class Forwarder:
    def __init__(self, slots):
        self._ring = ShmRing(slots)
    def _send(self, view):
        return self._ring.write(view)
    async def forward(self, view):
        seg = self._send(view)
        return seg
"""

# PR 4 latent worker-killer: set_result on a future its caller had
# deadline-cancelled raised InvalidStateError and killed the batcher
# thread (every later request then hung to its timeout).
HIST_SET_RESULT_RACE = """
def publish_done(futures, batcher):
    for rid in list(futures):
        tokens, _reason, _lps = batcher.claim(rid)
        fut = futures.pop(rid)
        fut.set_result(tokens)
"""

# PR 7 review-round-2 find: the cancelled _forward handler (upstream
# deadline mid-forward) skipped both release paths — success AND
# except(Exception) — leaking the ticket slot; 4 cancellations wedged
# the 4-slot ring for good. Only a finally is cancel-safe.
HIST_SLOT_LEAK = """
async def _forward(sender, call, y, rid):
    request = sender.make_request_nowait(y, rid)
    try:
        resp = await call(request)
        sender.sent_ok(request)
        return resp
    except Exception:
        sender.cleanup(request)
        raise
"""

HISTORICAL = {
    "CON001": HIST_SHMRING_DEADLOCK,
    "CON002": HIST_SET_RESULT_RACE,
    "CON003": HIST_SLOT_LEAK,
}


@pytest.mark.parametrize("rule", sorted(HISTORICAL))
def test_historical_bug_flagged_by_its_rule(rule):
    assert rule in rules_of(HISTORICAL[rule]), \
        f"the reintroduced historical bug must be a {rule} finding"


@pytest.mark.parametrize("rule", sorted(HISTORICAL))
def test_historical_bug_fails_the_gate(rule, tmp_path):
    from dnn_tpu.analysis.__main__ import main

    bad = tmp_path / f"hist_{rule.lower()}.py"
    bad.write_text(textwrap.dedent(HISTORICAL[rule]))
    assert main([str(bad), "--no-program", "--no-protocol",
                 "--no-baseline"]) == 1


# ----------------------------------------------------------------------
# protocol state machines
# ----------------------------------------------------------------------

def test_protocol_tables_model_check_clean():
    from dnn_tpu.analysis.protocol import MACHINES, check_machine

    for m in MACHINES:
        assert check_machine(m) == [], f"machine {m.name} must be sound"


def test_protocol_audit_clean_on_head():
    """Every declared machine's code sites map to declared edges and
    every edge has a site — the table/code correspondence on HEAD."""
    from dnn_tpu.analysis.protocol import run_protocol_audit

    report, findings = run_protocol_audit(REPO_ROOT)
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in findings)
    assert all(m["clean"] for m in report["machines"])
    assert {m["name"] for m in report["machines"]} == {
        "circuit_breaker", "supervisor", "drain", "relay_accept_window",
        "replica_lifecycle", "router", "kvtier_lease"}


def test_pro002_unsettled_probe_slot_is_a_model_failure():
    """The PR 8 review-round bug as a model-check failure: remove
    half_open's exits (what the consumed-then-delegated probe slot
    effectively did) and the breaker machine has an absorbing
    non-terminal state — it sheds 100% of traffic forever."""
    import dataclasses

    from dnn_tpu.analysis.protocol import BREAKER, check_machine

    buggy = dataclasses.replace(
        BREAKER,
        edges=tuple(e for e in BREAKER.edges if e.src != "half_open"))
    findings = check_machine(buggy)
    assert any(f.rule == "PRO002" and "half_open" in f.message
               for f in findings)


def test_pro001_unreachable_state():
    from dnn_tpu.analysis.protocol import Edge, Machine, check_machine

    m = Machine(name="t", states=("a", "b", "orphan"), initial="a",
                terminal=("b",), edges=(Edge("a", "go", "b"),))
    findings = check_machine(m)
    assert any(f.rule == "PRO001" and "orphan" in f.message
               for f in findings)


def test_pro003_undeclared_transition_site():
    from dnn_tpu.analysis.protocol import (
        Edge,
        Machine,
        check_machine_sites,
    )

    m = Machine(name="t", states=("a", "b"), initial="a",
                terminal=("b",), edges=(Edge("a", "go", "b"),),
                module="x.py", cls="T", state_attr="_state")
    src = textwrap.dedent("""
        class T:
            def __init__(self):
                self._state = "a"
            def go(self):
                self._state = "b"
            def wedge(self):
                self._state = "zombie"
    """)
    findings = check_machine_sites(m, REPO_ROOT, src=src)
    assert any(f.rule == "PRO003" and "zombie" in f.message
               for f in findings)


def test_pro004_stale_edge():
    from dnn_tpu.analysis.protocol import (
        Edge,
        Machine,
        check_machine_sites,
    )

    m = Machine(name="t", states=("a", "b", "c"), initial="a",
                terminal=("c",),
                edges=(Edge("a", "go", "b"), Edge("b", "fin", "c")),
                module="x.py", cls="T", state_attr="_state")
    src = textwrap.dedent("""
        class T:
            def __init__(self):
                self._state = "a"
            def go(self):
                self._state = "b"
    """)
    findings = check_machine_sites(m, REPO_ROOT, src=src)
    assert any(f.rule == "PRO004" and "fin" in f.message
               for f in findings)


# ----------------------------------------------------------------------
# loop-lag sanitizer (analysis/sanitize.py)
# ----------------------------------------------------------------------

def test_sanitizer_catches_planted_blocking_callback():
    from dnn_tpu.analysis.sanitize import LoopLagSanitizer

    async def scenario():
        s = LoopLagSanitizer(threshold_s=0.05, interval_s=0.01,
                             where="test-sanitize").install()
        await asyncio.sleep(0.05)
        time.sleep(0.3)  # the planted blocking callback
        await asyncio.sleep(0.05)
        s.stop()
        return s

    s = asyncio.run(scenario())
    assert s.breaches >= 1
    assert s.max_lag_s >= 0.2
    with pytest.raises(AssertionError, match="blocked the loop"):
        s.assert_bounded(0.1)
    # the breach landed in the flight ring (what /debugz serves)
    from dnn_tpu import obs

    evs = obs.flight.recorder().events(kind="loop_lag")
    assert any(e.get("where") == "test-sanitize" for e in evs)
    ons = obs.flight.recorder().events(kind="loop_sanitize_on")
    assert any(e.get("where") == "test-sanitize" for e in ons)


def test_sanitizer_clean_loop_passes_bound():
    from dnn_tpu.analysis.sanitize import LoopLagSanitizer

    async def scenario():
        s = LoopLagSanitizer(threshold_s=0.2, interval_s=0.01,
                             where="test-clean").install()
        for _ in range(10):
            await asyncio.sleep(0.01)
        s.stop()
        return s

    s = asyncio.run(scenario())
    assert s.breaches == 0
    s.assert_bounded(1.0)  # generous: CI scheduler jitter is not a breach


def test_sanitizer_event_cap_bounds_ring_traffic():
    from dnn_tpu.analysis.sanitize import LoopLagSanitizer

    async def scenario():
        s = LoopLagSanitizer(threshold_s=0.01, interval_s=0.005,
                             max_events=3, where="test-cap").install()
        for _ in range(8):
            await asyncio.sleep(0.01)  # let the rearmed tick schedule
            time.sleep(0.03)           # ...then breach it
        await asyncio.sleep(0.01)
        s.stop()
        return s

    s = asyncio.run(scenario())
    assert s.breaches >= 4
    from dnn_tpu import obs

    evs = [e for e in obs.flight.recorder().events(kind="loop_lag")
           if e.get("where") == "test-cap"]
    assert len(evs) <= 3  # bounded: a wedged loop can't flood the ring


def test_sanitizer_endpoint_readback():
    """read_endpoint reads installed/breaches/max_lag off a served
    /debugz."""
    from dnn_tpu import obs
    from dnn_tpu.analysis.sanitize import LoopLagSanitizer, read_endpoint

    srv = obs.serve_metrics(0)
    try:
        async def scenario():
            s = LoopLagSanitizer(threshold_s=0.05, interval_s=0.01,
                                 where="test-endpoint").install()
            await asyncio.sleep(0.02)
            time.sleep(0.2)
            await asyncio.sleep(0.02)
            s.stop()
            return s

        asyncio.run(scenario())
        rec = read_endpoint(f"http://127.0.0.1:{srv.port}")
        assert rec["installed"] is True
        assert rec["breaches"] >= 1
        assert rec["max_lag_ms"] >= 100.0
    finally:
        srv.close()


def test_sanitizer_env_gate(monkeypatch):
    from dnn_tpu.analysis import sanitize

    monkeypatch.delenv(sanitize.ENV_GATE, raising=False)
    assert sanitize.maybe_install() is None  # off by default
    monkeypatch.setenv(sanitize.ENV_GATE, "1")
    monkeypatch.setenv(sanitize.ENV_THRESHOLD, "0.5")

    async def scenario():
        s = sanitize.maybe_install(where="test-env")
        assert s is not None and s.threshold_s == 0.5
        s.stop()

    asyncio.run(scenario())


# ----------------------------------------------------------------------
# self-lint + baseline discipline over the serving stack
# ----------------------------------------------------------------------

def test_serving_stack_con_clean_modulo_baseline():
    """The burn-down contract (ISSUE 10 satellite): zero unjustified
    CON/protocol findings over comm/, runtime/lm_server, chaos/ — every
    surviving finding is baselined WITH a justification."""
    from dnn_tpu.analysis.findings import (
        diff_against_baseline,
        load_baseline,
    )
    from dnn_tpu.analysis.protocol import run_protocol_audit

    targets = [os.path.join(PKG_DIR, "comm"),
               os.path.join(PKG_DIR, "chaos"),
               os.path.join(PKG_DIR, "obs"),
               os.path.join(PKG_DIR, "runtime", "lm_server.py")]
    findings = lint_paths(targets, repo_root=REPO_ROOT)
    _report, proto = run_protocol_audit(REPO_ROOT)
    entries = load_baseline(ACCEPTED)
    new, suppressed, _stale = diff_against_baseline(
        list(findings) + list(proto), entries)
    assert not new, "unbaselined findings:\n" + "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in new)
    for e in entries:
        assert str(e.get("justification", "")).strip(), e


# ----------------------------------------------------------------------
# CLI: exit codes, --diff, --format sarif
# ----------------------------------------------------------------------

@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_cli_nonzero_per_rule(rule, tmp_path):
    from dnn_tpu.analysis.__main__ import main

    bad = tmp_path / f"inject_{rule.lower()}.py"
    bad.write_text(textwrap.dedent(FIXTURES[rule][0]))
    assert main([str(bad), "--no-program", "--no-protocol",
                 "--no-baseline"]) == 1
    good = tmp_path / f"clean_{rule.lower()}.py"
    good.write_text(textwrap.dedent(FIXTURES[rule][1]))
    assert main([str(good), "--no-program", "--no-protocol",
                 "--no-baseline"]) == 0


def test_cli_sarif_output(tmp_path, capsys):
    from dnn_tpu.analysis.__main__ import main

    bad = tmp_path / "user_async.py"
    bad.write_text(textwrap.dedent(FIXTURES["CON001"][0]))
    rc = main([str(bad), "--no-program", "--no-protocol",
               "--no-baseline", "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "CON001"
    assert results[0]["level"] == "error"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] >= 1
    rules = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert "CON001" in rules

    good = tmp_path / "user_async_ok.py"
    good.write_text(textwrap.dedent(FIXTURES["CON001"][1]))
    rc = main([str(good), "--no-program", "--no-protocol",
               "--no-baseline", "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["runs"][0]["results"] == []


def test_cli_sarif_carries_suppressions(capsys):
    """Baselined findings ride the SARIF report as suppressed notes —
    enumerated, not hidden, same policy as the text report."""
    from dnn_tpu.analysis.__main__ import main

    rc = main(["--no-program", "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    notes = [r for r in doc["runs"][0]["results"]
             if r["level"] == "note"]
    assert notes, "the baselined findings must appear as notes"
    assert all(r["suppressions"][0]["justification"] for r in notes)


def test_cli_diff_mode(tmp_path):
    """--diff REV lints only the package files changed since REV
    (program pass auto-skipped); the working tree's own diff against
    HEAD must pass the gate — tests/benchmarks (which plant hazard
    fixtures on purpose) are outside diff scope like they are outside
    the default gate's."""
    import subprocess

    from dnn_tpu.analysis.__main__ import changed_files, main

    git = subprocess.run(["git", "-C", REPO_ROOT, "rev-parse", "HEAD"],
                         capture_output=True, text=True)
    if git.returncode != 0:
        pytest.skip("no git repo / rev available")
    files = changed_files("HEAD", REPO_ROOT)
    assert all(f.endswith(".py") and os.path.exists(
        os.path.join(REPO_ROOT, f)) for f in files)
    assert main(["--diff", "HEAD"]) == 0
