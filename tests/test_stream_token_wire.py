"""A streamed token's way to its gRPC write (runtime/lm_server.py
`GenerateStream`, comm/wirecodec.py `make_token_tensor`).

The message of a token is made from the integer and must stay, byte for
byte, what `make_tensor(np.asarray([tok], np.int32))` sends — crc32c
declared when that machine's `make_tensor` would declare it, with the same
value. A stream waits on its queue with no timer a token: its deadline is
ONE `loop.call_at` a request, and what the deadline does is held here
against a live server (abort, cancel event, counter, flight record, the
timer cancelled however the handler leaves) and against the handler driven
token by token (a passed deadline yields nothing more; the timer's item
does not overtake tokens handed off before it). The last test counts what
the path calls, not how long it takes."""

import asyncio
import concurrent.futures
import contextlib
import threading
import time

import grpc
import jax
import numpy as np
import pytest

from dnn_tpu import native, obs
from dnn_tpu.comm import wirecodec as wc
from dnn_tpu.comm.client import NodeClient
from dnn_tpu.io.serialization import PayloadCorruptError
from dnn_tpu.models import gpt
from dnn_tpu.runtime import lm_server
from dnn_tpu.runtime.lm_server import start_lm_server_in_background

CFG = gpt.PRESETS["gpt2-test"]
PORT = 59347
PROMPT = np.array([3, 1, 4, 1, 5], np.int32)
TOKENS = [0, 1, 255, 256, 50256, 129279, 151935, 2**31 - 1]


# ----------------------------------------------------------------------
# the message
# ----------------------------------------------------------------------

@pytest.fixture(params=["native", "absent"])
def codec(request, monkeypatch):
    """The compiled codec as this machine has it, or patched away: the
    crc32c field is declared only with it."""
    if request.param == "absent":
        monkeypatch.setattr(native, "_LIB", None)
        monkeypatch.setattr(native, "_TRIED", True)
        assert not native.native_available()
    elif not native.native_available():
        pytest.skip("no compiled codec on this machine")
    return request.param


def _old_response(tok, n):
    """A token's response as the handler built it up to PR 52."""
    return wc.TensorResponse(
        status=f"[lm] token {n}",
        result_tensor=wc.make_tensor(np.asarray([tok], np.int32)))


@pytest.mark.parametrize("n", [1, 9, 10, 1000])
@pytest.mark.parametrize("tok", TOKENS)
def test_token_message_bytes_equal_the_array_forms(codec, tok, n):
    new = wc.TensorResponse(status=f"[lm] token {n}",
                            result_tensor=wc.make_token_tensor(tok))
    wire = wc.serialize_response(new)
    assert wire == wc.serialize_response(_old_response(tok, n))
    declared = wc.parse_response(wire).result_tensor.HasField("crc32c")
    assert declared == (codec == "native")
    if declared:  # the table's value is the compiled codec's
        assert new.result_tensor.crc32c == native.crc32c(
            np.asarray([tok], np.int32))


@pytest.mark.parametrize("tok", TOKENS)
def test_token_message_parses_back_with_its_crc_checked(tok):
    wire = wc.serialize_response(wc.TensorResponse(
        status="[lm] token 1", result_tensor=wc.make_token_tensor(tok)))
    resp = wc.parse_response(wire)
    assert resp.status == "[lm] token 1"
    arr = wc.tensor_view(resp.result_tensor)  # check_crc=True
    assert arr.dtype == np.int32 and arr.shape == (1,) and arr[0] == tok
    if native.native_available():  # ...and a flipped bit is caught
        at = wire.index(tok.to_bytes(4, "little"))
        bad = wire[:at] + bytes([wire[at] ^ 1]) + wire[at + 1:]
        with pytest.raises(PayloadCorruptError):
            wc.tensor_view(wc.parse_response(bad).result_tensor)


# ----------------------------------------------------------------------
# the deadline, over the wire
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def srv():
    prepared = gpt.prepare_stacked(
        gpt.init(jax.random.PRNGKey(0), CFG), CFG)
    _, stop = start_lm_server_in_background(
        CFG, prepared, port=PORT, slots=4, max_len=64, prompt_pad=8,
        default_max_new=8, request_timeout=60.0)
    c = NodeClient(f"127.0.0.1:{PORT}")
    assert len(list(c.generate_stream(PROMPT, max_new_tokens=3,
                                      seed=1))) == 3  # compiled
    c.close()
    try:
        yield stop.servicer
    finally:
        stop()


@pytest.fixture
def timers(monkeypatch):
    """The handles of the deadline timers armed while the test runs."""
    made = []
    real = asyncio.BaseEventLoop.call_at

    def call_at(self, when, callback, *args, **kw):
        handle = real(self, when, callback, *args, **kw)
        if args == (("deadline", None),):
            made.append(handle)
        return handle

    monkeypatch.setattr(asyncio.BaseEventLoop, "call_at", call_at)
    return made


@pytest.fixture
def submits(srv, monkeypatch):
    """What the handler submitted to the worker: (future, kwargs)."""
    seen = []
    real = srv.worker.submit

    def submit(*args, **kw):
        fut = real(*args, **kw)
        seen.append((fut, kw))
        return fut

    monkeypatch.setattr(srv.worker, "submit", submit)
    return seen


@contextlib.contextmanager
def _slow_steps(srv, seconds):
    """Every step of the batcher takes `seconds` longer: a stream waits."""
    real = srv.batcher.step

    def step():
        time.sleep(seconds)
        return real()

    srv.batcher.step = step
    try:
        yield
    finally:
        del srv.batcher.step


def _until(cond, seconds=10.0):
    end = time.monotonic() + seconds
    while not cond() and time.monotonic() < end:
        time.sleep(0.01)
    return cond()


def _tok(msg):
    return int(wc.tensor_view(msg.result_tensor)[0])


def _misses():
    m = obs.metrics()
    return m.counters["serving.deadline_exceeded_total"]


@pytest.mark.parametrize("how", ["request_timeout", "dl_budget"])
def test_a_waiting_stream_aborts_at_its_deadline(srv, timers, submits,
                                                 monkeypatch, how):
    """The deadline passes while the stream waits for its next token: the
    client gets DEADLINE_EXCEEDED after the tokens delivered so far (the
    greedy stream's own first tokens, in order), the request's cancel
    event is set and its slot retired, the counter grows by one, the
    flight ring holds one `deadline_miss` with `tokens=` what arrived and
    the trace id, and the handler's timer is left cancelled."""
    c = NodeClient(f"127.0.0.1:{PORT}")
    whole = c.generate(PROMPT, max_new_tokens=40, seed=7)
    rid = "gen:40:7"
    if how == "request_timeout":
        monkeypatch.setattr(srv, "request_timeout", 0.5)
    else:
        rid += ":dl=0.500"
    before = _misses()
    got = []
    with _slow_steps(srv, 0.05), obs.span("client.doomed") as root:
        with pytest.raises(grpc.RpcError) as ei:
            for resp in c.send_tensor_stream(
                    PROMPT, request_id=obs.tag_request_id(rid, root),
                    timeout=30.0):
                got.append(_tok(resp))
        fut, kw = submits[-1]  # (the unary request went first)
        assert kw["cancel_evt"].is_set()
        assert _until(fut.cancelled)  # retired at a step boundary
    c.close()
    assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
    assert "exceeded 0.5s" in ei.value.details()
    assert 0 < len(got) < 40 and got == list(whole[:len(got)])
    assert _misses() == before + 1
    miss, = obs.flight.recorder().events(kind="deadline_miss",
                                         trace_id=root.trace_id)
    assert miss["method"] == "GenerateStream" and miss["timeout_s"] == 0.5
    assert miss["tokens"] == len(got)
    timer, = timers
    assert _until(timer.cancelled)


@pytest.mark.parametrize("leaves", ["finished", "client_cancels"])
def test_a_stream_leaves_no_live_timer(srv, timers, submits, leaves):
    c = NodeClient(f"127.0.0.1:{PORT}")
    before = _misses()
    if leaves == "finished":
        assert len(list(c.generate_stream(PROMPT, max_new_tokens=6,
                                          seed=3))) == 6
    else:
        with _slow_steps(srv, 0.05):
            stream = c.generate_stream(PROMPT, max_new_tokens=40, seed=3)
            assert len([next(stream), next(stream)]) == 2
            stream.close()  # cancels the RPC
            (fut, kw), = submits
            assert _until(kw["cancel_evt"].is_set)
            assert _until(fut.cancelled)
    c.close()
    timer, = timers  # one a request, not one a token
    assert _until(timer.cancelled)
    assert _misses() == before


# ----------------------------------------------------------------------
# the handler, driven token by token on the test's own loop
# ----------------------------------------------------------------------

class _Aborted(Exception):
    pass


class _Ctx:
    async def abort(self, code, details):
        raise _Aborted(code, details)


class _Driven:
    """`GenerateStream` handlers of `srv` on the running loop, the worker's
    `submit` standing aside: each stream's TokenSink and future are kept
    here, and tokens reach the sinks through `lm_server._fan_out` as a
    hand-off's do."""

    def __init__(self, srv, monkeypatch):
        self.srv, self.sinks, self.futs, self.evts = srv, [], [], []
        monkeypatch.setattr(srv.worker, "submit", self._submit)

    def _submit(self, prompt, max_new, seed, *, on_token, cancel_evt,
                **_kw):
        self.sinks.append(on_token)
        self.evts.append(cancel_evt)
        self.futs.append(concurrent.futures.Future())
        return self.futs[-1]

    async def open(self, rid="gen:8:1"):
        """A handler started and run up to its first wait on its queue;
        -> (the handler, its pending `__anext__`)."""
        agen = self.srv.GenerateStream(
            wc.TensorRequest(request_id=rid, tensor=wc.make_tensor(PROMPT)),
            _Ctx())
        nxt = asyncio.ensure_future(agen.__anext__())
        n = len(self.sinks)
        while len(self.sinks) == n:
            await asyncio.sleep(0)
        return agen, nxt

    def hand_off(self, tokens):
        """One token a stream, in the streams' order."""
        lm_server._fan_out(
            [(s.put, t) for s, t in zip(self.sinks, tokens)],
            time.perf_counter(), self.srv._rpc)

    def finish(self, i, tokens):
        self.futs[i].set_result(tokens)
        self.sinks[i].put(("done", self.futs[i]))


def test_tokens_handed_off_before_the_deadline_come_before_it(
        srv, timers, monkeypatch):
    """Tokens put on the queue before the timer's item are yielded in
    their order, numbered 1.., and the abort follows them: the deadline
    does not overtake what a stream consumed in time."""
    monkeypatch.setattr(srv, "request_timeout", 0.25)

    async def run():
        d = _Driven(srv, monkeypatch)
        agen, nxt = await d.open()
        got = []
        for t in (11, 12, 13):
            d.hand_off([t])
            msg = await nxt
            got.append((msg.status, _tok(msg)))
            nxt = asyncio.ensure_future(agen.__anext__())
        with pytest.raises(_Aborted) as ei:
            await asyncio.wait_for(nxt, 5)  # woken by the ONE timer
        return got, ei.value.args, d.evts[0]

    before = _misses()
    got, (code, details), evt = asyncio.run(run())
    assert got == [("[lm] token 1", 11), ("[lm] token 2", 12),
                   ("[lm] token 3", 13)]
    assert code == grpc.StatusCode.DEADLINE_EXCEEDED and "0.25s" in details
    assert evt.is_set() and _misses() == before + 1
    assert obs.flight.recorder().events(
        kind="deadline_miss", last=1)[0]["tokens"] == 3
    timer, = timers
    assert timer.cancelled()


def test_a_passed_deadline_yields_no_token_that_still_waits(
        srv, timers, monkeypatch):
    """Tokens that wait on the queue when the deadline passes, in FRONT of
    the timer's item, are not yielded: the clock is read before every
    `get()`."""
    monkeypatch.setattr(srv, "request_timeout", 0.15)

    async def run():
        d = _Driven(srv, monkeypatch)
        agen, nxt = await d.open()
        d.hand_off([21])
        first = _tok(await nxt)
        d.hand_off([22])
        d.hand_off([23])
        await asyncio.sleep(0.3)  # the stream's consumer is late
        with pytest.raises(_Aborted) as ei:
            await agen.__anext__()
        return first, ei.value.args[0]

    first, code = asyncio.run(run())
    assert first == 21 and code == grpc.StatusCode.DEADLINE_EXCEEDED
    assert obs.flight.recorder().events(
        kind="deadline_miss", last=1)[0]["tokens"] == 1
    timer, = timers
    assert timer.cancelled()


def test_a_token_costs_no_array_no_payload_view_and_no_timer(
        srv, timers, monkeypatch):
    """Sixteen streams x eight tokens through the handler: once the
    streams are open (the prompt's own decode behind them), this thread
    calls `np.asarray`, `wirecodec.tensor_payload` and `asyncio.wait_for`
    zero times, and arms no timer beyond the one a request."""
    calls = {"asarray": 0, "tensor_payload": 0, "wait_for": 0}
    me = threading.get_ident()
    armed = []

    def counted(name, real):
        def fn(*args, **kw):
            if armed and threading.get_ident() == me:
                calls[name] += 1
            return real(*args, **kw)
        return fn

    monkeypatch.setattr(np, "asarray", counted("asarray", np.asarray))
    monkeypatch.setattr(wc, "tensor_payload",
                        counted("tensor_payload", wc.tensor_payload))
    monkeypatch.setattr(asyncio, "wait_for",
                        counted("wait_for", asyncio.wait_for))

    async def run():
        d = _Driven(srv, monkeypatch)
        opened = [await d.open(f"gen:8:{i}") for i in range(16)]
        agens = [a for a, _ in opened]
        pending = [nxt for _, nxt in opened]
        wires = []
        armed.append(True)
        for step in range(8):
            d.hand_off([1000 * step + i for i in range(16)])
            for i in range(16):
                wires.append(wc.serialize_response(await pending[i]))
                pending[i] = asyncio.ensure_future(agens[i].__anext__())
        await asyncio.sleep(0)  # every stream is back on its queue
        armed.clear()
        for i in range(16):
            d.finish(i, list(range(8)))
            with pytest.raises(StopAsyncIteration):
                await pending[i]
        return wires

    wires = asyncio.run(run())
    assert calls == {"asarray": 0, "tensor_payload": 0, "wait_for": 0}
    assert len(timers) == 16 and all(t.cancelled() for t in timers)
    for k, wire in enumerate(wires):
        step, i = divmod(k, 16)
        resp = wc.parse_response(wire)
        assert resp.status == f"[lm] token {step + 1}"
        assert _tok(resp) == 1000 * step + i
