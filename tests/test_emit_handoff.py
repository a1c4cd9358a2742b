"""One wake-up a step for all streams (runtime/lm_server.py).

A streamed request registers a `TokenSink` (its event loop, its queue's
`put_nowait`) with the batcher worker. The tokens a loop iteration commits
cross to the event loop in ONE `call_soon_threadsafe` and are fanned out to
the streams' queues on the loop's thread. Held here: the number of
hand-offs a step, the order of a stream's items (commit order, `done`
last), that a dead consumer costs the others nothing, that a plain callable
still works, and that a requeued stream keeps streaming."""

import asyncio
import threading
import time

import jax
import numpy as np
import pytest

from dnn_tpu import chaos
from dnn_tpu.chaos import inject as chaos_inject
from dnn_tpu.comm.client import NodeClient
from dnn_tpu.models import gpt
from dnn_tpu.runtime import lm_server
from dnn_tpu.runtime.lm_server import (
    LMServer,
    TokenSink,
    _BatcherWorker,
    start_lm_server_in_background,
)
from dnn_tpu.runtime.serving import ContinuousBatcher
from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

CFG = gpt.PRESETS["gpt2-test"]
DRAFT = gpt.GPTConfig(block_size=CFG.block_size, vocab_size=CFG.vocab_size,
                      n_layer=1, n_head=2, n_embd=32)


def _prepared(cfg=CFG, seed=0):
    return gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(seed), cfg), cfg)


PROMPTS = [np.array(p, np.int32) for p in
           ([3, 1, 4, 1, 5], [9, 2, 6, 5], [5, 3, 5, 8, 9, 7], [2, 7, 1, 8])]


class _Loop:
    """A real event loop on a thread of its own, behind a
    `call_soon_threadsafe` that writes down what crossed: ("handoff",
    tokens) for a batch of tokens, ("call",) for anything else (a `done`).
    Whoever else has events to order among them (a wrapped step) appends
    to `log` too."""

    def __init__(self):
        self.log = []
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()

    def call_soon_threadsafe(self, fn, *args):
        if fn is lm_server._fan_out:
            self.log.append(("handoff", len(args[0]),
                             threading.current_thread().name))
        else:
            self.log.append(("call",))
        return self.loop.call_soon_threadsafe(fn, *args)

    def stream(self, worker, prompt, max_new, seed, **kw):
        """Submit as `GenerateStream` does; returns (future, queue)."""
        q = asyncio.Queue()
        fut = worker.submit(prompt, max_new, seed,
                            on_token=TokenSink(self, q.put_nowait), **kw)
        fut.add_done_callback(lambda f: self.call_soon_threadsafe(
            q.put_nowait, ("done", f)))
        return fut, q

    def items(self, q):
        """Everything put on `q`, read on the loop's thread up to the
        stream's `done` and whatever trails it (nothing should)."""
        async def drain():
            out = []
            while not out or out[-1][0] != "done":
                out.append(await asyncio.wait_for(q.get(), 60))
            await asyncio.sleep(0.05)
            while not q.empty():
                out.append(q.get_nowait())
            return out
        return asyncio.run_coroutine_threadsafe(drain(), self.loop).result(90)

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=10)
        self.loop.close()


@pytest.fixture
def loop():
    lp = _Loop()
    yield lp
    if not lp.loop.is_closed():
        lp.close()


def _batcher(kind, slots=4):
    if kind == "spec":
        return SpeculativeBatcher(CFG, _prepared(), DRAFT,
                                  _prepared(DRAFT, seed=1), spec_k=3,
                                  slots=slots, max_len=48, prompt_pad=8)
    kw = {"interleaved": {"prefill_chunk_tokens": 8, "overlap": True}}.get(
        kind, {})
    return ContinuousBatcher(CFG, _prepared(), slots=slots, max_len=48,
                             prompt_pad=8, **kw)


def _tokens(items):
    return [v[0] for kind, v in items if kind == "tok"]


def _finish(worker):
    worker.stop()
    worker.join(timeout=30)
    assert not worker.is_alive()


def test_one_handoff_a_step_for_all_streams(loop):
    """Four streams admitted in one iteration and stepped together: each
    first token crosses alone, in the `admit` part and before the step,
    then ONE hand-off a step carries the four streams' tokens; the
    counters read the same."""
    b = _batcher("plain")
    real_step = b.step

    def step():
        loop.log.append(("step",))
        return real_step()

    b.step = step
    w = _BatcherWorker(b)
    streams = [loop.stream(w, p, 6, 10 + i) for i, p in enumerate(PROMPTS)]
    w.start()  # all four are queued: one iteration admits them all
    outs = [fut.result(timeout=120) for fut, _ in streams]
    _finish(w)
    events = [e[:2] for e in loop.log if e[0] != "call"]
    assert events[:5] == [("handoff", 1)] * 4 + [("step",)]
    after = events[4:]
    assert after == [("step",), ("handoff", 4)] * 5, after
    assert {e[2] for e in loop.log if e[0] == "handoff"} == {"lm-batcher"}
    assert w.emit_counts == [4 + 5, 4 + 4 * 5]
    for (_, q), out in zip(streams, outs):
        assert _tokens(loop.items(q)) == [int(t) for t in out]


@pytest.mark.parametrize("kind,max_new", [
    ("plain", 6), ("plain", 1), ("interleaved", 6), ("interleaved", 1),
    ("spec", 9), ("spec", 1)])
def test_stream_items_in_commit_order_done_last(loop, kind, max_new):
    """Each stream's queue holds its tokens in commit order, equal to the
    future's result, with `done` as its last item: for a request that
    retires in the iteration that admitted it (budget 1), for a deferred
    first token (interleaved admission), and for the speculative batcher's
    list of tokens a step."""
    w = _BatcherWorker(_batcher(kind, slots=3))
    streams = [loop.stream(w, p, max_new, 20 + i)
               for i, p in enumerate(PROMPTS)]  # 4 requests, 3 slots
    w.start()
    outs = [fut.result(timeout=120) for fut, _ in streams]
    _finish(w)
    for (fut, q), out in zip(streams, outs):
        items = loop.items(q)
        assert [k for k, _ in items] == ["tok"] * max_new + ["done"]
        assert _tokens(items) == [int(t) for t in out]
        assert items[-1][1] is fut
        stamps = [v[1] for k, v in items if k == "tok"]
        assert stamps == sorted(stamps)
    handoffs, tokens = w.emit_counts
    assert tokens == 4 * max_new and 0 < handoffs <= tokens
    if kind == "spec" and max_new > 1:
        # a step commits several tokens of a stream: fewer hand-offs than
        # one a stream a token
        assert handoffs < tokens


def test_streamed_tokens_equal_unary_over_the_wire():
    """Concurrent streams through `GenerateStream` itself: every client
    reads the unary result of the same seeded request, token by token."""
    port = 59341
    t, stop = start_lm_server_in_background(
        CFG, _prepared(), port=port, slots=4, max_len=48, prompt_pad=8,
        default_max_new=8)
    try:
        c = NodeClient(f"127.0.0.1:{port}")
        want = [[int(x) for x in c.generate(p, max_new_tokens=8,
                                            seed=30 + i)]
                for i, p in enumerate(PROMPTS)]
        got = [None] * len(PROMPTS)

        def read(i):
            ci = NodeClient(f"127.0.0.1:{port}")
            got[i] = list(ci.generate_stream(PROMPTS[i], max_new_tokens=8,
                                             seed=30 + i))
            ci.close()

        readers = [threading.Thread(target=read, args=(i,))
                   for i in range(len(PROMPTS))]
        for r in readers:
            r.start()
        for r in readers:
            r.join(timeout=120)
        assert got == want
        c.close()
    finally:
        stop()


@pytest.mark.parametrize("gone", ["put_raises", "loop_closed"])
def test_a_gone_consumer_costs_the_others_nothing(loop, gone):
    """One stream's queue refuses its tokens, or its event loop is closed:
    the other streams of the batch receive every token, the worker lives
    and serves the next request."""
    w = _BatcherWorker(_batcher("plain"))
    streams = [loop.stream(w, p, 6, 40 + i)
               for i, p in enumerate(PROMPTS[:3])]
    if gone == "put_raises":
        def put(item):
            raise RuntimeError("consumer went away")
        sink = TokenSink(loop, put)
    else:
        dead = asyncio.new_event_loop()
        dead.close()
        sink = TokenSink(dead, lambda item: None)
    lost = w.submit(PROMPTS[3], 6, 43, on_token=sink)
    w.start()
    outs = [fut.result(timeout=120) for fut, _ in streams]
    assert len(lost.result(timeout=120)) == 6
    for (_, q), out in zip(streams, outs):
        items = loop.items(q)
        assert _tokens(items) == [int(t) for t in out]
        assert items[-1][0] == "done"
    fut, q = loop.stream(w, PROMPTS[0], 3, 44)
    assert len(fut.result(timeout=120)) == 3 and w.is_alive()
    assert len(_tokens(loop.items(q))) == 3
    _finish(w)


def test_plain_callable_fires_a_token_from_the_worker_thread(loop):
    """A plain `on_token` callable beside two sinks: called once a token,
    on the worker thread, and not counted among the hand-offs."""
    w = _BatcherWorker(_batcher("plain"))
    called = []
    plain = w.submit(PROMPTS[0], 5, 50, on_token=lambda tok: called.append(
        (tok, threading.current_thread().name)))
    streams = [loop.stream(w, p, 5, 51 + i)
               for i, p in enumerate(PROMPTS[1:3])]
    w.start()
    out = plain.result(timeout=120)
    for fut, _ in streams:
        fut.result(timeout=120)
    _finish(w)
    assert [t for t, _ in called] == [int(t) for t in out]
    assert {name for _, name in called} == {"lm-batcher"}
    assert w.emit_counts[1] == 2 * 5


def test_a_requeued_stream_keeps_streaming(loop):
    """The worker dies with a streamed request still queued (no token
    delivered): the death hook requeues it and the successor worker
    streams it, tokens equal to an undisturbed run; the counters go on
    from the predecessor's."""
    srv = LMServer(CFG, _prepared(), slots=1, max_len=32, prompt_pad=8,
                   default_max_new=6, worker_restarts=2)
    try:
        base = [srv.worker.submit(p, 6, 60 + i).result(timeout=120)
                for i, p in enumerate(PROMPTS[:2])]
        first = srv.worker
        # hold the worker at the top of an iteration until both requests
        # are queued, so that the one slot leaves the stream in the queue
        held, gate = threading.Event(), threading.Event()
        first.submit_control(lambda: (held.set(), gate.wait(60)))
        assert held.wait(30)
        chaos.install({"seed": 0, "faults": [
            {"kind": "step_fault", "at_n": 0, "count": 1}]})
        unary = first.submit(PROMPTS[0], 6, 60)
        fut, q = loop.stream(first, PROMPTS[1], 6, 61)
        gate.set()
        np.testing.assert_array_equal(unary.result(timeout=120), base[0])
        np.testing.assert_array_equal(fut.result(timeout=120), base[1])
        assert srv.worker is not first and not first.is_alive()
        items = loop.items(q)
        assert _tokens(items) == [int(t) for t in base[1]]
        assert items[-1][0] == "done"
        assert srv.worker.emit_counts is first.emit_counts
        assert srv.worker.emit_counts[1] == 6
    finally:
        chaos_inject.uninstall()
        srv.close()


def test_emit_lag_is_stamped_once_a_handoff(loop):
    """The streams' tokens of one step carry one `perf_counter` stamp, the
    worker's at the hand-off (what `serving_emit_lag_seconds_*` start
    from), not later than the loop thread's own clock when it fans out."""
    w = _BatcherWorker(_batcher("plain"))
    streams = [loop.stream(w, p, 4, 70 + i) for i, p in enumerate(PROMPTS)]
    w.start()
    for fut, _ in streams:
        fut.result(timeout=120)
    _finish(w)
    now = time.perf_counter()
    by_stream = [[v[1] for k, v in loop.items(q) if k == "tok"]
                 for _, q in streams]
    for step in range(1, 4):  # stamps of the three common steps
        assert len({stamps[step] for stamps in by_stream}) == 1
    assert all(s <= now for stamps in by_stream for s in stamps)


# ----------------------------------------------------------------------
# the event-loop thread's own clock (obs/timeline.RpcLoopClock)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def wire():
    """A served daemon on a background thread, its clients and the
    streams' reader: `stream_all(n_new)` reads one stream a prompt."""
    port = 59343
    _, stop = start_lm_server_in_background(
        CFG, _prepared(), port=port, slots=4, max_len=48, prompt_pad=8,
        default_max_new=8)

    def stream_all(n_new):
        got = [None] * len(PROMPTS)

        def read(i):
            ci = NodeClient(f"127.0.0.1:{port}")
            got[i] = list(ci.generate_stream(PROMPTS[i],
                                             max_new_tokens=n_new,
                                             seed=40 + i))
            ci.close()

        readers = [threading.Thread(target=read, args=(i,))
                   for i in range(len(PROMPTS))]
        for r in readers:
            r.start()
        for r in readers:
            r.join(timeout=120)
        assert all(len(g) == n_new for g in got), got
        time.sleep(0.1)  # the loop thread writes the last message out

    stream_all(3)  # every program compiled
    try:
        yield stop.servicer, stream_all
    finally:
        stop()


def _rpc_parts(srv):
    rpc = srv._rpc
    return dict(rpc.seconds, select=rpc.select_seconds())


@pytest.mark.parametrize("traffic", ["served", "idle"])
def test_rpc_loop_parts_sum_to_the_wall_time(wire, traffic):
    """The four parts of the event-loop thread's time sum to the wall
    time of a window, whatever it did in it: `select` is nearly all of an
    idle server's (the block in progress counts at a read), and serving
    streams takes time from it for the other three."""
    srv, stream_all = wire
    before, t0 = _rpc_parts(srv), time.perf_counter()
    if traffic == "served":
        stream_all(8)
    else:
        time.sleep(0.4)
    wall = time.perf_counter() - t0
    after = _rpc_parts(srv)
    took = {p: after[p] - before[p] for p in after}
    assert sum(took.values()) == pytest.approx(wall, rel=0.02)
    assert all(v >= 0 for v in took.values())
    if traffic == "idle":
        assert took["select"] > 0.98 * wall
        assert took["fan_out"] == took["token"] == 0.0
    else:
        assert took["fan_out"] > 0 and took["token"] > 0 and took["rest"] > 0
        assert took["token"] + took["fan_out"] + took["rest"] < wall


def test_a_scrape_never_counts_a_select_twice():
    """`select_seconds` adds the block in progress only if it is the same
    block before and after it read the total: a block that ends in between
    is in the total already."""
    from dnn_tpu.obs.timeline import RpcLoopClock

    now = [10.0]
    clock = RpcLoopClock(now=lambda: now[0])
    clock.select_begins()
    now[0] = 12.5
    assert clock.select_seconds() == 2.5  # in progress
    clock.select_returns()
    assert clock.select_seconds() == clock.seconds["select"] == 2.5
    t = now[0] = 13.0
    now[0] = 13.25
    clock.section_ends("token", t)
    now[0] = 14.0
    clock.select_begins()  # the run: 1.5 s, 0.25 of it a token's
    assert clock.seconds["rest"] == 1.25 and clock.seconds["token"] == 0.25
    assert clock.iterations == 1
    now[0] = 15.0
    assert sum(clock.seconds.values()) - clock.seconds["select"] \
        + clock.select_seconds() == 5.0


@pytest.mark.parametrize("ahead", [0.0, 0.25])
def test_a_scrape_at_any_point_of_the_loops_stamps_never_steps_back(
        monkeypatch, ahead):
    """`select_seconds` against every interleaving of its reads with the
    loop thread's writes, from ONE thread and an injected clock: two
    `select()`s are stamped through a clock whose every write of
    `_selecting` and of the total, and every clock read, is a MOMENT of a
    recorded history; the scrape's own code then runs over a view whose
    every read is answered from a moment of its choosing, no earlier than
    its last (a yielded scrape resumes later), for every such choice. Its
    clock reads `ahead` of the loop's last. No scrape counts a select
    twice (it never passes the select time there was when it ended), none
    falls short of the total there was when it began, and no scrape that
    begins where another ended reads less."""
    from dnn_tpu.obs import timeline
    from dnn_tpu.obs.timeline import RpcLoopClock

    history = []  # (`_selecting`, the total, the clock's last value)
    stamps = [1.0, 2.5, 4.0, 7.0]  # two selects' begin, end
    spans, ticks = list(zip(stamps[::2], stamps[1::2])), iter(stamps)

    class Stamped(RpcLoopClock):
        """The loop thread's side, each write and clock read a moment."""
        t = 0.0

        def moment(self):
            history.append((RpcLoopClock._selecting.__get__(self),
                            dict.__getitem__(self.seconds, "select"), self.t))

        @property
        def _selecting(self):
            return RpcLoopClock._selecting.__get__(self)

        @_selecting.setter
        def _selecting(self, since):
            RpcLoopClock._selecting.__set__(self, since)
            if history:
                self.moment()

    class Seconds(dict):
        def __setitem__(self, part, value):
            super().__setitem__(part, value)
            clock.moment()

    def tick():
        clock.t = next(ticks)
        clock.moment()
        return clock.t

    clock = Stamped(now=tick)
    clock.seconds = Seconds(clock.seconds)
    clock.moment()
    for _ in spans:
        clock.select_begins()
        clock.select_returns()
    assert clock.seconds["select"] == sum(e - b for b, e in spans) == 4.5
    last = len(history) - 1

    def select_time(t):
        """The time inside `select()` up to the clock's t."""
        return sum(max(0.0, min(e, t) - b) for b, e in spans)

    class Scrape:
        """What `select_seconds` reads, each read at a scripted moment."""

        def __init__(self, script):
            self.script, self.taken, self.floor = script, [], 0

        def read(self, field):
            i = len(self.taken)
            at = self.script[i] if i < len(self.script) else self.floor
            assert self.floor <= at <= last
            self.taken.append(at)
            self.floor = at
            return history[at][field]

        def yielded(self, _seconds):
            self.floor = min(self.floor + 1, last)

        _selecting = property(lambda self: self.read(0))
        seconds = property(lambda self: {"select": self.read(1)})

        def _now(self):
            return self.read(2) + ahead

    began, ended = {}, {}  # a moment -> the least / the most a scrape read
    script, runs = [], 0
    monkeypatch.setattr(timeline.time, "sleep",
                        lambda seconds: scrape.yielded(seconds))
    while True:
        scrape = Scrape(script)
        seen = RpcLoopClock.select_seconds(scrape)
        first, end = scrape.taken[0], scrape.taken[-1]
        assert history[first][1] <= seen <= select_time(
            history[end][2] + ahead), scrape.taken
        began[first] = min(seen, began.get(first, seen))
        ended[end] = max(seen, ended.get(end, seen))
        runs += 1
        script = scrape.taken
        while script and script[-1] == last:
            script.pop()
        if not script:
            break
        script[-1] += 1
    assert runs > 1000 and set(began) == set(range(last + 1))
    for end, most in ended.items():
        for first, least in began.items():
            assert first < end or most <= least, (end, first)


def test_rpc_sections_are_counted_once_and_spans_only_in_a_capture(
        wire, monkeypatch):
    """Every token the worker hands off is one `token` section on the loop
    thread and every hand-off one `fan_out` section with one lag; with no
    capture recording no `rpc.*` annotation object is built, and while one
    records there is one `rpc.fan_out` a hand-off (`tokens=` summing to
    the tokens, `handoff=` the counter's values), one `rpc.tokens` marker
    a run of the loop that built messages (`tokens=` summing to the
    tokens again: no annotation a token) and `rpc.run`s around them, all
    from the loop's thread."""
    from dnn_tpu.obs import profile

    srv, stream_all = wire
    built = []

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, name, **stats):
            built.append((name, stats, threading.get_ident()))
            super().__init__(name, **stats)

    monkeypatch.setattr(profile, "_trace_annotation", Spy)
    counts = srv._emit_counts
    stream_all(5)
    assert not profile.capturing()
    assert [b for b in built if b[0].startswith("rpc")] == []
    assert srv._rpc.fan_out_lag[1] == counts[0]  # a lag a hand-off
    assert srv._rpc.tokens == counts[1]  # a `token` section a token
    handoffs, tokens = counts
    with profile.mark_recording():
        stream_all(6)
    rpc = [b for b in built if b[0].startswith("rpc")]
    assert {b[0] for b in rpc} == {"rpc.run", "rpc.fan_out", "rpc.tokens"}
    assert len({b[2] for b in rpc}) == 1  # one thread: the loop's
    assert rpc[0][2] != srv.worker.ident
    n_new = 6 * len(PROMPTS)
    assert counts[1] - tokens == n_new
    built_in_runs = [b[1]["tokens"] for b in rpc if b[0] == "rpc.tokens"]
    assert sum(built_in_runs) == n_new == srv._rpc.tokens - tokens
    assert 0 < len(built_in_runs) <= n_new and min(built_in_runs) > 0
    fans = [b[1] for b in rpc if b[0] == "rpc.fan_out"]
    assert len(fans) == counts[0] - handoffs
    assert sum(f["tokens"] for f in fans) == n_new
    assert [f["handoff"] for f in fans] == list(
        range(handoffs + 1, counts[0] + 1))
    runs = [b[1]["iter"] for b in rpc if b[0] == "rpc.run"]
    assert runs == sorted(set(runs)) and len(runs) >= len(fans)
    assert srv._rpc.fan_out_lag[1] == counts[0]
    assert 0 < srv._rpc.fan_out_lag[0] / counts[0] < 1.0
    n_built = len(built)
    stream_all(3)  # the capture has ended: at most the open run closes
    assert len([b for b in built[n_built:] if b[0].startswith("rpc")]) == 0
