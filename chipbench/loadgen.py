"""Load generation against the LM daemon, timed on the client's side.

Every request is streamed through `NodeClient.generate_stream` and each
token is stamped with `time.perf_counter()` as it arrives (the timing of
`dnn_tpu/workloads/runner.py`'s `_GrpcTarget`, copied). What is replaced
is the submission: the ORDER in which requests reach the daemon is decided
by one submitter thread, never by a race between client threads.

  * `Backlog`: the submitter issues the request list in order and keeps
    exactly `outstanding` in flight — the next request goes out when one
    completes. Only the first `outstanding` go out together, and those are
    paced `FILL_GAP_S` apart so that they too arrive in list order.
  * `OpenLoop`: the submitter sleeps to each request's due time and sends
    it whatever the daemon is doing; requests are timed from when they were
    DUE, and how late the generator ran is reported.

A reader thread per in-flight request only drains its own stream. This
process never initializes a JAX backend.

A traffic file names its generator (`"generator": "loadgen:Backlog"`), so
a traffic kind that needs another way of submitting brings a class in a
module of its own. What `serve.py` asks of one: `from_traffic(client,
traffic, seed=, vocab=, seconds=)`, `start()`, `window_start()` (blocks
until the measured window may begin and returns its start),
`unanswered(t0, t1)`, `report()` (lines for the window row; raises where
the run is not valid), `snapshot()`, `stop`, `finish()`.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from chipbench import stats, traffic as tg

__all__ = ["Sent", "Backlog", "OpenLoop", "FILL_GAP_S"]

FILL_GAP_S = 0.01
RPC_TIMEOUT_S = 600.0
ANCHOR_DEADLINE_S = 300.0
FIRST_TOKEN_GRACE_S = 20.0


class Sent:
    """One request as the client saw it. `t0` is where its clock starts
    (submit time in a backlog, due time in an open loop)."""

    __slots__ = ("req", "t0", "t_submit", "times", "tokens", "error", "done")

    def __init__(self, req, t0, t_submit):
        self.req = req
        self.t0 = t0
        self.t_submit = t_submit
        self.times: List[float] = []
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.done = False


class _Generator:
    def __init__(self, client, requests):
        self.client = client
        self.requests = requests
        self.sent: List[Sent] = []
        self.stop = threading.Event()
        self._lock = threading.Lock()
        self._readers: List[threading.Thread] = []
        self._submitter = threading.Thread(target=self._submit_all,
                                           name="submitter", daemon=True)

    def start(self):
        self._submitter.start()

    def _send(self, req, t0):
        s = Sent(req, t0 if t0 is not None else time.perf_counter(),
                 time.perf_counter())
        with self._lock:
            self.sent.append(s)
        th = threading.Thread(target=self._read, args=(s,), daemon=True)
        self._readers.append(th)
        th.start()
        return s

    def _read(self, s: Sent):
        try:
            for tok in self.client.generate_stream(
                    s.req.prompt, max_new_tokens=s.req.max_new,
                    timeout=RPC_TIMEOUT_S):
                s.times.append(time.perf_counter())
                s.tokens.append(tok)
                if self.stop.is_set():
                    return
            s.done = True
        except Exception as e:  # noqa: BLE001 — recorded, judged by caller
            if not self.stop.is_set():
                s.error = repr(e)
        finally:
            self._on_finished(s)

    def _on_finished(self, s: Sent):
        pass

    def unanswered(self, t0: float, t1: float) -> int:
        """Requests of the window that are owed an answer and have none."""
        return 0

    def report(self) -> dict:
        return {}

    def snapshot(self) -> List[Sent]:
        with self._lock:
            return list(self.sent)

    def finish(self, join_s: float = 30.0):
        """Stop submitting and reading. The caller closes the client's
        channel first, which ends every stream still open."""
        self.stop.set()
        self._wake()
        self._submitter.join(join_s)
        for th in self._readers:
            th.join(join_s)
        alive = [th for th in [self._submitter] + self._readers
                 if th.is_alive()]
        if alive:
            raise RuntimeError(f"{len(alive)} load-generator threads did "
                               "not end")

    def _wake(self):
        pass


class Backlog(_Generator):
    def __init__(self, client, requests, *, outstanding: int,
                 anchor_index: int):
        super().__init__(client, requests)
        self.outstanding = int(outstanding)
        self.anchor_index = int(anchor_index)
        self._free = threading.Semaphore(self.outstanding)
        self.exhausted = False

    @classmethod
    def from_traffic(cls, client, traffic, *, seed, vocab, seconds):
        return cls(client, tg.make_requests(traffic, seed, vocab),
                   outstanding=traffic["outstanding"],
                   anchor_index=traffic["anchor_index"])

    def window_start(self) -> float:
        return self.first_token_time(self.anchor_index, ANCHOR_DEADLINE_S)

    def report(self) -> dict:
        if self.exhausted:
            raise RuntimeError("the backlog ran out before the window "
                               "closed: raise `requests` in the traffic file")
        return {}

    def _submit_all(self):
        for i, req in enumerate(self.requests):
            self._free.acquire()
            if self.stop.is_set():
                return
            self._send(req, None)
            if i < self.outstanding - 1:
                time.sleep(FILL_GAP_S)
        self.exhausted = True

    def _on_finished(self, s):
        self._free.release()

    def _wake(self):
        self._free.release()

    def first_token_time(self, index: int, deadline_s: float) -> float:
        """Block until request `index` has streamed its first token."""
        t_end = time.perf_counter() + deadline_s
        while time.perf_counter() < t_end:
            sent = self.snapshot()
            if len(sent) > index:
                s = sent[index]
                if s.times:
                    return s.times[0]
                if s.error:
                    raise RuntimeError(f"anchor request failed: {s.error}")
            time.sleep(0.005)
        raise RuntimeError(f"request {index} streamed no token within "
                           f"{deadline_s:.0f}s")


class OpenLoop(_Generator):
    def __init__(self, client, requests, *, warm_s: float):
        super().__init__(client, requests)
        self.warm_s = float(warm_s)
        self.t_start: Optional[float] = None
        self.lateness: List[float] = []
        self._wakeup = threading.Event()

    @classmethod
    def from_traffic(cls, client, traffic, *, seed, vocab, seconds):
        warm_s = float(traffic["warm_s"])
        return cls(client, tg.make_requests(traffic, seed, vocab,
                                            horizon_s=warm_s + seconds),
                   warm_s=warm_s)

    def window_start(self) -> float:
        t0 = self.t_start + self.warm_s
        time.sleep(max(0.0, t0 - time.perf_counter()))
        return t0

    def unanswered(self, t0: float, t1: float) -> int:
        """Wait (at most FIRST_TOKEN_GRACE_S) until every request of the
        schedule is sent and those due in [t0, t1) have a first token;
        the number still unsent or unanswered."""
        t_grace = time.perf_counter() + FIRST_TOKEN_GRACE_S
        while True:
            sent = self.snapshot()
            waiting = sum(1 for s in sent
                          if t0 <= s.t0 < t1 and not s.times and not s.error)
            missing = waiting + len(self.requests) - len(sent)
            if not missing or time.perf_counter() > t_grace:
                return missing
            time.sleep(0.02)

    def report(self) -> dict:
        late = self.lateness
        return {"generator_lateness_ms": {
            "p50": 1e3 * stats.percentile(late, 50) if late else None,
            "max": 1e3 * max(late) if late else None}}

    def start(self):
        self.t_start = time.perf_counter()
        super().start()

    def _submit_all(self):
        for req in self.requests:
            due = self.t_start + req.due_s
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or self.stop.is_set():
                    break
                self._wakeup.wait(min(wait, 0.5))
            if self.stop.is_set():
                return
            self.lateness.append(time.perf_counter() - due)
            self._send(req, due)

    def _wake(self):
        self._wakeup.set()
