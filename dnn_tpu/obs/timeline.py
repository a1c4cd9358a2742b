"""Step-timeline attribution: where does one decode step's wall time go?

ROADMAP item 4 claims the post-MBU gap is serialization — host dispatch
between steps, prefill stalling decode, per-token host syncs for
sampling — but until this module nothing in the repo DECOMPOSED a
decode step into those phases: MBU prices bytes, the loop-lag sanitizer
times callbacks, the fleet stitcher attributes inter-stage bubbles.
This is the intra-step instrument, in two connected halves:

  * **StepClock** — the serving step loop's phase clock. The
    ContinuousBatcher (and its speculative override) splits every
    decode iteration into named contiguous phases:

        admit     submit() end-to-end: validation, slot install,
                  prefill chunks, first-token sample (accumulated onto
                  the NEXT step's record — admits happen between steps);
                  split by ADMIT_PARTS into self / prefill / first_token
                  / install, of which only self and install are host
                  work the device does not overlap
        host      step-entry bookkeeping before the device call
                  (bucket growth, constraint-row flush)
        dispatch  the jit call itself, call-to-return — host time spent
                  handing the program to the runtime (the device begins
                  executing inside this window)
        wait      dispatch-return -> result-on-host: the blocking
                  device->host sync the per-token sampling commit
                  forces (np.asarray of the committed tokens — the
                  moral equivalent of block_until_ready)
        commit    the host slot loop: token append, stop/eos/constraint
                  checks, retirement (sampling/detokenize bookkeeping)
        obs       the step's one bulk registry update + goodput feed

    Derived series (definitions the item-4 overlap PR is judged by):

        device_s        = dispatch + wait   (the window the compiled
                          step program is in flight)
        host_s          = admit + host + commit + obs  (host work NOT
                          overlapped with the device program)
        host_fraction   = host_s / wall     — the host-serialization
                          share of step wall time.
                          Chunked-prefill interleave removes the admit
                          convoy; double-buffered dispatch hides
                          host/commit/obs under device steps.

    All series land in the existing registry behind the one-None-check
    DNN_TPU_OBS gate: `begin()` returns None when the gate is off, and
    every producer site guards on that one None. Scrape-time CALLABLE
    gauges (step.host_fraction / step.per_sec / step.last_wall_ms, and
    the cumulative totals
    step.steps_total / step.tokens_advanced_total /
    step.phase_seconds_total{phase=} / step.admit_seconds_total{part=}
    and the loop's step.loop_seconds_total{part=} (below),
    over a paged KV pool step.attn_live_blocks_total /
    step.attn_table_blocks_total (the blocks the paged decode kernel
    walks, of those the slots' tables have) and, where a decode program
    calls that kernel, step.attn_groups_total /
    step.attn_full_groups_total (the groups of blocks it walked, and
    the whole ones, whose copies it awaits — and from a slot's second
    group on starts — as straight-line code),
    and for a model with experts moe.layer_calls_total /
    moe.assignments_total / moe.active_experts_total /
    moe.peak_expert_rows_total / moe.rows_permuted_total /
    moe.extra_rounds_total{program=decode|prefill} — what the expert
    layers of the step and chunk programs cost, as the programs
    themselves counted it — exact at every scrape to the last ended
    step; for a model whose attention selects what it reads
    dsa.layer_calls_total / dsa.candidate_positions_total /
    dsa.selected_positions_total / dsa.walked_positions_total
    {program=decode|prefill}, counted on the host from each slot's
    position; for a model whose cache is a
    compressed latent mla.layer_calls_total /
    mla.cached_positions_total / mla.query_pairs_total{program=}, counted
    the same way) + fixed-bucket histograms
    (step.phase_seconds{phase=...}, step.wall_seconds). Phase-boundary
    timestamps are ring-buffered for /stepz. While a profiler capture
    records (POST /profilez), the same boundaries are ALSO written into
    the capture as `step` / `step.<phase>` annotations (_StepSpans), and
    submit() writes `admit` / `admit.prefill` / `admit.first_token` /
    `admit.install`: host spans on the device trace's own clock, which
    is what attributes a device idle gap to a phase.

  * **analyze()** — device-trace analysis: parses the gzipped Perfetto
    JSON the obs/profile.py Profiler already spools (stdlib gzip+json,
    no new deps) into structured numbers — per-track busy fraction,
    device busy/idle inside the capture window (the armed window, from
    the capture's sidecar `meta.json`), the host-gap histogram between
    consecutive device ops (the serialization bubbles made visible),
    top-K ops by device time.

    **What is partitioned.** The six phases partition the inside of
    `step()` and `submit()`, and until PR 37 nothing else: what the
    worker did BETWEEN those calls (heartbeat, control ops, cancels,
    the work around `submit()`, handing tokens to the streams,
    publishing results, waiting for an arrival) was in no phase and
    under no span. The worker's loop (`lm_server._BatcherWorker.run`)
    now reports it through `loop_part()`, as LOOP_PARTS:

        pre       heartbeat, control ops, tick, abandon / drain checks,
                  cancels
        wait      blocked on the request queue with nothing active,
                  queued or held (the one part that is not a cost)
        admit     the admission loop LESS the `submit()` walls the
                  `admit` phase already holds: flight record,
                  histograms, the first token's hand-off
        emit      after the step: the watchdog's step_done, one
                  hand-off a token a stream, publishing finished
                  requests (and the few microseconds around `step()`)

    step.loop_seconds_total{part=} with step.phase_seconds_total{phase=}
    partition the worker thread's time: over a window the ten series
    sum to the window (to the part in progress at each scrape). While a
    capture records the parts are `loop` / `loop.<part>` annotations
    (_LoopSpans, `iter=` stat; `loop.step` is the call of step(), with
    the `step*` spans nested inside, as the `admit*` spans are in
    `loop.admit`), and a retirement's device edits are
    `step.commit.retire` (`rid`, `slot`) under `step.commit`.

    **No CPU clock.** A wall clock cannot tell a phase in which the
    worker ran from one in which it stood ready and waited for the
    interpreter lock; the thread's CPU clock (`time.thread_time`) could,
    and is NOT read here: on the chip's host one read costs 5.9 us (a
    system call; `perf_counter` 0.07 us) and the clock advances in
    steps of 10 ms and charges a quarter of a blocked thread's time to
    it (my chip runs, PR 37; PERF.md section 6), so a stamp a phase
    would cost a step more than all its other marks and read noise.
    What the threads burn is read at scrape time only
    (`process.thread_cpu_seconds_total`, lm_server.py), over windows
    long enough for such a clock.

    **The other thread.** The gRPC aio server's event loop runs on a
    thread of its own, which takes each step's tokens from the worker
    in one hand-off and streams them. Its wall time divides into
    RPC_PARTS, accumulated by that thread (RpcLoopClock) and read at a
    scrape as `serving.rpc_loop_seconds_total{part=}`:

        select    blocked in the loop's selector: nothing to do
        fan_out   `_fan_out`: a hand-off's tokens onto their streams'
                  queues
        token     in `GenerateStream`, from the `q.get()` that returns a
                  token to the `yield` of its message: the lag
                  bookkeeping, `np.asarray`, the protobuf
        rest      everything else between two `select()`s: asyncio's
                  task wake-ups and timers, gRPC's serialization and
                  write, the unary front, preflight

    with `serving.rpc_loop_iterations_total` and
    `serving.fan_out_lag_seconds_{sum,count}`. Over a window the four
    sum to the window (to the run in progress at a scrape). While a
    capture records the runs are `rpc.run` annotations (`iter=`) with
    `rpc.fan_out` (`tokens=`, `handoff=`) nested in them and, at the
    end of a run that built messages, one `rpc.tokens` marker with
    their count (`tokens=`; with an annotation a token a capture slowed
    the steps ~3 points more than the parent's, on the chip), on that
    thread's line of the host plane.

Served via GET /stepz (JSON; ?format=prom) on the obs endpoint
and `python -m dnn_tpu.obs timeline [--url URL | PATH]`; the chip
benchmark reads the same totals and spans (chipbench/spans.py,
chipbench/hosttime.py; PERF.md section 3).

No jax import anywhere in this module — the clock is pure
perf_counter bookkeeping and analyze() is stdlib-only, so the CLI
works on any host (the obs/__main__.py contract).
"""

from __future__ import annotations

import asyncio
import glob
import gzip
import json
import os
import selectors
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

from dnn_tpu import obs as _obs
from dnn_tpu.obs import profile as _profile
from dnn_tpu.utils.metrics import labeled

__all__ = ["StepClock", "PHASES", "STEP_BUCKETS", "analyze",
           "active_clock", "render_report", "RpcLoopClock", "RPC_PARTS",
           "StampedSelector", "rpc_event_loop"]

#: phase names, in within-step order (admit precedes the step proper)
PHASES = ("admit", "host", "dispatch", "wait", "commit", "obs")

#: histogram bounds for phase/wall series (seconds): decode phases run
#: tens of µs (host bookkeeping) through seconds (a cold dispatch)
STEP_BUCKETS = (2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
                0.01, 0.025, 0.05, 0.1, 0.25, 1.0, 5.0)

_HOST_PHASES = ("admit", "host", "commit", "obs")
_DEVICE_PHASES = ("dispatch", "wait")

#: what one admission's wall time divides into: `prefill` (dispatching
#: the chunk programs), `install` (building the inputs of the
#: finish-and-install program and launching it), `first_token` (the
#: device-to-host read the host then waits for the prefill in), and
#: `self` — the rest: validation, slot and block allocation, prefix
#: lookup. submit() stamps the three inner intervals; `self` is what
#: they leave of the admit slice.
ADMIT_PARTS = ("self", "prefill", "first_token", "install")
_NO_PARTS = (0.0, 0.0, 0.0)
#: what the worker's loop does outside step() and submit() (module
#: docstring). `loop_part("step")` names the call of step() itself: the
#: microseconds around the step's own record go to `emit`.
LOOP_PARTS = ("pre", "wait", "admit", "emit")
_LOOP_ACCRUES = {"pre": 0, "wait": 1, "admit": 2, "emit": 3, "step": 3}
# the moe.* cumulative series (StepClock.note_moe), each labeled with the
# program whose expert layers it counts
MOE_PROGRAMS = ("decode", "prefill")
MOE_SERIES = ("layer_calls_total", "assignments_total",
              "active_experts_total", "peak_expert_rows_total",
              "rows_permuted_total", "extra_rounds_total")
# the dsa.* cumulative series (StepClock.note_dsa), same programs: what a
# learned-sparse-attention model's indexer scored, what it selected, and
# the (query, column) pairs the masked prefill kernel's grid walked
DSA_SERIES = ("layer_calls_total", "candidate_positions_total",
              "selected_positions_total", "walked_positions_total")
# the mla.* cumulative series (StepClock.note_mla), same programs: the
# cached latents a latent-attention model's layers read, and a prefill
# chunk's causal (query, position) pairs
MLA_SERIES = ("layer_calls_total", "cached_positions_total",
              "query_pairs_total")

#: the phase whose annotation opens when a mark closes phase P (None
#: after the last): the in-step order of PHASES, one definition
_PHASE_AFTER = dict(zip(PHASES[1:], PHASES[2:] + (None,)))


class _StepSpans:
    """One step's `step` annotation and, under it, the annotation of the
    phase now running: the StepClock phases written into a recording
    profiler capture (jax.profiler.TraceAnnotation, via obs/profile.py),
    on the device trace's own clock. Exists only while a capture
    records; `begin()` opens it, each mark moves it on, `end()` closes
    it. Every annotation carries `step=<index>`, the value of
    `steps_total` when the step began."""

    __slots__ = ("step", "phase", "idx")

    def __init__(self, idx: int, first: str):
        self.idx = idx
        self.step = _profile.open_span("step", step=idx)
        self.phase = _profile.open_span("step." + first, step=idx)

    def next(self, closed: str):
        """The mark that closed phase `closed` was just stamped."""
        _profile.close_span(self.phase)
        nxt = _PHASE_AFTER[closed]
        self.phase = None if nxt is None else _profile.open_span(
            "step." + nxt, step=self.idx)

    def close(self):
        _profile.close_span(self.phase)
        _profile.close_span(self.step)


class _LoopSpans:
    """One iteration of the worker's loop as a `loop` annotation and,
    under it, the annotation of the part now running: `loop.<part>`, and
    `loop.step` around the call of step(), whose `step*` spans nest in
    it (what it keeps of itself is the call's own microseconds). Each
    part's annotation opens where the last one closed, so `loop` keeps
    nothing of itself. Exists only while a capture records, as
    _StepSpans does; every annotation carries `iter=<n>`."""

    __slots__ = ("loop", "part", "idx")

    def __init__(self, idx: int, part: str):
        self.idx = idx
        self.loop = _profile.open_span("loop", iter=idx)
        self.part = _profile.open_span("loop." + part, iter=idx)

    def enter(self, part: str):
        _profile.close_span(self.part)
        self.part = _profile.open_span("loop." + part, iter=self.idx)

    def close(self):
        _profile.close_span(self.part)
        _profile.close_span(self.loop)


#: shared empty admit-slice seq — most steps have no admissions, and
#: a per-step allocation is host work paid inside every decode step;
#: end() REPLACES the attribute (never appends) when slices exist, and
#: every consumer (fold/summary/stepz) only iterates, so sharing is safe
_NO_ADMITS: tuple = ()


class _StepRec:
    """One step's phase boundaries: t0 at step entry, then (phase, t)
    marks in order — phase P's duration is its mark minus the previous
    boundary. `phases`/`wall` are folded LAZILY (`_fold`) at flush or
    scrape time: the producer path only stamps timestamps. The worker
    thread owns the record until `StepClock.end` publishes it into the
    ring; after that it is append-only, and the idempotent fold from a
    scrape thread recomputes the same values it would assign twice."""

    __slots__ = ("t0", "t_end", "marks", "n_adv", "wall", "phases",
                 "admit_slices", "admit_parts", "mixed", "spans", "moe",
                 "loop")

    def __init__(self, t0: float):
        self.t0 = t0
        self.t_end = t0
        self.marks: list = []
        self.n_adv = 0
        self.wall = 0.0
        self.phases: "Optional[Dict[str, float]]" = None
        self.admit_slices = _NO_ADMITS
        # per admit slice, the seconds of its (prefill, first_token,
        # install) parts — the slice's remainder is admission's own host
        # time (ADMIT_PARTS)
        self.admit_parts = _NO_ADMITS
        # the open profiler annotations of this step while a capture
        # records (_StepSpans), else None — producers check this ONE
        # attribute after each mark
        self.spans: "Optional[_StepSpans]" = None
        # mixed = this step's dispatch folded an interleaved prefill
        # chunk (serving prefill_chunk_tokens) — /stepz distinguishes
        # interleaved-prefill steps from pure-decode steps with it
        self.mixed = False
        # {program: [layer calls, assignments, active experts, peak
        # expert rows]} of the expert layers noted since the last step
        # ended (StepClock.note_moe), or None: a model without experts
        self.moe: "Optional[Dict[str, list]]" = None
        # seconds of each LOOP_PARTS part the worker's loop spent outside
        # step() and submit() since the last record ended, or None: a
        # clock nobody reports a loop to
        self.loop: "Optional[list]" = None


def _fold(rec: _StepRec) -> _StepRec:
    """Fold a published record's marks into per-phase durations (in
    place, idempotent). Runs off the step path — at flush and scrape
    time only."""
    if rec.phases is not None:
        return rec
    phases: Dict[str, float] = {}
    t = rec.t0
    for name, tm in rec.marks:  # marks are unique per step
        phases[name] = tm - t
        t = tm
    if rec.t_end > t:
        # remainder after the last mark (end() stamps right after the
        # "obs" mark, so this is ns-scale) stays attributed
        phases["obs"] = phases.get("obs", 0.0) + (rec.t_end - t)
    admit_s = sum(t1 - t0 for t0, t1 in rec.admit_slices)
    if admit_s:
        phases["admit"] = phases.get("admit", 0.0) + admit_s
    rec.wall = (rec.t_end - rec.t0) + admit_s
    rec.phases = phases
    return rec


class StepClock:
    """Per-phase decode-step clock. Attach post-construction like the
    goodput tracker (`batcher.step_clock = StepClock().install()`);
    the batcher's step()/submit() feed it behind the obs gate.

    Producer protocol (what serving.py calls):

        rec = clock.begin()            # None when the obs gate is off
        ... bookkeeping ...            # -> "host"
        clock.mark(rec, "host")
        ... device call ...            # -> "dispatch"
        clock.mark(rec, "dispatch")
        ...
        clock.end(rec, n_adv)          # publishes + one bulk registry
                                       # update (counters, histograms,
                                       # idempotent gauge re-register)

    submit() reports its whole wall as `note_admit(t0)`; pending admit
    slices attach to the NEXT step's record (admissions happen between
    steps, and the worker loop's iteration = admits + one step).

    Thread safety: the worker thread produces; /stepz scrapes read the
    ring under the lock. `now` is injectable for deterministic tests:
    every stamp (begin/mark/end/note_admit) reads it.

    Registry cost: per-step observations are accumulated locally and
    FLUSHED in one bulk update every `FLUSH_EVERY` steps (summary()/
    render_prom() flush first, so scrapes stay fresh) — per-step
    histogram observes tax the very decode step this clock exists to
    measure (a lock and a reservoir update per series per step).
    The derived gauges are scrape-time callables over the ring, so
    they are exact at every scrape regardless of the flush cadence.
    """

    FLUSH_EVERY = 32

    def __init__(self, capacity: int = 256, *, registry=None,
                 now=time.perf_counter):
        self.capacity = int(capacity)
        self._ring: "deque[_StepRec]" = deque(maxlen=self.capacity)
        self._now = now
        self._lock = threading.Lock()
        self._pending_admit: list = []
        self._pending_parts: list = []
        # cumulative since process start, EXACT at every scrape to the
        # last ended step: plain numbers the producer adds to in end() /
        # note_admit(), read by scrape-time callables (_gauges) — never
        # through the FLUSH_EVERY bulk, so a /metrics window difference
        # of them covers the whole window
        self.steps_total = 0
        self.tokens_advanced_total = 0
        self.phase_seconds_total = {p: 0.0 for p in PHASES}
        self.admit_seconds_total = {p: 0.0 for p in ADMIT_PARTS}
        # the worker's loop outside step() and submit() (loop_part): the
        # part it is in and where that began, the seconds step records
        # and admissions have claimed inside it, the parts accrued since
        # the last record ended, the open annotations while a capture
        # records
        self.loop_seconds_total = {p: 0.0 for p in LOOP_PARTS}
        self._loop_part: Optional[str] = None
        self._loop_t = self._loop_in_t = 0.0
        self._pending_loop: Optional[list] = None
        self._loop_spans: Optional[_LoopSpans] = None
        self._loop_iter = 0
        # expert layers (note_moe): per program, MOE_SERIES in order
        self.moe_total = {p: [0] * len(MOE_SERIES) for p in MOE_PROGRAMS}
        self.moe_latent_rows_total = dict.fromkeys(MOE_PROGRAMS, 0)
        # a paged KV pool's blocks (note_attn_blocks): live, in the tables
        self.attn_blocks_total = [0, 0]
        # the paged decode kernel's groups (note_attn_groups): walked, full
        self.attn_groups_total = [0, 0]
        # an indexer's work (note_dsa): per program, DSA_SERIES in order
        self.dsa_total = {p: [0, 0, 0, 0] for p in MOE_PROGRAMS}
        # latent attention's reads (note_mla): MLA_SERIES in order
        self.mla_total = {p: [0, 0, 0] for p in MOE_PROGRAMS}
        # the same reads by layer KIND (note_mla_kind): (kind, program) ->
        # cached positions the kind's layers had to read
        self.mla_kind_total: "Dict[tuple, int]" = {}
        # a state kind's counters (`note_state`): name -> total
        self.state_total: "Dict[str, int]" = {}
        self._pending_moe: "Optional[Dict[str, list]]" = None
        self._gauges_registered = False
        self._registry = registry
        self._t_last_end: Optional[float] = None
        # registry batch: records awaiting the bulk flush (end() only
        # appends; flush() does the per-phase fan-out off the hot path)
        self._pending_flush: list = []
        self._pending_bulk: list = []  # landed, not yet billed
        # memoized labeled histogram keys — string formatting is
        # measurable on the per-step path (the serving _bucket_key
        # lesson)
        self._hist_keys = {p: labeled("step.phase_seconds", phase=p)
                           for p in PHASES}
        # scrape-time callable gauges, weakly bound: the registry must
        # not pin a dead clock (and its ring) for the process lifetime
        ref = weakref.ref(self)

        def _weak(method):
            def read():
                c = ref()
                return getattr(c, method)() if c is not None else 0.0
            return read

        # overlap_depth: how many dispatched-but-uncommitted steps the
        # producer's pipeline holds (0 = classic dispatch→wait→commit;
        # 1 = the batcher's double-buffered dispatch is live). Set by
        # the producer with one attr store; scraped like every gauge.
        self.overlap_depth = 0
        # constrained_slots: how many of the producer's live slots hold
        # a grammar constraint (ISSUE 16: constrained requests ride the
        # same hot path, so the scrape must say WHEN the host_fraction
        # it reports covered constraint-live traffic). Set by the
        # producer at admit/retire with one attr store, never per step.
        self.constrained_slots = 0
        def _weak_total(attr, key=None):
            def read():
                c = ref()
                if c is None:
                    return 0.0
                v = getattr(c, attr)
                return float(v if key is None else v[key])
            return read

        def _weak_moe(program, i):
            def read():
                c = ref()
                return float(c.moe_total[program][i]) if c is not None \
                    else 0.0
            return read

        def _weak_of(attr, program, i):
            def read():
                c = ref()
                return float(getattr(c, attr)[program][i]) \
                    if c is not None else 0.0
            return read

        # registered with the first note_dsa / note_mla: a model without
        # an indexer shows no dsa_* series, one without a latent cache no
        # mla_* series
        self._dsa_gauges = {
            labeled(f"dsa.{name}", program=p): _weak_of("dsa_total", p, i)
            for p in MOE_PROGRAMS for i, name in enumerate(DSA_SERIES)}
        self._mla_gauges = {
            labeled(f"mla.{name}", program=p): _weak_of("mla_total", p, i)
            for p in MOE_PROGRAMS for i, name in enumerate(MLA_SERIES)}
        # registered with the first note_attn_blocks: a dense cache shows
        # no step_attn_* series
        self._attn_gauges = {
            "step.attn_live_blocks_total":
                _weak_total("attn_blocks_total", 0),
            "step.attn_table_blocks_total":
                _weak_total("attn_blocks_total", 1)}
        # registered with the first note_attn_groups: a pool no kernel
        # reads shows none
        self._attn_group_gauges = {
            "step.attn_groups_total":
                _weak_total("attn_groups_total", 0),
            "step.attn_full_groups_total":
                _weak_total("attn_groups_total", 1)}
        # registered with the first note_moe: a model without experts
        # shows no moe_* series
        self._latent_registered = False
        self._latent_gauges = {
            labeled("moe.latent_rows_total", program=p):
                _weak_total("moe_latent_rows_total", p)
            for p in MOE_PROGRAMS}
        self._moe_registered = False
        self._moe_gauges = {
            labeled(f"moe.{name}", program=p): _weak_moe(p, i)
            for p in MOE_PROGRAMS for i, name in enumerate(MOE_SERIES)}
        self._gauges = {
            "step.steps_total": _weak_total("steps_total"),
            "step.tokens_advanced_total":
                _weak_total("tokens_advanced_total"),
            **{labeled("step.phase_seconds_total", phase=p):
               _weak_total("phase_seconds_total", p) for p in PHASES},
            **{labeled("step.admit_seconds_total", part=p):
               _weak_total("admit_seconds_total", p) for p in ADMIT_PARTS},
            **{labeled("step.loop_seconds_total", part=p):
               _weak_total("loop_seconds_total", p) for p in LOOP_PARTS},
            "step.host_fraction": _weak("host_fraction"),
            "step.per_sec": _weak("steps_per_sec"),
            "step.last_wall_ms": _weak("last_wall_ms"),
            "step.overlap_depth": _weak("_overlap_depth_read"),
            "step.constrained_slots": _weak("_constrained_slots_read"),
        }

    def install(self) -> "StepClock":
        """Make this the process's active clock (what profile.py's
        sidecar meta reads its step-counter range from)."""
        global _active_clock
        _active_clock = weakref.ref(self)
        return self

    # -- producer side (the batcher worker thread) ---------------------

    def begin(self, first: str = "host") -> Optional[_StepRec]:
        """Start one step's record — None when observability is off
        (the producer's one None check covers every later site). While
        a profiler capture records, the step and its phases are also
        written into it as annotations (_StepSpans); `first` names the
        phase the step opens in (`wait` for a call that only commits)."""
        if not _obs.enabled():
            return None
        rec = _StepRec(self._now())
        if _profile._capturing:
            rec.spans = _StepSpans(self.steps_total, first)
        return rec

    def mark(self, rec: _StepRec, phase: str):
        """Close the current phase at now: one perf_counter read, one
        tuple append, and one attribute check for the open annotations
        (`rec.spans`, None unless a capture records)."""
        rec.marks.append((phase, self._now()))
        if rec.spans is not None:
            rec.spans.next(phase)

    def loop_part(self, part: str, iteration: int = 0):
        """The worker's loop enters `part` (LOOP_PARTS, or "step" for
        the call of step() itself): the part it was in ends here. Its
        seconds, less what step records and admissions claimed inside
        it, go to the cumulative loop totals and onto the next record.
        While a capture records, the iteration and its parts are `loop`
        / `loop.<part>` annotations (_LoopSpans), moved on BEFORE the
        bookkeeping so that one part's span opens where the last one's
        closed. One perf_counter read a part, and one check for a
        recording capture when none does."""
        if not _obs.enabled():
            self._loop_part = None  # a gap is no part: start clean
            return
        spans = self._loop_spans
        if part == "pre":  # the next iteration
            self._loop_iter = iteration
            if spans is not None:
                spans.close()
                spans = self._loop_spans = None
        if spans is not None:
            spans.enter(part)
        elif _profile._capturing:
            self._loop_spans = _LoopSpans(self._loop_iter, part)
        t = self._now()
        was = self._loop_part
        if was is not None:
            i = _LOOP_ACCRUES[was]
            dt = (t - self._loop_t) - self._loop_in_t
            self.loop_seconds_total[LOOP_PARTS[i]] += dt
            pend = self._pending_loop
            if pend is None:
                pend = self._pending_loop = [0.0] * 4
            pend[i] += dt
        self._loop_part, self._loop_t, self._loop_in_t = part, t, 0.0

    def _register_gauges(self):
        """Put the scrape-time callables on the registry before the
        first bulk flush would (FLUSH_EVERY steps in): the cumulative
        series must be there at a window's first scrape."""
        m = self._registry if self._registry is not None \
            else _obs.metrics()
        if m is not None:
            m.bulk(gauge_fns=self._gauges)
            self._gauges_registered = True

    def note_admit(self, t0: float, parts: tuple = _NO_PARTS):
        """One submit()'s wall interval [t0, now) — attached to the
        next step's record — and the seconds of its (prefill,
        first_token, install) parts (ADMIT_PARTS; an admission that
        dispatches nothing reports none). Bounded: a pathological admit
        storm with no steps keeps the newest 64 slices. Lock-free:
        submit and step run on the ONE thread that owns the batcher
        (the lm_server worker contract), so the producer side never
        races itself — and flush()'s swap-then-read is safe against a
        GIL-atomic append (an append racing the swap lands in whichever
        list the interpreter saw, and both are drained)."""
        if not _obs.enabled():
            return
        t1 = self._now()
        pa = self._pending_admit
        pa.append((t0, t1))
        self._pending_parts.append(parts)
        if len(pa) > 64:
            del pa[0], self._pending_parts[0]
        self.phase_seconds_total["admit"] += t1 - t0
        self._loop_in_t += t1 - t0  # the loop's `admit` part less this
        tot = self.admit_seconds_total
        tot["self"] += (t1 - t0) - sum(parts)
        tot["prefill"] += parts[0]
        tot["first_token"] += parts[1]
        tot["install"] += parts[2]
        if not self._gauges_registered:
            self._register_gauges()

    def note_attn_blocks(self, live: int, table: int):
        """One decode step over a paged pool: `live` blocks hold a
        position some slot attends (sum over the slots of ceil(positions
        / block_len)), of the `table` entries the slots' block tables
        have (slots x blocks a slot). The paged decode kernel's work
        follows the first; a grid over table entries would follow the
        second. Cumulative step.attn_{live,table}_blocks_total, on
        /metrics with the first note — a dense cache has none."""
        if not _obs.enabled():
            return
        tot = self.attn_blocks_total
        if not tot[1]:
            self._gauges.update(self._attn_gauges)
            self._gauges_registered = False  # re-register with them
        tot[0] += live
        tot[1] += table

    def note_attn_groups(self, groups: int, full: int):
        """One decode step through the paged decode kernel: it walked
        `groups` groups of blocks (sum over the layers that call it and
        the slots of ceil(blocks / blocks a group)), `full` of them whole
        — those whose copies it awaits, and from a slot's second group on
        starts, as straight-line code; a slot's last, partial group keeps
        the loop. From the slots' positions, no device read. Cumulative step.attn_{groups,full_groups}_total, on
        /metrics with the first note."""
        if not _obs.enabled():
            return
        tot = self.attn_groups_total
        if not tot[0]:
            self._gauges.update(self._attn_group_gauges)
            self._gauges_registered = False  # re-register with them
        tot[0] += groups
        tot[1] += full

    def note_dsa(self, program: str, layer_calls: int, candidates: int,
                 selected: int, walked: int = 0):
        """One dispatched program of a model whose attention selects what
        it reads (models/dsa.py): `layer_calls` attention layers, whose
        indexers scored `candidates` live positions and selected
        `selected` of them (min(position + 1, topk) a query), both summed
        over the layers and the queries; `walked`: the (query, column)
        pairs the grid of a chunk's masked kernel covered for them
        (ops/pallas/sparse_attention.py `walked_columns` a query — what
        the kernel pays for, where `candidates` is what it had to).
        Counted by the batcher on the host from each slot's position — no
        device read. Cumulative dsa.* totals, on /metrics with the first
        note."""
        self._note3(self.dsa_total, self._dsa_gauges, program,
                    (layer_calls, candidates, selected, walked))

    def note_mla(self, program: str, layer_calls: int, cached: int,
                 pairs: int):
        """One dispatched program of a model whose cache is a compressed
        latent (models/mla.py): `layer_calls` attention layers, which
        read `cached` cached positions (decode: every live position of
        every slot; prefill: the positions the chunk attends) and scored
        `pairs` causal (query, position) pairs, both summed over the
        layers. Counted on the host as the dsa.* series are. Cumulative
        mla.* totals, on /metrics with the first note."""
        self._note3(self.mla_total, self._mla_gauges, program,
                    (layer_calls, cached, pairs))

    def note_mla_kind(self, program: str, kind: str, cached: int,
                      series: str = "mla"):
        """`note_mla`'s cached positions for a model whose layers are of
        KINDS (models/mla.py), by kind: what the kind's layers had to
        read — an indexer's selected positions for "full", the window's
        for "window" — summed over its layers. Cumulative
        `mla.cached_positions_read_total{kind=,program=}`, on /metrics
        with a kind's first note. `series` "attn": the same for kinds
        whose cache is K and V (models/llama.py `LlamaKindRows`: pos + 1 a
        full layer, min(pos + 1, W) a window layer, a chunk's pairs
        within the band), `attn.cached_positions_read_total{...}`."""
        if not _obs.enabled():
            return
        key = (kind, program) if series == "mla" else (series, kind, program)
        if key not in self.mla_kind_total:
            self.mla_kind_total[key] = 0
            ref = weakref.ref(self)

            def read(key=key):
                c = ref()
                return float(c.mla_kind_total[key]) if c is not None else 0.0

            self._gauges[labeled(f"{series}.cached_positions_read_total",
                                 kind=kind, program=program)] = read
            self._gauges_registered = False  # re-register with it
        self.mla_kind_total[key] += cached

    def note_state(self, **adds):
        """A model that keeps a STATE a slot — beside K and V (models/
        kda.py) or with no K and V at all (models/retention.py: the
        counters then carry most of a step's bytes, state and normaliser,
        and `kv_bytes_read` stays 0) —, counted on the host: `bytes_read`
        / `bytes_written` (a decode step reads and writes every slot's
        state leaves),
        `kv_bytes_read` (the live K and V positions the step read),
        `prefill_real_positions` / `prefill_pad_positions` (a chunk's),
        `installs` (states written into a slot by a finish, a layer
        each). Cumulative `state_pool.<name>_total`, on /metrics with a
        name's first note."""
        if not _obs.enabled():
            return
        for name, n in adds.items():
            if name not in self.state_total:
                self.state_total[name] = 0
                ref = weakref.ref(self)

                def read(name=name):
                    c = ref()
                    return float(c.state_total[name]) if c is not None \
                        else 0.0

                self._gauges[f"state_pool.{name}_total"] = read
                self._gauges_registered = False  # re-register with it
            self.state_total[name] += n

    def _note3(self, total, gauges, program, add):
        if not _obs.enabled():
            return
        if not (total["decode"][0] or total["prefill"][0]):
            self._gauges.update(gauges)
            self._gauges_registered = False  # re-register with them
        for i, v in enumerate(add):
            total[program][i] += v

    def note_moe_latent(self, program: str, rows: int):
        """Rows that went through an expert layer's latent down-projection
        (models/llama_moe.py `moe_latent`: every row of every expert layer
        call of one executed program): `moe.latent_rows_total{program}`,
        the experts' input rows without the model's width. The series
        appears with the first note — a model whose experts are as wide as
        the model has none."""
        if not _obs.enabled():
            return
        if not self._latent_registered:
            self._latent_registered = True
            self._gauges.update(self._latent_gauges)
            self._gauges_registered = False  # re-register with them
        self.moe_latent_rows_total[program] += rows

    def note_moe(self, program: str, layer_calls: int, stats):
        """What the expert layers of one executed program cost:
        `layer_calls` of them, and `stats` = (rows through experts,
        sum over the calls of experts with at least one row, sum of the
        fullest expert's rows, rows the permutation moved, its rounds
        beyond a call's first), as the program counted them on the
        device (parallel/moe.moe_ffn_grouped) and handed back with its
        tokens. Adds to the cumulative moe.* totals; the next ended
        step's record carries it for /stepz. The series appear on
        /metrics with the first note — a model without experts has
        none."""
        if not _obs.enabled():
            return
        add = (layer_calls, *(int(v) for v in stats))
        tot = self.moe_total[program]
        pend = self._pending_moe
        if pend is None:
            pend = self._pending_moe = {}
            if not self._moe_registered:
                self._moe_registered = True
                self._gauges.update(self._moe_gauges)
                self._gauges_registered = False  # re-register with them
        cur = pend.setdefault(program, [0] * len(MOE_SERIES))
        for i, v in enumerate(add):
            tot[i] += v
            cur[i] += v

    def end(self, rec: _StepRec, n_adv: int = 0):
        """Stamp and publish one step. Deliberately MINIMAL — one
        perf_counter read, the cumulative totals (a handful of adds)
        and ONE GIL-atomic append, no lock: this runs inside the decode
        loop the clock exists to measure, so every microsecond here
        is host time added to the step.
        Single-producer by the batcher's threading contract. The rec
        lands only in the pending batch here; flush() moves the batch
        into the scrape ring (and runs the ring's evictions) every
        FLUSH_EVERY steps — ring maintenance per step was measurable
        against the budget, and every ring reader (_sums, records,
        summary, render_prom) flushes first, so scrapes stay exact.
        The phase fold and the registry bulk run off this path too."""
        rec.t_end = self._now()
        rec.n_adv = n_adv
        if rec.spans is not None:
            rec.spans.close()
            rec.spans = None
        if self._pending_admit:
            rec.admit_slices, self._pending_admit = \
                self._pending_admit, []
            rec.admit_parts, self._pending_parts = \
                self._pending_parts, []
        if self._pending_moe is not None:
            rec.moe, self._pending_moe = self._pending_moe, None
        if self._pending_loop is not None:
            rec.loop, self._pending_loop = self._pending_loop, None
        # the loop part this step ran in keeps what the record leaves
        self._loop_in_t += rec.t_end - rec.t0
        tot = self.phase_seconds_total
        t = rec.t0
        for name, tm in rec.marks:
            tot[name] += tm - t
            t = tm
        tot["obs"] += rec.t_end - t  # as _fold attributes the remainder
        self.tokens_advanced_total += n_adv
        self.steps_total += 1
        self._t_last_end = rec.t_end
        if not self._gauges_registered:
            self._register_gauges()
        pf = self._pending_flush
        pf.append(rec)
        if len(pf) >= self.FLUSH_EVERY:
            self.flush()

    def _land(self):
        """Move the pending batch into the scrape ring (one extend +
        up to FLUSH_EVERY evictions instead of an append+eviction per
        step). This is the HALF of flush() ring readers need — and the
        only half they may run: the registry's own gauge render calls
        the ring-derived series (host_fraction & co.) while HOLDING
        the registry lock, so a reader that reached Metrics.bulk from
        there would self-deadlock on that non-reentrant lock. Landed
        recs queue in _pending_bulk for the next real flush()'s
        histogram bill. The swap is locked against concurrent landers
        (two scrapes must not double-land a batch); a producer append
        racing the swap is GIL-atomic and lands in one of the two
        lists, never lost."""
        if not self._pending_flush:
            return
        with self._lock:
            pending, self._pending_flush = self._pending_flush, []
            self._ring.extend(pending)
            self._pending_bulk.extend(pending)

    def flush(self):
        """Land the accumulated observations in ONE bulk registry
        update. Called every FLUSH_EVERY steps by end(), and by
        summary()/render_prom() — StepClock's own scrape surfaces,
        never reached from inside a registry render — so a /stepz
        scrape never reads a stale histogram. Pending work is dropped
        (not retried) when the gate went off mid-batch — re-enabling
        starts clean."""
        m = self._registry if self._registry is not None \
            else _obs.metrics()
        self._land()
        with self._lock:
            pending, self._pending_bulk = self._pending_bulk, []
        if m is None or not pending:
            return
        hists: Dict[str, list] = {}
        walls = []
        for r in pending:
            _fold(r)
            for p, v in r.phases.items():
                hists.setdefault(self._hist_keys[p], []).append(v)
            walls.append(r.wall)
        hists["step.wall_seconds"] = walls
        m.bulk(hists=hists, hist_buckets=STEP_BUCKETS,
               gauge_fns=self._gauges)

    # -- derived series (scrape-time reads over the ring) --------------

    def _sums(self, last: Optional[int] = None):
        self._land()  # ring readers: land only, never the registry
        with self._lock:
            recs = list(self._ring)
        if last:
            recs = recs[-last:]
        tot: Dict[str, float] = {p: 0.0 for p in PHASES}
        wall = 0.0
        n_adv = 0
        for r in recs:
            _fold(r)
            for p, v in r.phases.items():
                tot[p] = tot.get(p, 0.0) + v
            wall += r.wall
            n_adv += r.n_adv
        return recs, tot, wall, n_adv

    @staticmethod
    def _admit_split(recs, admit_s: float) -> Dict[str, float]:
        """Seconds of each ADMIT_PARTS part over `recs`; the four sum to
        the records' admit phase (`admit_s`)."""
        pf = ft = ins = 0.0
        for r in recs:
            for a, b, c in r.admit_parts:
                pf += a
                ft += b
                ins += c
        return {"self": admit_s - pf - ft - ins, "prefill": pf,
                "first_token": ft, "install": ins}

    @staticmethod
    def _loop_split(recs) -> Dict[str, float]:
        """Seconds of each LOOP_PARTS part the records carry."""
        acc = [0.0] * 4
        for r in recs:
            if r.loop is not None:
                acc = [a + b for a, b in zip(acc, r.loop)]
        return {p: round(acc[i], 6) for i, p in enumerate(LOOP_PARTS)}

    def host_fraction(self) -> float:
        """(admit + host + commit + obs) / wall over the ring, for the
        scrape-time gauge."""
        _, tot, wall, _ = self._sums()
        return sum(tot[p] for p in _HOST_PHASES) / wall if wall > 0 else 0.0

    def steps_per_sec(self) -> float:
        """Rate over the ring's newest 60 s of records — computed at
        scrape time (a per-step Throughput feed measurably taxed the
        step; the ring already carries every timestamp needed)."""
        self._land()  # gauge-reachable: land only (registry deadlock)
        now = self._now()
        with self._lock:
            n = sum(1 for r in self._ring if now - r.t0 <= 60.0)
            oldest = self._ring[0].t0 if self._ring else now
        if n == 0:
            return 0.0
        # divide by the span the surviving records actually cover: a
        # full ring may have evicted part of the 60 s window
        return n / max(min(60.0, now - oldest), 1e-9)

    def _overlap_depth_read(self) -> float:
        return float(self.overlap_depth)

    def _constrained_slots_read(self) -> float:
        return float(self.constrained_slots)

    def last_wall_ms(self) -> float:
        self._land()  # gauge-reachable: land only (registry deadlock)
        with self._lock:
            if not self._ring:
                return 0.0
            rec = self._ring[-1]
        return _fold(rec).wall * 1e3

    def last_step_age_s(self) -> Optional[float]:
        with self._lock:
            t = self._t_last_end
        return None if t is None else max(0.0, self._now() - t)

    def records(self, last: Optional[int] = None) -> List[dict]:
        """Ring records as plain dicts (newest last) — what a coverage
        assertion reads."""
        self._land()
        with self._lock:
            recs = list(self._ring)
        if last:
            recs = recs[-last:]
        return [{"t0": r.t0, "wall": _fold(r).wall, "n_adv": r.n_adv,
                 "mixed": r.mixed,
                 "phases": dict(r.phases),
                 "loop": None if r.loop is None else list(r.loop),
                 "admit_slices": list(r.admit_slices),
                 "marks": list(r.marks)} for r in recs]

    # -- export surfaces -----------------------------------------------

    def summary(self, last: Optional[int] = None) -> dict:
        """The /stepz JSON payload: per-phase totals/means/fractions
        over the ring (or the newest `last` steps) plus the derived
        series."""
        self.flush()  # scrapes read fresh histograms/counters
        recs, tot, wall, n_adv = self._sums(last)
        n = len(recs)
        n_mixed = sum(1 for r in recs if r.mixed)
        phases = {}
        for p in PHASES:
            s = tot.get(p, 0.0)
            phases[p] = {"s": round(s, 6),
                         "frac": round(s / wall, 4) if wall > 0 else 0.0,
                         "mean_ms": round(s / n * 1e3, 4) if n else 0.0}
        dev = sum(tot[p] for p in _DEVICE_PHASES)
        host = sum(tot[p] for p in _HOST_PHASES)
        split = self._admit_split(recs, tot["admit"])
        return {
            "steps_total": self.steps_total,
            "window_steps": n,
            "window_wall_s": round(wall, 6),
            "tokens": n_adv,
            # interleaved-prefill steps in the window (the `mixed` tag:
            # the dispatch folded a prompt chunk into the decode program)
            "mixed_steps": n_mixed,
            "mixed_frac": round(n_mixed / n, 4) if n else 0.0,
            # the producer's dispatch-pipeline depth (0 = no overlap,
            # 1 = double-buffered dispatch live)
            "overlap_depth": self.overlap_depth,
            # live slots holding a grammar constraint — says whether
            # the window's host_fraction covered constrained traffic
            "constrained_slots": self.constrained_slots,
            "phases": phases,
            "device_s": round(dev, 6),
            "host_s": round(host, 6),
            # the admit phase by what the worker did in it (ADMIT_PARTS):
            # host_s counts all of it, though `prefill` and `first_token`
            # are device time the host dispatches and waits for —
            # pure_host_s is host_s without those two
            "admit_split": {k: round(v, 6) for k, v in split.items()},
            "pure_host_s": round(
                host - split["prefill"] - split["first_token"], 6),
            # the worker's loop outside step() and submit() over the
            # same records (LOOP_PARTS; not part of `window_wall_s`)
            "loop_split": self._loop_split(recs),
            "host_fraction": round(host / wall, 4) if wall > 0 else 0.0,
            "steps_per_sec": round(self.steps_per_sec(), 3),
            "last_wall_ms": round(self.last_wall_ms(), 4),
            # the expert layers over the same steps, per program
            # (MOE_SERIES); {} for a model without experts
            "moe": self._moe_sums(recs),
        }

    @staticmethod
    def _moe_sums(recs) -> dict:
        out: Dict[str, dict] = {}
        for r in recs:
            for program, vals in (r.moe or {}).items():
                cur = out.setdefault(program, dict.fromkeys(MOE_SERIES, 0))
                for name, v in zip(MOE_SERIES, vals):
                    cur[name] += v
        return out

    def status_component(self) -> dict:
        """The /statusz `step` component: slow-but-healthy vs wedged at
        a glance, no profile pull needed. Informational — state stays
        "ok"; the watchdog's decode_heartbeat owns escalation (both
        read the same worker loop, so their recency agrees)."""
        s = self.summary()
        age = self.last_step_age_s()
        return {
            "state": "ok",
            "detail": (f"last step {s['last_wall_ms']:.2f} ms "
                       f"({'never' if age is None else f'{age:.1f}s ago'}), "
                       f"host fraction {s['host_fraction']:.0%}, "
                       f"{s['steps_per_sec']:.1f} steps/s"),
            "last_wall_ms": s["last_wall_ms"],
            "last_step_age_s": None if age is None else round(age, 3),
            "host_fraction": s["host_fraction"],
            # host_fraction counts the prefill an admission dispatches
            # and waits for as host time; this one leaves it out
            "pure_host_fraction": round(
                s["pure_host_s"] / s["window_wall_s"], 4)
            if s["window_wall_s"] > 0 else 0.0,
            "steps_per_sec": s["steps_per_sec"],
            "steps_total": s["steps_total"],
        }

    def render_prom(self, last: Optional[int] = None) -> str:
        """The ?format=prom re-export: the summary as gauges, for
        scrape-only collectors (same pattern as /statusz?format=prom).
        `last` bounds the window like the JSON form."""
        from dnn_tpu.utils.metrics import Metrics, render_prometheus

        s = self.summary(last)
        m = Metrics()
        for k in ("steps_total", "window_steps", "window_wall_s",
                  "host_fraction", "steps_per_sec", "last_wall_ms",
                  "mixed_steps", "overlap_depth", "constrained_slots"):
            m.set(f"dnn_tpu_step_{k}", float(s[k]))
        for p, d in s["phases"].items():
            m.set(labeled("dnn_tpu_step_phase_seconds_total", phase=p),
                  d["s"])
            m.set(labeled("dnn_tpu_step_phase_frac", phase=p), d["frac"])
        return render_prometheus(m)


# the process's active clock (profile.py sidecar meta reads it)
_active_clock: "Optional[weakref.ref]" = None


def active_clock() -> Optional[StepClock]:
    ref = _active_clock
    if ref is None:
        return None
    return ref()


# ----------------------------------------------------------------------
# the event-loop thread: the daemon's other thread
# ----------------------------------------------------------------------

#: what the event-loop thread's wall time divides into (module docstring)
RPC_PARTS = ("select", "fan_out", "token", "rest")


# `RpcLoopClock._selecting` between a block's end and its entry in the total
_ENDING = object()


class RpcLoopClock:
    """The event-loop thread's wall time by RPC_PARTS, accumulated by the
    thread itself as the worker's loop accumulates LOOP_PARTS: plain
    numbers only that thread adds to, read by scrape-time callables
    (`lm_server._install_host_gauges`).

    The loop's selector (StampedSelector) stamps `perf_counter` on each
    side of `select()`: blocked in it is `select`, and from its return to
    its next call is one RUN of the loop — callbacks, task steps, gRPC's
    writes. Inside a run the program's own synchronous sections report
    themselves (`section_ends`): `fan_out` (a hand-off's tokens put on
    their queues) and `token` (one streamed token's message); `rest` is
    what they leave of the run. While a capture records, a run is an
    `rpc.run` annotation (`iter=`) with `rpc.fan_out` nested in it by its
    caller and, where the run built messages, one `rpc.tokens` marker at
    its end (`tokens=`: how many): synchronous stretches only, nothing is
    open across an `await`.

    `fan_out_lag` is [seconds, hand-offs] from the worker's stamp at a
    hand-off to `_fan_out`'s entry on this thread: the loop's wake-up
    (self-pipe, `select()`'s return, the interpreter lock)."""

    __slots__ = ("seconds", "iterations", "tokens", "fan_out_lag", "_now",
                 "_t_run", "_claimed", "_selecting", "_run_span",
                 "_run_tokens")

    def __init__(self, now=time.perf_counter):
        self.seconds = dict.fromkeys(RPC_PARTS, 0.0)
        self.iterations = 0
        self.tokens = 0  # `token` sections, ever
        self.fan_out_lag = [0.0, 0]
        self._now = now
        self._t_run: Optional[float] = None  # where the run began
        self._claimed = 0.0  # seconds its sections have taken of it
        # where select() began; _ENDING while `select_returns` adds it up
        self._selecting = None
        self._run_span = None
        self._run_tokens = 0  # `tokens` when the run's annotation opened

    def select_begins(self):
        """A run ends: what its sections left of it is `rest`."""
        t = self._now()
        if self._run_span is not None:
            built = self.tokens - self._run_tokens
            if built:
                _profile.close_span(_profile.open_span("rpc.tokens",
                                                       tokens=built))
            _profile.close_span(self._run_span)
            self._run_span = None
        if self._t_run is not None:
            self.seconds["rest"] += (t - self._t_run) - self._claimed
        self._selecting = t

    def select_returns(self):
        # marked as ending before the clock is read and the total grows: a
        # scrape that still sees this block open (select_seconds) read an
        # earlier clock, and none reads the total between the block's end
        # and its entry in it
        since, self._selecting = self._selecting, _ENDING
        t = self._now()
        self.seconds["select"] += t - since
        self._selecting = None
        self.iterations += 1
        self._t_run, self._claimed = t, 0.0
        if _profile._capturing:
            self._run_tokens = self.tokens
            self._run_span = _profile.open_span("rpc.run",
                                                iter=self.iterations)

    def fan_out_begins(self, t_commit: float) -> float:
        t = self._now()
        self.fan_out_lag[0] += t - t_commit
        self.fan_out_lag[1] += 1
        return t

    def section_ends(self, part: str, t0: float, span=None):
        """The section of the running iteration that began at `t0` (and
        `span`, its annotation while a capture records) ends here."""
        _profile.close_span(span)
        dt = self._now() - t0
        self.seconds[part] += dt
        self._claimed += dt

    def token_ends(self, t0: float):
        """One streamed token's `token` section, begun at `t0`, ends."""
        self.section_ends("token", t0)
        self.tokens += 1

    def select_seconds(self) -> float:
        """`seconds["select"]` with the `select()` in progress, for a
        scrape from another thread: an idle loop blocks for as long as
        nothing arrives, and the parts would stop short of the window by
        that much. The block counts only if it is the same open one before
        the total is read and after the clock is; a scrape that finds it
        ending (`select_returns` is between its clock and the total: a few
        bytecodes) or changed lets the loop thread run and reads again. So
        no scrape counts a block twice and the series never steps back
        (tests/test_emit_handoff.py walks every interleaving)."""
        while True:
            since = self._selecting
            total = self.seconds["select"]
            if since is None:
                return total
            if since is not _ENDING:
                now = self._now()
                if self._selecting is since:
                    return total + (now - since)
            time.sleep(0)


class StampedSelector(selectors.DefaultSelector):
    """The platform's selector with its `clock`'s two stamps around
    `select()`; without a clock, the platform's selector."""

    clock: Optional[RpcLoopClock] = None

    def select(self, timeout=None):
        clock = self.clock
        if clock is None:
            return super().select(timeout)
        clock.select_begins()
        try:
            return super().select(timeout)
        finally:
            clock.select_returns()


def rpc_event_loop() -> asyncio.AbstractEventLoop:
    """A selector event loop over a StampedSelector, which it keeps as
    `stamped_selector` for whoever serves on it to give a clock
    (`LMServer.note_rpc_loop_thread`): `asyncio.run`'s `loop_factory`."""
    selector = StampedSelector()
    loop = asyncio.SelectorEventLoop(selector)
    loop.stamped_selector = selector
    return loop


# ----------------------------------------------------------------------
# capture analysis: the device half of the attribution
# ----------------------------------------------------------------------

#: host-gap histogram bounds (seconds between consecutive device ops)
GAP_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
               5e-3, 0.01, 0.05, 0.25)


def _merge(intervals: List[tuple]) -> List[tuple]:
    """Union of [t0, t1) intervals, sorted."""
    out: List[tuple] = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def _load_trace(path: str) -> dict:
    """One Perfetto/Chrome trace JSON, possibly gzipped. ValueError
    with a plain message for anything that is not one — a truncated
    spool or a stray file must fail loud, not half-parse."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as f:
                data = json.load(f)
        else:
            with open(path, "r") as f:
                data = json.load(f)
    except (OSError, EOFError, gzip.BadGzipFile, json.JSONDecodeError,
            UnicodeDecodeError) as e:
        raise ValueError(f"not a readable Perfetto JSON trace: {path} "
                         f"({e})") from None
    if isinstance(data, list):  # chrome's bare-array form
        data = {"traceEvents": data}
    if not isinstance(data, dict) or not isinstance(
            data.get("traceEvents"), list):
        raise ValueError(f"no traceEvents array in {path}")
    return data


def find_trace_file(path: str) -> str:
    """Resolve a capture DIR (obs/profile.py spool layout) or a direct
    trace-JSON path to the trace file to analyze (newest when several)."""
    if os.path.isdir(path):
        hits = sorted(
            glob.glob(os.path.join(path, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
            or glob.glob(os.path.join(path, "*.trace.json.gz"))
            or glob.glob(os.path.join(path, "*.json.gz"))
            or glob.glob(os.path.join(path, "*.json")))
        if not hits:
            raise ValueError(
                f"no trace json found under {path}: a capture holds one "
                "when it was asked for (POST /profilez?...&perfetto=1)")
        return hits[-1]
    return path


def find_meta(path: str) -> Optional[dict]:
    """The sidecar meta.json for a capture (profile.py writes it at the
    capture root; a trace FILE lives a few levels below it)."""
    d = path if os.path.isdir(path) else os.path.dirname(path)
    for _ in range(4):
        cand = os.path.join(d, "meta.json")
        if os.path.isfile(cand):
            try:
                with open(cand) as f:
                    return json.load(f)
            except (OSError, json.JSONDecodeError):
                return None
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return None


def analyze(path: str, *, meta: Optional[dict] = None,
            top_k: int = 10) -> dict:
    """Structured numbers out of one device capture.

    `path` is a capture dir (POST /profilez's return) or a trace JSON
    (.json / .json.gz). Returns:

      window_s            capture window (first event start to last end)
      tracks              per-(process/thread) busy seconds + fraction
      device              busy/idle fraction of the union of DEVICE ops
                          (events carrying an hlo_op arg, or any event
                          on a "/device:*" process — covers the TPU/GPU
                          per-device tracks AND the CPU backend's
                          execution thread)
      host_gaps           histogram of the gaps between consecutive
                          device ops — each gap is host serialization
                          the device sat idle through
      top_ops             top-K op names by summed device time

    Where the steps and admissions lie in the capture is IN the capture:
    the batcher writes its phases as `step.*` / `admit*` annotations on
    the profiler's own clock (_StepSpans), so nothing here places a
    StepClock record on the trace's axis.

    Stdlib only; tolerant of the capture's host-side noise (the
    profiler's own start_trace span, threadpool markers)."""
    trace_file = find_trace_file(path)
    data = _load_trace(trace_file)
    if meta is None:
        meta = find_meta(path)

    proc_names: Dict[int, str] = {}
    thread_names: Dict[tuple, str] = {}
    xs = []
    for e in data["traceEvents"]:
        ph = e.get("ph")
        if ph == "M":
            args = e.get("args") or {}
            if e.get("name") == "process_name":
                proc_names[e.get("pid")] = str(args.get("name", ""))
            elif e.get("name") == "thread_name":
                thread_names[(e.get("pid"), e.get("tid"))] = str(
                    args.get("name", ""))
        elif ph == "X":
            xs.append(e)
    if not xs:
        raise ValueError(f"trace has no complete (ph=X) events: "
                         f"{trace_file}")

    def _num(e, k):
        v = e.get(k, 0.0)
        return float(v) if isinstance(v, (int, float)) else 0.0

    t_min = min(_num(e, "ts") for e in xs)
    t_max = max(_num(e, "ts") + _num(e, "dur") for e in xs)

    # ts-axis anchor of the armed window: the trace's ts 0 is the
    # profiler SESSION start (start_trace entry), but the sidecar meta's
    # perf_begin lands at start_trace RETURN — a first capture pays
    # seconds of profiler init in between. The host track records that
    # init as a "start_trace" span; its END is where perf_begin sits on
    # the ts axis. Synthetic/processed traces without one anchor at 0.
    anchor = 0.0
    for e in xs:
        if "start_trace" in str(e.get("name", "")):
            anchor = _num(e, "ts") + _num(e, "dur")
            break

    # analysis window: the ARMED capture window (meta perf bounds,
    # anchored) when available — a first capture's init seconds must
    # not read as device idle — else the events' own span
    w0, w1 = t_min, t_max
    if meta is not None and isinstance(meta.get("perf_begin"),
                                       (int, float)) \
            and isinstance(meta.get("perf_end"), (int, float)):
        w0 = anchor
        w1 = anchor + (meta["perf_end"] - meta["perf_begin"]) * 1e6
    window_s = max(w1 - w0, 1e-9) / 1e6

    def _clipped_busy(merged) -> float:
        return sum(max(0.0, min(t1, w1) - max(t0, w0))
                   for t0, t1 in merged) / 1e6

    by_track: Dict[tuple, list] = {}
    device_ops: list = []
    for e in xs:
        key = (e.get("pid"), e.get("tid"))
        by_track.setdefault(key, []).append(e)
        args = e.get("args") or {}
        pname = proc_names.get(e.get("pid"), "")
        if "hlo_op" in args or "/device:" in pname \
                or pname.startswith("/device"):
            # skip the CPU runtime's zero-width threadpool markers —
            # they carry no hlo_op but would otherwise ride a /device
            # pid on some backends
            if _num(e, "dur") > 0.0 or "hlo_op" in args:
                device_ops.append(e)

    tracks = {}
    for (pid, tid), evs in sorted(by_track.items(),
                                  key=lambda kv: str(kv[0])):
        merged = _merge([(_num(e, "ts"), _num(e, "ts") + _num(e, "dur"))
                         for e in evs])
        busy = _clipped_busy(merged)
        name = (proc_names.get(pid, str(pid)) + "/"
                + thread_names.get((pid, tid), str(tid)))
        tracks[name] = {"events": len(evs),
                        "busy_s": round(busy, 6),
                        "busy_frac": round(busy / window_s, 4)}

    dev_ivals = _merge([(_num(e, "ts"), _num(e, "ts") + _num(e, "dur"))
                        for e in device_ops])
    dev_busy_s = _clipped_busy(dev_ivals)
    device = {
        "ops": len(device_ops),
        "busy_s": round(dev_busy_s, 6),
        "busy_frac": round(dev_busy_s / window_s, 4),
        "idle_frac": round(1.0 - dev_busy_s / window_s, 4),
    }

    gaps = [(t0 - prev_t1) / 1e6
            for (_, prev_t1), (t0, _) in zip(dev_ivals, dev_ivals[1:])
            if t0 > prev_t1]
    gap_hist: Dict[str, int] = {}
    for b in GAP_BUCKETS:
        gap_hist[f"le_{b:g}"] = sum(1 for g in gaps if g <= b)
    gap_hist["inf"] = len(gaps)
    gaps_sorted = sorted(gaps)

    def _pct(q):
        if not gaps_sorted:
            return 0.0
        k = min(len(gaps_sorted) - 1,
                int(round(q / 100.0 * (len(gaps_sorted) - 1))))
        return gaps_sorted[k]

    host_gaps = {
        "count": len(gaps),
        "total_s": round(sum(gaps), 6),
        "p50_ms": round(_pct(50) * 1e3, 4),
        "p90_ms": round(_pct(90) * 1e3, 4),
        "max_ms": round((gaps_sorted[-1] if gaps_sorted else 0.0) * 1e3,
                        4),
        "hist": gap_hist,
    }

    by_op: Dict[str, list] = {}
    for e in device_ops:
        by_op.setdefault(str(e.get("name", "?")), [0.0, 0])
        rec = by_op[str(e.get("name", "?"))]
        rec[0] += _num(e, "dur") / 1e6
        rec[1] += 1
    top_ops = [{"name": n, "total_ms": round(s * 1e3, 4), "count": c,
                "frac_of_device": round(s / dev_busy_s, 4)
                if dev_busy_s > 0 else 0.0}
               for n, (s, c) in sorted(by_op.items(),
                                       key=lambda kv: -kv[1][0])[:top_k]]

    return {
        "trace_file": trace_file,
        "window_s": round(window_s, 6),
        "events": len(xs),
        "tracks": tracks,
        "device": device,
        "host_gaps": host_gaps,
        "top_ops": top_ops,
    }


def render_report(a: dict) -> str:
    """Human-readable one-capture report (the CLI's default output)."""
    lines = [f"capture: {a['trace_file']}",
             f"window: {a['window_s'] * 1e3:.2f} ms, "
             f"{a['events']} events",
             f"device: busy {a['device']['busy_frac']:.1%} / idle "
             f"{a['device']['idle_frac']:.1%} "
             f"({a['device']['ops']} ops, "
             f"{a['device']['busy_s'] * 1e3:.2f} ms)",
             f"host gaps between device ops: {a['host_gaps']['count']} "
             f"(total {a['host_gaps']['total_s'] * 1e3:.2f} ms, "
             f"p50 {a['host_gaps']['p50_ms']:.3f} ms, "
             f"p90 {a['host_gaps']['p90_ms']:.3f} ms, "
             f"max {a['host_gaps']['max_ms']:.3f} ms)"]
    if a["top_ops"]:
        lines.append("top device ops:")
        for op in a["top_ops"]:
            lines.append(f"  {op['total_ms']:10.3f} ms  "
                         f"{op['frac_of_device']:6.1%}  x{op['count']:<5d}"
                         f" {op['name']}")
    lines.append("tracks:")
    for name, t in sorted(a["tracks"].items(),
                          key=lambda kv: -kv[1]["busy_s"]):
        lines.append(f"  {t['busy_frac']:6.1%} busy "
                     f"({t['busy_s'] * 1e3:9.2f} ms, {t['events']:6d} ev)"
                     f"  {name}")
    return "\n".join(lines)
