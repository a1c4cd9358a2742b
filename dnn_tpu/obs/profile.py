"""On-demand device profiling: programmatic jax.profiler capture.

The span collector (obs/trace.py) answers "where did this request's
milliseconds go" at host granularity; this module answers the next
question — "what was the DEVICE doing" — with a real `jax.profiler`
capture (device + host timeline: the profiler's `*.xplane.pb` under
the capture dir, which TensorBoard / xprof load and
`jax.profiler.ProfileData` reads) taken from a RUNNING server:

  * `POST /profilez?ms=N` on the obs HTTP endpoint (obs/http.py)
    captures N milliseconds into a bounded spool directory and returns
    the capture path — no restart, no TensorBoard session;
  * `POST /profilez?auto=1&threshold_ms=T[&ms=N]` ARMS the auto
    trigger: the LM batcher worker captures the next decode step after
    one exceeds T milliseconds (the p99-breach post-mortem: you never
    have to be watching when the slow step happens);
  * `&perfetto=1` on either asks for the Perfetto-loadable
    `*.trace.json.gz` beside the `.xplane.pb` (what
    `obs/timeline.analyze()` reads). It is not written unasked because
    it is most of what ending a capture costs: the JSON export gzips
    every event, 57-70 s of the 76-90 s that `stop_trace` took after a
    4 s capture of the loaded daemon (0.5 M device events), against
    19-20 s to collect the events (my chip runs, PR 29) — a minute in
    which a core of the serving host compresses text;
  * `annotation(name)` / `step_annotation(step)` are the obs-gated host
    span annotations (jax.profiler.TraceAnnotation) that make captures
    readable — the serving runtime writes every batcher step and its
    StepClock phases (`step`, `step.<phase>`, with a `step=` stat),
    every admission and its parts (`admit`, `admit.prefill`, ..., with
    `rid=`), prefill chunks and relay stage hops, and the models thread
    `jax.named_scope` through their blocks so TPU timelines name layers
    too. utils/tracing.py re-exports these (its original span API
    predates the obs gate and is deprecated).

Capture locking: jax.profiler supports ONE trace at a time per process;
concurrent `capture()` calls (two curls racing, or a curl racing the
auto trigger) serialize on a module lock, with the loser failing fast
(`ProfilerBusy`) rather than corrupting the winner's capture.

The spool is bounded (default 8 captures): oldest captures are deleted
as new ones land, so a long-lived daemon with a trigger-happy operator
cannot fill the disk.

Every obs-driven capture writes a sidecar `meta.json` at the capture
root — monotonic (perf_counter) begin/end, wall-clock bounds, the
StepClock step-counter range, and the backend — so
`obs/timeline.analyze()` knows the armed window. Which step ran when
is in the capture itself (the `step=` stat of the annotations).
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import socket
import threading
import time
from typing import Iterator, Optional

__all__ = ["ProfilerBusy", "capture", "capture_step", "spool_dir",
           "list_captures", "xplane_files", "trace_files", "annotation",
           "annotation_ctx", "open_span", "close_span", "step_annotation",
           "Profiler"]


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (jax.profiler is single-trace)."""


_capture_lock = threading.Lock()


def spool_dir() -> str:
    """$DNN_TPU_OBS_DIR/profiles (obs/flight.default_dump_dir anchors
    the shared obs artifact root)."""
    from dnn_tpu.obs.flight import default_dump_dir

    return os.path.join(default_dump_dir(), "profiles")


def list_captures(root: Optional[str] = None) -> list:
    """Capture dirs in the spool, oldest first."""
    root = root or spool_dir()
    if not os.path.isdir(root):
        return []
    out = [os.path.join(root, d) for d in os.listdir(root)
           if d.startswith("capture-")]
    return sorted(out)


def _prune(root: str, keep: int):
    for old in list_captures(root)[:-keep] if keep > 0 else []:
        shutil.rmtree(old, ignore_errors=True)


def xplane_files(capture_dir: str) -> list:
    """The profiler's own artifacts inside one capture dir."""
    return sorted(glob.glob(os.path.join(
        capture_dir, "plugins", "profile", "*", "*.xplane.pb")))


def trace_files(capture_dir: str) -> list:
    """The Perfetto-loadable artifacts inside one capture dir: those of
    a capture made with `perfetto`, else none."""
    return sorted(glob.glob(os.path.join(
        capture_dir, "plugins", "profile", "*", "*.trace.json.gz")))


_capturing = False  # read by annotation_ctx: annotations only pay their
# TraceAnnotation cost while a capture is actually recording


def capturing() -> bool:
    return _capturing


@contextlib.contextmanager
def mark_recording() -> Iterator[None]:
    """Mark an EXTERNALLY-driven capture (bare jax.profiler.start_trace,
    a TensorBoard attach) as recording so annotation_ctx emits during
    it. obs-driven captures (_traced) set the flag themselves; this is
    the compatibility hook utils/tracing.trace_to wraps its body in so
    the legacy trace_to + span pattern still yields annotated captures."""
    global _capturing
    prev = _capturing
    _capturing = True
    try:
        yield
    finally:
        _capturing = prev


def _step_counter() -> Optional[int]:
    """The active StepClock's step counter (obs/timeline.py), or None
    when no clock is installed — guarded so a broken clock can never
    cost a capture."""
    try:
        from dnn_tpu.obs.timeline import active_clock

        clk = active_clock()
        return None if clk is None else int(clk.steps_total)
    except Exception:  # noqa: BLE001 — meta is best-effort
        return None


def _write_meta(path: str, meta: dict):
    """Sidecar `meta.json` at the capture root: monotonic begin/end
    (perf_counter), wall-clock bounds, the step-counter range, the
    backend, and how long ending the capture took (`stop_s`): the
    armed window `timeline.analyze()` reads idle time inside.
    Best-effort: an unwritable spool loses the meta, never the trace."""
    try:
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)
    except OSError:
        pass


def _stop_trace(path: str, perfetto: bool):
    """End the session that `jax.profiler.start_trace(path)` began and
    write its `.xplane.pb` where the profiler's own export puts it
    (`plugins/profile/<run>/<host>.xplane.pb`). `jax.profiler.stop_trace`
    has one form, which also exports the Perfetto JSON (module
    docstring: most of its time); the session object it ends hands over
    the collected XSpace without it. Where this JAX keeps that session
    elsewhere, or the JSON is asked for, `stop_trace` does the whole."""
    import jax

    state = None
    if not perfetto:
        try:
            from jax._src.profiler import _profile_state as state
        except ImportError:
            pass
    sess = getattr(state, "profile_session", None)
    if not hasattr(sess, "stop"):
        jax.profiler.stop_trace()
        return
    with state.lock:
        try:
            xspace = sess.stop()
        finally:
            state.reset()
    run = os.path.join(path, "plugins", "profile",
                       time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run, exist_ok=True)
    with open(os.path.join(run, socket.gethostname() + ".xplane.pb"),
              "wb") as f:
        f.write(xspace)


#: whether a capture that does not say records Python frames
#: (`jax.profiler.ProfileOptions.python_tracer_level` 1, the profiler's
#: own default, which hooks every Python call of every thread while it
#: records). Off since PR 37: on the chip it cost the loaded daemon
#: 12-15 % of its steps a second while recording, against under 2 %
#: without (PERF.md section 7), and the worker's `loop*` / `step*` /
#: `admit*` spans name what the frames were needed for. `python_tracer=`
#: / `POST /profilez?py=1` bring the frames back for hunting host code no
#: span covers. The runtime's own host events (`DevicePut`, the launches,
#: the reads: host tracer level 2) and the program's annotations are
#: recorded either way.
PYTHON_TRACER = False


def _profile_options(python_tracer: Optional[bool]):
    """`ProfileOptions` for `start_trace`: the profiler's defaults but
    for the Python tracer (PYTHON_TRACER when `python_tracer` is None)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = int(
        PYTHON_TRACER if python_tracer is None else python_tracer)
    return opts


@contextlib.contextmanager
def _traced(capture_root: Optional[str], keep: int,
            perfetto: bool = False,
            python_tracer: Optional[bool] = None) -> Iterator[str]:
    """Exclusive start_trace/stop around the body; yields the capture
    dir. Raises ProfilerBusy instead of queueing — a capture request
    against a busy profiler wants a fast 409, not a pile-up."""
    global _capturing
    import jax

    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture is already in flight")
    try:
        root = capture_root or spool_dir()
        path = os.path.join(root, f"capture-{int(time.time() * 1e3):x}")
        os.makedirs(path, exist_ok=True)
        try:
            backend = jax.default_backend()
        except Exception:  # noqa: BLE001 — a wedged backend still traces
            backend = None
        opts = _profile_options(python_tracer)
        jax.profiler.start_trace(path, profiler_options=opts)
        # perf_begin lands right after start_trace returns: the armed
        # window starts here (a first capture's profiler init is before)
        meta = {"perf_begin": time.perf_counter(),
                "t_begin_unix": time.time(),
                "step_begin": _step_counter(),
                "backend": backend,
                "python_tracer": bool(opts.python_tracer_level)}
        _capturing = True
        try:
            yield path
        finally:
            _capturing = False
            meta["perf_end"] = time.perf_counter()
            meta["t_end_unix"] = time.time()
            meta["step_end"] = _step_counter()
            _stop_trace(path, perfetto)
            meta["stop_s"] = time.perf_counter() - meta["perf_end"]
            # the step counter once the events are collected: what the
            # worker got done while ending the capture held a core
            meta["step_stopped"] = _step_counter()
            _write_meta(path, meta)
            try:
                keep_n = int(os.environ["DNN_TPU_OBS_PROFILE_KEEP"])
            except (KeyError, ValueError):
                keep_n = keep
            _prune(root, keep_n)
    finally:
        _capture_lock.release()


def capture(duration_ms: float = 1000.0, *,
            capture_root: Optional[str] = None, keep: int = 8,
            perfetto: bool = False,
            python_tracer: Optional[bool] = None) -> str:
    """Capture `duration_ms` of whatever the process is doing (the
    serving worker keeps stepping; this thread just sleeps inside the
    trace). Returns the capture dir; flight-records the capture.
    `perfetto` adds the `*.trace.json.gz` (module docstring);
    `python_tracer` says whether Python frames are recorded
    (PYTHON_TRACER when None)."""
    from dnn_tpu.obs import flight

    with _traced(capture_root, keep, perfetto, python_tracer) as path:
        time.sleep(max(0.0, float(duration_ms)) / 1e3)
    flight.record("profile_capture", path=path, ms=float(duration_ms))
    return path


def capture_step(fn, *, capture_root: Optional[str] = None,
                 keep: int = 8, extra_s: float = 0.0,
                 perfetto: bool = False,
                 python_tracer: Optional[bool] = None):
    """Capture exactly one call of `fn` (the auto-trigger's "next decode
    step") instead of a wall-clock window; `extra_s` extends the trace
    past the call. Returns (capture_dir, fn's result).

    NOTE the capture wall time is dominated by profiler init + trace
    EXPORT (ending it collects the events and writes the xplane.pb, and
    with `perfetto` the json.gz: seconds for a first capture), during
    which the calling thread
    (the batcher worker, for the auto trigger) is stalled: requests
    queue behind an auto capture. That is the accepted cost of an
    operator-armed post-mortem, not a steady-state tax.

    Failure contract: ProfilerBusy and `fn`'s OWN exceptions propagate
    (the caller decides what a failed step means — for the batcher
    worker it is fatal). Any OTHER profiler-machinery failure — a trace
    conflict with a bare jax.profiler.start_trace, an unwritable spool,
    an export error inside stop_trace — must never cost the step: the
    step runs uninstrumented (setup failure) or its already-computed
    result is returned (export failure), with (None, result) and a
    `profile_capture_failed` flight event recording the miss. An armed
    auto-capture is an observer; it is not allowed to kill the serving
    loop it observes."""
    from dnn_tpu.obs import flight

    t0 = time.perf_counter()
    ran, out, step_err, step_ms, path = False, None, None, None, None
    try:
        with _traced(capture_root, keep, perfetto, python_tracer) as path:
            t1 = time.perf_counter()
            try:
                out = fn()
                ran = True
            except Exception as e:
                step_err = e
                raise
            step_ms = round((time.perf_counter() - t1) * 1e3, 3)
            if extra_s > 0:
                time.sleep(extra_s)
    except ProfilerBusy:
        raise
    except Exception as e:
        if step_err is not None:
            raise  # the step's own failure is the caller's business
        flight.record("profile_capture_failed", error=str(e)[:200])
        if not ran:
            out = fn()
        return None, out
    flight.record("profile_capture", path=path, trigger="auto",
                  step_ms=step_ms,
                  capture_ms=round((time.perf_counter() - t0) * 1e3, 3))
    return path, out


# ----------------------------------------------------------------------
# host annotations (the obs-gated successor of utils/tracing.span)
# ----------------------------------------------------------------------

_NULL_CTX = contextlib.nullcontext()
_trace_annotation = False  # unresolved; None = profiler unavailable


def annotation_ctx(name: str, **stats):
    """HOT-PATH form: returns a jax.profiler.TraceAnnotation (obs on AND
    an obs-driven capture recording) or a shared nullcontext — a plain
    call + two checks, no generator. `stats` (ints, floats, strings)
    become the event's stats in the capture: `step=` on the batcher's
    step phases, `rid=` on an admission's parts. Two host costs
    forced this shape: the @contextmanager `annotation` below pays
    generator machinery and per-call imports around a jit dispatch, and
    even a bare TraceAnnotation is not free there — both paid EVERY
    step of a ms-scale decode loop for annotations nobody is recording.
    Gating on `capturing()` (set by _traced during POST /profilez and
    the auto-trigger) leaves the steady state two attribute checks; a
    capture driven outside obs.profile (bare
    jax.profiler.start_trace) won't see these annotations unless it
    wraps its body in `mark_recording` (utils/tracing.trace_to does) —
    prefer obs.profile.capture. The
    TraceAnnotation class is resolved once, lazily — importing this
    module still never touches jax.

    The annotation lands on the capture's `/host:CPU` plane, on the
    calling thread's line and on the same clock as the device planes'
    operations, so a reader intersects the two with no clock arithmetic
    (chipbench/spans.py does)."""
    global _trace_annotation
    from dnn_tpu import obs

    if not _capturing or not obs.enabled():
        return _NULL_CTX
    if _trace_annotation is False:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except Exception:  # pragma: no cover - profiler unavailable
            _trace_annotation = None
    if _trace_annotation is None:
        return _NULL_CTX
    return _trace_annotation(name, **stats)


def open_span(name: str, **stats):
    """`annotation_ctx` for a span whose end is not a block's end (a
    StepClock phase closes where the next mark is stamped): enters the
    annotation now and returns it for `close_span`, or None when no
    capture records. A span that is never closed is never written."""
    ctx = annotation_ctx(name, **stats)
    if ctx is _NULL_CTX:
        return None
    ctx.__enter__()
    return ctx


def close_span(span):
    if span is not None:
        span.__exit__(None, None, None)


@contextlib.contextmanager
def annotation(name: str) -> Iterator[None]:
    """Named host-side span, visible in captured profiles. Degrades to
    nothing when observability is off or the profiler is unavailable —
    library code annotates unconditionally. Convenient for ms-scale
    paths (relay stage hops, prefill chunks); per-decode-step code uses
    `annotation_ctx`."""
    with annotation_ctx(name):
        yield


@contextlib.contextmanager
def step_annotation(step: int, name: str = "step") -> Iterator[None]:
    """Mark one pipeline/training step; XLA profilers group device ops
    under it. Obs-gated like `annotation`."""
    from dnn_tpu import obs

    if not obs.enabled():
        yield
        return
    try:
        import jax

        ctx = jax.profiler.StepTraceAnnotation(name, step_num=step)
    except Exception:  # pragma: no cover
        ctx = contextlib.nullcontext()
    with ctx:
        yield


# ----------------------------------------------------------------------
# server-side handle (what obs/http.py drives)
# ----------------------------------------------------------------------

class Profiler:
    """The /profilez backend: on-demand capture plus (optionally) the
    auto-trigger arm. `arm_target` is any object with a writable
    `auto_profile` attribute — the LM batcher worker reads it once per
    step (one None check) and, when armed, captures the step after the
    first one that exceeds the threshold."""

    def __init__(self, *, capture_root: Optional[str] = None,
                 arm_target=None, keep: int = 8):
        self.capture_root = capture_root or spool_dir()
        self.keep = keep
        self._arm_target = arm_target

    def capture(self, duration_ms: float, perfetto: bool = False,
                python_tracer: Optional[bool] = None) -> str:
        return capture(duration_ms, capture_root=self.capture_root,
                       keep=self.keep, perfetto=perfetto,
                       python_tracer=python_tracer)

    @property
    def can_arm(self) -> bool:
        return self._arm_target is not None

    def arm_auto(self, threshold_ms: float, duration_ms: float = 0.0,
                 perfetto: bool = False,
                 python_tracer: Optional[bool] = None):
        """Arm the next-slow-step auto capture. duration_ms > 0 extends
        the capture past the triggering step by that wall window (0 =
        exactly one step)."""
        if self._arm_target is None:
            raise ValueError("this endpoint has no step loop to arm "
                             "(stage servers capture on demand only)")
        self._arm_target.auto_profile = {
            "threshold_s": float(threshold_ms) / 1e3,
            "extra_s": max(0.0, float(duration_ms)) / 1e3,
            "capture_root": self.capture_root, "keep": self.keep,
            "perfetto": bool(perfetto), "python_tracer": python_tracer,
        }

    def disarm(self):
        if self._arm_target is not None:
            self._arm_target.auto_profile = None

    def status(self) -> dict:
        armed = getattr(self._arm_target, "auto_profile", None) \
            if self._arm_target is not None else None
        return {
            "captures": list_captures(self.capture_root),
            "armed": None if armed is None else {
                "threshold_ms": armed["threshold_s"] * 1e3,
                "extra_ms": armed["extra_s"] * 1e3},
        }
