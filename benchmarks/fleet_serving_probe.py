"""fleet_serving: the fleet front door's measured contract (ISSUE 13).

Open-loop many-client load (the seed of ROADMAP item 5b's generator)
drives REAL `node --serve_lm` replica subprocesses, each under its own
`chaos.supervisor.Supervisor`, in two legs at the SAME demand:

  * SINGLE (the asserted single-replica baseline row): the load hits
    one replica directly — no front door. Demand is calibrated to
    ~2x the replica's measured capacity, so its FIFO queue saturates
    and the admit-then-deadline-cancel pathology takes over: requests
    are admitted just as their propagated `dl=` budget runs out, burn
    decode on work nobody will receive, and DELIVERED tokens/sec
    collapses far below capacity.
  * FLEET: the same demand through the router over 2 replicas with
    SLO-driven admission (per-replica in-flight bound): excess
    arrivals shed EXPLICITLY (UNAVAILABLE — cheap, retriable),
    admitted work finishes inside its deadline, and ONE replica is
    SIGKILLed mid-measurement (the supervisor respawns it; the router
    routes around and sibling-retries the in-flight casualties).

Asserted floors (--assert exits nonzero when any fails):

  * availability (fleet leg): >= 99% of submitted requests COMPLETED-
    OR-EXPLICITLY-REJECTED and ZERO silently lost — through a kill;
  * fleet tokens/sec >= 1.5x the single-replica leg's — WHOLE-LEG
    delivered on both sides (the single leg keeps its healthy
    pre-saturation ramp, the fleet keeps its kill dent; the post-
    settle steady-state window rides the row as detail, where the
    single replica reads ~ZERO). On this 1-core host the win is pure
    CONTROL PLANE — admission keeping queues short enough that
    admitted work completes (the single leg wastes its capacity on
    doomed decodes); on a multi-chip substrate the same row adds the
    width win on top. STUDIES §17 has the collapse numbers;
  * the kill pairs with its `supervisor_restart` recovery event IN THE
    DUMPED RING (the incident reconstructs from the flight recorder).

`python -m benchmarks.fleet_serving_probe [--assert] [--light]
[--require-substrate tpu|cpu]` prints one JSON row; the run_all
`fleet_serving` row rides `measure()` and honors the same substrate
contract (PR 11's flag) via $DNN_TPU_REQUIRE_SUBSTRATE.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

AVAILABILITY_FLOOR = 0.99
FLEET_SPEEDUP_FLOOR = 1.5
RECOVERY_DEADLINE_S = 240.0   # gpt2 child respawn incl. jax import +
# first compile on a contended core

MODEL = "gpt2"       # the full config: ~1.8 s/request on this host —
# the regime where deadline waste is REAL (gpt2-test decodes a whole
# request in ~50 ms, far below any honest client deadline)
SLOTS = 2
MAX_LEN = 96
PROMPT_LEN = 8
MAX_NEW = 24
REQ_TIMEOUT_S = 10.0
OVERLOAD = 2.0       # open-loop demand vs the measured capacity

# ports: distinct from chaos (594xx/595xx) and relay probes
_SINGLE = (59901, 59911)            # (grpc, metrics)
_FLEET_BASE = (59921, 59931)        # 2 replicas from here
_ROUTER_PORT = 59920


def _prompt():
    import numpy as np

    return (np.arange(1, PROMPT_LEN + 1) % 999).astype(np.int32)


class _OpenLoopGen:
    """Open-loop arrivals at `rate_hz`, one thread per request (the
    chaos-probe pattern): every request records exactly one outcome —
    ok (with its token count and completion time) or rejected — or
    stays None (silently lost, the thing the probe asserts cannot
    happen). Completion-timestamped so delivered-tokens/sec can be
    windowed identically across legs."""

    def __init__(self, address: str, rate_hz: float, dur_s: float,
                 t0: float):
        self.address = address
        self.rate = float(rate_hz)
        self.dur = float(dur_s)
        self.t0 = t0
        self.records: list = []

    def run(self):
        import numpy as np

        from dnn_tpu.comm.client import NodeClient

        prompt = np.asarray(_prompt(), np.int32)
        threads = []
        stop_at = time.monotonic() + self.dur
        nxt = time.monotonic()
        i = 0

        def one(rec):
            cl = NodeClient(self.address, transport="grpc",
                            breaker=False)
            try:
                status, result = cl.send_tensor(
                    prompt, request_id=f"gen:{MAX_NEW}:{rec['i']}",
                    timeout=REQ_TIMEOUT_S, retries=0)
                if result is not None:
                    rec["outcome"] = "ok"
                    rec["tokens"] = int(np.asarray(result).size)
                else:
                    rec["outcome"] = "rejected"
                    rec["error"] = str(status)[:120]
            except Exception as e:  # noqa: BLE001 — EXPLICIT rejection
                rec["outcome"] = "rejected"
                rec["error"] = f"{type(e).__name__}: {e}"[:120]
            finally:
                rec["t_done"] = time.monotonic() - self.t0
                cl.close()

        while time.monotonic() < stop_at:
            now = time.monotonic()
            if now < nxt:
                time.sleep(min(nxt - now, 0.05))
                continue
            nxt += 1.0 / self.rate
            rec = {"i": i, "t": now - self.t0, "outcome": None,
                   "tokens": 0}
            self.records.append(rec)
            th = threading.Thread(target=one, args=(rec,), daemon=True)
            th.start()
            threads.append(th)
            i += 1
        t_end = time.monotonic() + REQ_TIMEOUT_S + 10
        for th in threads:
            th.join(timeout=max(t_end - time.monotonic(), 0.1))
        return self


def _delivered_tps(records, lo_s: float, hi_s: float) -> float:
    """Tokens of COMPLETED requests finishing inside [lo, hi) per
    second — goodput, not offered load (a deadline-cancelled request's
    decoded-then-discarded tokens count for nothing, which is exactly
    the collapse the single leg measures)."""
    toks = sum(r["tokens"] for r in records
               if r["outcome"] == "ok"
               and lo_s <= r.get("t_done", -1) < hi_s)
    return toks / max(hi_s - lo_s, 1e-9)


def _warm(address: str, deadline_s: float = 300.0):
    """First real request (pays the child's compile); polled — a
    mid-boot UNAVAILABLE is 'not yet', not 'failed'."""
    import numpy as np

    from dnn_tpu.comm.client import NodeClient

    t_end = time.monotonic() + deadline_s
    last = "no attempt"
    while time.monotonic() < t_end:
        cl = NodeClient(address, transport="grpc", breaker=False)
        try:
            status, result = cl.send_tensor(
                np.asarray(_prompt(), np.int32),
                request_id=f"gen:{MAX_NEW}:0", timeout=120.0, retries=0)
            if result is not None:
                return
            last = status
        except Exception as e:  # noqa: BLE001 — still booting
            last = f"{type(e).__name__}: {e}"
        finally:
            cl.close()
        time.sleep(1.0)
    raise RuntimeError(f"warm request never completed: {last[:200]}")


def _calibrate_capacity(address: str, secs: float) -> float:
    """Closed-loop saturation (SLOTS+1 workers) -> tokens/sec: the
    replica's real capacity on THIS host, so the open-loop demand is
    an honest multiple of it whatever silicon runs the probe."""
    import numpy as np

    from dnn_tpu.comm.client import NodeClient

    done = []
    stop_at = time.monotonic() + secs

    def w():
        cl = NodeClient(address, transport="grpc", breaker=False)
        try:
            while time.monotonic() < stop_at:
                try:
                    _, result = cl.send_tensor(
                        np.asarray(_prompt(), np.int32),
                        request_id=f"gen:{MAX_NEW}:1",
                        timeout=60.0, retries=0)
                    if result is not None:
                        done.append(int(np.asarray(result).size))
                except Exception:  # noqa: BLE001 — calibration only
                    time.sleep(0.2)
        finally:
            cl.close()

    ths = [threading.Thread(target=w, daemon=True)
           for _ in range(SLOTS + 1)]
    t0 = time.monotonic()
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=secs + 90)
    wall = time.monotonic() - t0
    return sum(done) / max(wall, 1e-9)


def measure(light: bool = False) -> dict:
    from dnn_tpu import obs
    from dnn_tpu.control.policy import wanted_replicas
    from dnn_tpu.control.replicaset import ReplicaSet
    from dnn_tpu.control.router import start_router_in_background

    settle_s = 12.0 if light else 24.0
    measure_s = 16.0 if light else 40.0
    calib_s = 6.0 if light else 10.0
    flight_rec = obs.flight.recorder()
    row: dict = {"model": MODEL, "slots": SLOTS, "max_new": MAX_NEW,
                 "req_timeout_s": REQ_TIMEOUT_S, "overload": OVERLOAD}

    # ---- leg A: one replica, no front door ---------------------------
    with tempfile.TemporaryDirectory(prefix="fleet_single_") as tmp:
        rset1 = ReplicaSet.spawn_lm_fleet(
            tmp, model=MODEL, base_port=_SINGLE[0],
            metrics_base_port=_SINGLE[1], roles=["both"], slots=SLOTS,
            max_len=MAX_LEN, kv="dense",
            ready_deadline_s=RECOVERY_DEADLINE_S)
        rset1.start()
        try:
            if not rset1.wait_serving(1, RECOVERY_DEADLINE_S):
                raise RuntimeError("single replica never came up")
            addr = f"127.0.0.1:{_SINGLE[0]}"
            _warm(addr)
            cap_tps = _calibrate_capacity(addr, calib_s)
            rate_hz = OVERLOAD * cap_tps / MAX_NEW
            t0 = time.monotonic()
            gen = _OpenLoopGen(addr, rate_hz,
                               settle_s + measure_s, t0).run()
            single_tps = _delivered_tps(gen.records, settle_s,
                                        settle_s + measure_s)
            single_whole = _delivered_tps(gen.records, 0.0,
                                          settle_s + measure_s)
            ok_n = sum(1 for r in gen.records if r["outcome"] == "ok")
            row.update({
                "capacity_tokens_per_sec": round(cap_tps, 1),
                "open_loop_hz": round(rate_hz, 2),
                "single_requests": len(gen.records),
                "single_completed": ok_n,
                "single_tokens_per_sec": round(single_tps, 1),
                "single_tokens_per_sec_whole_leg":
                    round(single_whole, 1),
                "single_delivered_frac_of_capacity":
                    round(single_tps / max(cap_tps, 1e-9), 3),
            })
        finally:
            rset1.stop()

    # ---- leg B: 3 replicas + router, kill one mid-measurement --------
    with tempfile.TemporaryDirectory(prefix="fleet_router_") as tmp:
        rset = ReplicaSet.spawn_lm_fleet(
            tmp, model=MODEL, base_port=_FLEET_BASE[0],
            metrics_base_port=_FLEET_BASE[1], roles=["both"] * 2,
            slots=SLOTS, max_len=MAX_LEN, kv="dense",
            ready_deadline_s=RECOVERY_DEADLINE_S)
        rset.start()
        router = rstop = None
        try:
            if not rset.wait_serving(2, RECOVERY_DEADLINE_S):
                raise RuntimeError("fleet replicas never all came up")
            # in-flight bound = the replica's slot count: admitted
            # work fills each replica's batch (amortizing per-step
            # overhead — measured: two batch-1 gpt2 processes thrash to
            # 9 tok/s aggregate on this host, two batch-2 recover the
            # full 22) while staying few enough to finish inside the
            # propagated deadline — the admission controller IS the
            # contract
            router, rstop = start_router_in_background(
                rset, port=_ROUTER_PORT, policy="least_queue",
                max_inflight_per_replica=SLOTS,
                default_deadline_s=REQ_TIMEOUT_S + 2.0)
            raddr = f"127.0.0.1:{_ROUTER_PORT}"
            # warm EVERY replica by address (the first generate pays
            # the child's compile — routed warmups can land on one
            # replica thrice and leave the others cold inside the
            # client deadline), then one routed round-trip
            for h in rset.replicas.values():
                _warm(h.address)
            _warm(raddr)
            rate_hz = row["open_loop_hz"]
            t0 = time.monotonic()
            gen = _OpenLoopGen(raddr, rate_hz, settle_s + measure_s, t0)
            runner = threading.Thread(target=gen.run, daemon=True)
            runner.start()
            # SIGKILL one replica halfway into the measured window
            while time.monotonic() - t0 < settle_s + measure_s / 2.0:
                time.sleep(0.2)
            victim = rset.replicas["r1"]
            ev = obs.flight.record("fleet_kill", replica="r1",
                                   t_rel=round(time.monotonic() - t0, 2))
            ts_kill = ev["ts"] if ev else time.time()
            victim.kill()
            # the autoscaling signal, sampled UNDER load (an idle
            # fleet legitimately scales down — that is not the number
            # this row reports); the router's own view: shedding-aware
            # (admission keeps replica queues short exactly when the
            # fleet is overloaded, so queue depth alone is blind)
            time.sleep(2.0)
            wanted = wanted_replicas(
                router._views(), slots_hint=SLOTS,
                shedding=router.state == "shedding")
            runner.join(timeout=settle_s + measure_s
                        + REQ_TIMEOUT_S + 60)
            fleet_tps = _delivered_tps(gen.records, settle_s,
                                       settle_s + measure_s)
            fleet_whole = _delivered_tps(gen.records, 0.0,
                                         settle_s + measure_s)
            total = len(gen.records)
            ok_n = sum(1 for r in gen.records if r["outcome"] == "ok")
            rej_n = sum(1 for r in gen.records
                        if r["outcome"] == "rejected")
            lost = total - ok_n - rej_n
            availability = (ok_n + rej_n) / total if total else 0.0
            # recovery: wait for the supervisor to bring r1 back and
            # record supervisor_restart AFTER the kill
            rec_ev = None
            t_end = time.monotonic() + RECOVERY_DEADLINE_S
            while time.monotonic() < t_end and rec_ev is None:
                for e in flight_rec.events(kind="supervisor_restart"):
                    if e.get("stage") == "r1" and e["ts"] > ts_kill:
                        rec_ev = e
                        break
                time.sleep(0.5)
            row.update({
                "fleet_replicas": 2,
                "fleet_requests": total,
                "fleet_completed": ok_n,
                "fleet_explicitly_rejected": rej_n,
                "fleet_silently_lost": lost,
                "fleet_availability": round(availability, 5),
                "fleet_tokens_per_sec": round(fleet_tps, 1),
                "fleet_tokens_per_sec_whole_leg":
                    round(fleet_whole, 1),
                "fleet_shed_total": router.shed_total,
                "kill_outage_s": (round(rec_ev["ts"] - ts_kill, 1)
                                  if rec_ev else None),
                "wanted_replicas": wanted,
            })
        finally:
            if rstop is not None:
                rstop()
            rset.stop()

    # ---- ring dump: assertions read the ARTIFACT, not memory ---------
    dump_path = os.path.join(tempfile.gettempdir(),
                             f"fleet_serving_ring_{os.getpid()}.jsonl")
    flight_rec.dump(dump_path)
    dumped = [json.loads(line) for line in open(dump_path)
              if line.strip()]
    kills = [e for e in dumped if e["kind"] == "fleet_kill"]
    restarts = [e for e in dumped
                if e["kind"] == "supervisor_restart"]
    paired = bool(kills) and all(
        any(r.get("stage") == k.get("replica") and r["ts"] > k["ts"]
            for r in restarts)
        for k in kills)

    # the asserted ratio compares WHOLE-LEG delivered tokens/sec: the
    # single leg keeps its healthy pre-saturation ramp (its best
    # behavior), the fleet leg keeps its kill dent — both legs priced
    # end to end, no degenerate zero denominators. The post-settle
    # window rides the row as the steady-state detail (the single
    # replica's steady state under sustained overload is ~ZERO — the
    # admit-then-deadline-cancel collapse STUDIES §17 walks through).
    speedup = min(row["fleet_tokens_per_sec_whole_leg"]
                  / max(row["single_tokens_per_sec_whole_leg"], 1e-9),
                  999.0)
    ok_avail = (row["fleet_availability"] >= AVAILABILITY_FLOOR
                and row["fleet_silently_lost"] == 0)
    ok_speed = speedup >= FLEET_SPEEDUP_FLOOR
    row.update({
        "fleet_vs_single": round(speedup, 2),
        "flight_dump": dump_path,
        "events_paired": paired,
        "ok_availability": bool(ok_avail),
        "ok_speedup": bool(ok_speed),
        "ok_paired": bool(paired),
        "ok": bool(ok_avail and ok_speed and paired),
        # the substrate of the MEASURED serving, not of this parent
        # process: spawn_lm_fleet pins every replica child to
        # JAX_PLATFORMS=cpu (a chip belongs to one process — N children
        # cannot share it; one replica per device is ROADMAP R5), so a
        # TPU parent must not stamp a substrate the serving never touched
        "platform": "cpu",
        "round_substrate": "cpu",
    })
    return row


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--assert", dest="do_assert", action="store_true",
                    help="exit nonzero when a floor fails "
                         f"(fleet availability >= "
                         f"{AVAILABILITY_FLOOR} with zero silent "
                         f"losses through a kill, fleet tokens/sec >= "
                         f"{FLEET_SPEEDUP_FLOOR}x the single-replica "
                         "leg, kill paired with supervisor_restart in "
                         "the dumped ring)")
    ap.add_argument("--light", action="store_true",
                    help="shortened legs (smoke use; the acceptance "
                         "configuration is the full run)")
    ap.add_argument("--require-substrate", choices=["tpu", "cpu"],
                    default=os.environ.get("DNN_TPU_REQUIRE_SUBSTRATE")
                    or None,
                    help="fail the row (ok=false, nonzero exit) when "
                         "the probe ran on a different substrate — "
                         "PR 11's trajectory contract "
                         "($DNN_TPU_REQUIRE_SUBSTRATE is the run_all "
                         "spelling)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    row = measure(light=args.light)
    if args.require_substrate:
        row["required_substrate"] = args.require_substrate
        if row["round_substrate"] != args.require_substrate:
            row["ok"] = False
            row["note"] = (f"required substrate "
                           f"'{args.require_substrate}' but the probe "
                           f"ran on '{row['round_substrate']}'")
    print(json.dumps(row), flush=True)
    if args.do_assert and not row["ok"]:
        print(f"ASSERT FAILED: availability="
              f"{row['fleet_availability']} (floor "
              f"{AVAILABILITY_FLOOR}, lost="
              f"{row['fleet_silently_lost']}), fleet_vs_single="
              f"{row['fleet_vs_single']} (floor {FLEET_SPEEDUP_FLOOR}),"
              f" paired={row['events_paired']}, ok={row['ok']}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
