"""kv_tier: the fleet KV tier's measured contract (ISSUE 15).

Router + 2 REAL `node --serve_lm` replica subprocesses (gpt2, paged KV
+ the radix prefix store) under the PR 13 multi-turn-chat arrival
schedule with affinity DELIBERATELY BROKEN: the router runs
`kvtier="pull"` (placement by round-robin policy, never by prefix
holder) and the schedule assigns every warm chat turn to the replica
that did NOT prefill its tenant's system prompt — the worst case for
a per-replica cache, and exactly the traffic the fleet tier exists to
serve. The only thing that can save the reuse is block migration over
the lease rungs.

Asserted (--assert exits nonzero when any fails):

  * cross-replica block-hit ratio >= CROSS_HIT_FLOOR (0.5): of all
    block-granular prefix hits across the fleet, at least half were
    served from blocks ADOPTED from a sibling (read off the replicas'
    own counters — serving_kvtier_remote_block_hits_total /
    serving_prefix_blocks_reused_total);
  * adopted-block decode is TOKEN-IDENTICAL to local prefill, greedy
    AND seeded-sampled (direct replica clients, the migration forced
    with kv_pull_from);
  * warm-turn TTFT p95 is >= TTFT_RATIO_FLOOR (2.0x) better than
    forced-cold (unique-prefix) TTFT p95 — both measured as
    first-streamed-token time through the SAME router. "Warm" = the
    tier's steady state: each tenant's FIRST anti-affinity turn pays
    the one-time synchronous migration on its own TTFT and rides the
    row as `migration_ttft_p95_ms` instead (the price of moving the
    blocks is reported, not hidden — and paid once, not per turn);
  * migrated bytes per warm request < the full-KV row-handoff baseline
    (the PR 12 `prefill` endpoint's packed payload for the same
    prompt, measured on the wire);
  * the donor-death chaos leg: a lease with no adopter EXPIRES
    (lease_expire + lease_reclaim in the donor's dumped /debugz ring),
    and a pull against a SIGKILLed donor falls back loud
    (kvtier_fallback in the adopter's ring) with the follow-up
    generate completing token-identical to the donor's pre-kill output
    and the adopter's pool accounting at baseline (zero leaked
    blocks).

Prefill-FLOPs-avoided lands on the goodput gauges the replicas already
export; the row reports the fleet's prefill-chunk saving against the
cold-equivalent count.

`python -m benchmarks.kv_tier_probe [--assert] [--light]` prints one
JSON row; run_all's `kv_tier` row rides `measure()` and the ledger
imports the floors from here.
"""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CROSS_HIT_FLOOR = 0.5
TTFT_RATIO_FLOOR = 2.0

MODEL = "gpt2"        # real prefill costs: the regime where skipping
# chunks is a measurable TTFT win (a toy config's prefill is noise)
SLOTS = 2
MAX_LEN = 96
PROMPT_PAD = 16
BLOCK_LEN = 8
SYS_BLOCKS = 6        # system prompt = 48 tokens = 6 shared blocks
MAX_NEW = 8
LEASE_TTL_S = 4.0
READY_DEADLINE_S = 240.0

_BASE = (59941, 59951)   # (grpc base, metrics base) for 2 replicas
_ROUTER_PORT = 59940


def _sys_prompt(tenant: int):
    import numpy as np

    return (np.arange(1, SYS_BLOCKS * BLOCK_LEN + 1) * (tenant + 3)
            % 997 + 1).astype(np.int32)


def _tail(i: int):
    import numpy as np

    n = 4 + (i * 7) % 4
    return ((np.arange(n) * 13 + i * 31) % 997 + 1).astype(np.int32)


def _scrape(port: int) -> dict:
    """Prometheus text -> {name: value} (labels folded by summation —
    enough for the counters this probe reads)."""
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ).read().decode()
    out: dict = {}
    for line in text.splitlines():
        m = re.match(r"^([a-zA-Z_:][\w:]*)(?:\{[^}]*\})? ([-+0-9.eE]+)$",
                     line)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(
                m.group(2))
    return out


def _rotation() -> int:
    """The in-process router's round-robin position, READ OFF ITS OWN
    COUNTERS instead of mirrored locally: every admitted request
    (outcome ok / error / deadline / unroutable — sheds never reach
    the pick) advanced the rotation exactly once in this serialized
    probe. Re-read before every placement-sensitive send, so a stray
    sibling retry (which advances the pick invisibly) mis-steers at
    most the one next turn instead of flipping the whole anti-affinity
    pattern — the drift that read 0.52 where the pattern should read
    ~1.0."""
    from dnn_tpu import obs

    m = obs.metrics()
    if m is None:
        return 0
    n = 0
    for key, val in m.snapshot()["counters"].items():
        if key.startswith("dnn_tpu_router_requests_total") \
                and 'outcome="shed"' not in key \
                and 'outcome="draining"' not in key:
            n += int(val)
    return n


def _debugz(port: int) -> list:
    return json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/debugz?format=json", timeout=10
    ).read().decode())


def _stream_ttft(address: str, prompt, rid: str,
                 timeout: float = 120.0):
    """-> (ttft_s, tokens) via GenerateStream — first token time is
    the real TTFT, not request completion."""
    import numpy as np

    from dnn_tpu.comm.client import NodeClient

    cl = NodeClient(address, transport="grpc", breaker=False)
    n = 0
    t0 = time.perf_counter()
    ttft = None
    try:
        for _resp in cl.send_tensor_stream(prompt, request_id=rid,
                                           timeout=timeout):
            if ttft is None:
                ttft = time.perf_counter() - t0
            n += 1
    finally:
        cl.close()
    return ttft, n


def _gen(address: str, prompt, *, seed=None, temperature=None,
         timeout: float = 120.0):
    import numpy as np

    from dnn_tpu.comm.client import NodeClient

    cl = NodeClient(address, transport="grpc", breaker=False)
    try:
        return np.asarray(cl.generate(
            prompt, max_new_tokens=MAX_NEW, seed=seed,
            temperature=temperature, timeout=timeout))
    finally:
        cl.close()


def _warm(address: str, deadline_s: float = 300.0):
    import numpy as np

    from dnn_tpu.comm.client import NodeClient

    t_end = time.monotonic() + deadline_s
    last = "no attempt"
    probe = (np.arange(1, 9) % 97 + 1).astype(np.int32)
    while time.monotonic() < t_end:
        cl = NodeClient(address, transport="grpc", breaker=False)
        try:
            _, result = cl.send_tensor(
                probe, request_id=f"gen:{MAX_NEW}:0", timeout=120.0,
                retries=0)
            if result is not None:
                return
        except Exception as e:  # noqa: BLE001 — still booting
            last = f"{type(e).__name__}: {e}"
        finally:
            cl.close()
        time.sleep(1.0)
    raise RuntimeError(f"warm request never completed: {last[:200]}")


def _p95(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(0.95 * (len(xs) - 1)))] if xs \
        else None


def measure(light: bool = False) -> dict:
    import numpy as np

    from dnn_tpu.control.replicaset import ReplicaSet
    from dnn_tpu.control.router import start_router_in_background
    from dnn_tpu.workloads.arrivals import poisson_arrivals

    n_cold = 4 if light else 8
    warm_rate = 0.5 if light else 0.6
    warm_dur = 20.0 if light else 40.0
    row: dict = {"model": MODEL, "block_len": BLOCK_LEN,
                 "sys_blocks": SYS_BLOCKS, "max_new": MAX_NEW}
    with tempfile.TemporaryDirectory(prefix="kv_tier_") as tmp:
        rset = ReplicaSet.spawn_lm_fleet(
            tmp, model=MODEL, base_port=_BASE[0],
            metrics_base_port=_BASE[1], roles=["both"] * 2,
            slots=SLOTS, max_len=MAX_LEN, kv="paged",
            ready_deadline_s=READY_DEADLINE_S,
            extra_args=["--prefix_cache", "64",
                        "--block_len", str(BLOCK_LEN),
                        "--prompt_pad", str(PROMPT_PAD),
                        # pool sized for the TIER, not only the slots:
                        # the auto-sized 25-block pool forces constant
                        # store eviction under 2 tenants + live slots
                        # (a mis-deployment, and measurement churn)
                        "--paged_blocks", "64",
                        "--kv_lease_ttl_s", str(LEASE_TTL_S)])
        rset.start()
        router = rstop = None
        try:
            if not rset.wait_serving(2, READY_DEADLINE_S):
                raise RuntimeError("replicas never came up")
            # affinity deliberately broken: placement is the rotation,
            # never the holder; the directory only instructs PULLS
            router, rstop = start_router_in_background(
                rset, port=_ROUTER_PORT, policy="round_robin",
                kvtier="pull", kv_block_len=BLOCK_LEN,
                max_inflight_per_replica=SLOTS,
                default_deadline_s=120.0)
            raddr = f"127.0.0.1:{_ROUTER_PORT}"
            addrs = {name: h.address
                     for name, h in rset.replicas.items()}
            mports = {name: int(h.obs_url.rsplit(":", 1)[1])
                      for name, h in rset.replicas.items()}
            names = sorted(addrs)
            for a in addrs.values():
                _warm(a)
            _warm(raddr)

            def stream_routed(prompt, rid):
                t, _ = _stream_ttft(raddr, prompt, rid)
                return t

            # ---- forced-cold TTFT: unique prefixes, zero reuse ------
            cold_ttfts = []
            for i in range(n_cold):
                p = np.concatenate([
                    ((np.arange(1, SYS_BLOCKS * BLOCK_LEN + 1)
                      * (i + 11) * 17) % 991 + 1).astype(np.int32),
                    _tail(900 + i)])
                cold_ttfts.append(stream_routed(
                    p, f"gen:{MAX_NEW}:{7000 + i}"))

            # ---- seed: one cold turn per tenant through the router --
            origin = {}
            for t in range(2):
                placed = names[_rotation() % 2]
                stream_routed(
                    np.concatenate([_sys_prompt(t), _tail(t)]),
                    f"gen:{MAX_NEW}:{7100 + t}")
                origin[t] = placed
            row["origin"] = dict(origin)

            # ---- warm turns: every arrival goes to the tenant whose
            # blocks live on the OTHER replica (anti-affinity) --------
            arrivals = poisson_arrivals(warm_rate, warm_dur, seed=15,
                                        name="kvtier:chat")
            scr0 = {n: _scrape(mports[n]) for n in names}
            t0 = time.monotonic()
            # each tenant's FIRST anti-affinity turn carries the
            # synchronous block migration (lease + pull + adopt ride
            # its TTFT — the price of moving the blocks, paid once);
            # every later turn is the tier's steady state. Both
            # populations ride the row; the asserted p95 is the steady
            # state — the number millions of follow-up turns see.
            warm_ttfts, migration_ttfts = [], []
            seen_tenant: set = set()
            for i, at in enumerate(arrivals):
                now = time.monotonic() - t0
                if now < at:
                    time.sleep(at - now)
                placed = names[_rotation() % 2]
                tenant = next(t for t in (0, 1)
                              if origin[t] != placed)
                ttft = stream_routed(
                    np.concatenate([_sys_prompt(tenant),
                                    _tail(100 + i)]),
                    f"gen:{MAX_NEW}:{7200 + i}")
                if tenant in seen_tenant:
                    warm_ttfts.append(ttft)
                else:
                    seen_tenant.add(tenant)
                    migration_ttfts.append(ttft)
            scr1 = {n: _scrape(mports[n]) for n in names}

            def delta(key):
                return sum(scr1[n].get(key, 0.0)
                           - scr0[n].get(key, 0.0) for n in names)

            reused = delta("serving_prefix_blocks_reused_total")
            remote = delta("serving_kvtier_remote_block_hits_total")
            chunks = delta("serving_prefill_chunks_total")
            cold_equiv = sum(
                -(-(SYS_BLOCKS * BLOCK_LEN + _tail(100 + i).size)
                  // PROMPT_PAD)
                for i in range(len(arrivals)))
            migrated_bytes = delta("dnn_tpu_kvtier_migrated_bytes_total")
            migrated_blocks = delta(
                "dnn_tpu_kvtier_migrated_blocks_total")
            cross_ratio = remote / reused if reused else 0.0

            # ---- full-KV row-handoff baseline (the PR 12 wire) ------
            from dnn_tpu.comm.client import NodeClient

            cl = NodeClient(addrs[names[0]], transport="grpc",
                            breaker=False)
            try:
                row_handoff_bytes = int(cl.prefill_kv(
                    np.concatenate([_sys_prompt(0), _tail(0)]),
                    timeout=120.0).size)
            finally:
                cl.close()
            n_turns = len(warm_ttfts) + len(migration_ttfts)
            per_request_bytes = (migrated_bytes / n_turns
                                 if n_turns else 0.0)

            # ---- adopted-vs-local token parity (greedy + sampled) ---
            from dnn_tpu.comm.client import NodeClient as _NC

            par_prompt = np.concatenate([_sys_prompt(0), _tail(555)])
            donor_name = origin[0]
            other = next(n for n in names if n != donor_name)
            greedy_d = _gen(addrs[donor_name], par_prompt)
            samp_d = _gen(addrs[donor_name], par_prompt, seed=42,
                          temperature=0.9)
            cl = _NC(addrs[other], transport="grpc", breaker=False)
            try:
                pull_status = cl.kv_pull_from(addrs[donor_name],
                                              par_prompt)
            finally:
                cl.close()
            greedy_a = _gen(addrs[other], par_prompt)
            samp_a = _gen(addrs[other], par_prompt, seed=42,
                          temperature=0.9)
            parity = (greedy_d.tolist() == greedy_a.tolist()
                      and samp_d.tolist() == samp_a.tolist())
            row["parity_pull_status"] = str(pull_status)[:120]

            # ---- donor-death chaos leg ------------------------------
            # (a) an unconsumed lease on the donor expires: stage a
            # fresh prefix, lease it, never fetch — the TTL sweep must
            # record lease_expire + lease_reclaim in the DONOR's ring
            chaos_prompt = np.concatenate([
                ((np.arange(1, SYS_BLOCKS * BLOCK_LEN + 1) * 29)
                 % 983 + 1).astype(np.int32), _tail(777)])
            pre_kill = _gen(addrs[donor_name], chaos_prompt, seed=5,
                            temperature=0.8)
            cl = _NC(addrs[donor_name], transport="grpc",
                     breaker=False)
            try:
                lease_meta = cl.kv_lease(chaos_prompt)
            finally:
                cl.close()
            time.sleep(LEASE_TTL_S + 2.5)  # TTL + housekeeping tick
            donor_ring = _debugz(mports[donor_name])
            expired = [e for e in donor_ring
                       if e.get("kind") == "lease_expire"
                       and e.get("lease") == lease_meta["lease"]]
            reclaimed = [e for e in donor_ring
                         if e.get("kind") == "lease_reclaim"
                         and e.get("lease") == lease_meta["lease"]]
            # (b) SIGKILL the donor mid-migration: the adopter's pull
            # fails -> kvtier_fallback in ITS ring, the follow-up
            # generate re-prefills token-identically, zero leaks
            rset.replicas[donor_name].kill()
            cl = _NC(addrs[other], transport="grpc", breaker=False)
            try:
                dead_status = cl.kv_pull_from(addrs[donor_name],
                                              chaos_prompt,
                                              timeout=30.0)
            finally:
                cl.close()
            post_kill = _gen(addrs[other], chaos_prompt, seed=5,
                             temperature=0.8)
            other_ring = _debugz(mports[other])
            fallback_ev = [e for e in other_ring
                           if e.get("kind") == "kvtier_fallback"]
            other_m = _scrape(mports[other])
            used = other_m.get("serving_paged_blocks_used", -1.0)
            resident = other_m.get("dnn_tpu_kvtier_blocks", -2.0)
            # with no live requests, every used block must be store-
            # resident — anything else is a leak
            zero_leaks = used == resident
            chaos_ok = (bool(expired) and bool(reclaimed)
                        and "kvtier_fallback" in dead_status
                        and pre_kill.tolist() == post_kill.tolist()
                        and zero_leaks)
            # dump the artifacts the assertions just read
            dump = os.path.join(tempfile.gettempdir(),
                                f"kv_tier_rings_{os.getpid()}.json")
            with open(dump, "w") as f:
                json.dump({"donor": donor_ring, "adopter": other_ring},
                          f)

            warm_p95 = _p95(warm_ttfts)
            cold_p95 = _p95(cold_ttfts)
            ttft_ratio = (cold_p95 / warm_p95
                          if warm_p95 and cold_p95 else 0.0)
            ok_cross = cross_ratio >= CROSS_HIT_FLOOR
            ok_ttft = ttft_ratio >= TTFT_RATIO_FLOOR
            ok_bytes = (0 < per_request_bytes < row_handoff_bytes
                        if n_turns else False)
            row.update({
                "warm_turns": len(warm_ttfts),
                "migration_turns": len(migration_ttfts),
                "migration_ttft_p95_ms": round(
                    (_p95(migration_ttfts) or 0.0) * 1e3, 1),
                "cold_requests": n_cold,
                "ttft_cold_p95_ms": round(cold_p95 * 1e3, 1),
                "ttft_warm_p95_ms": round(warm_p95 * 1e3, 1),
                "ttft_cold_over_warm": round(ttft_ratio, 2),
                "blocks_reused": int(reused),
                "remote_block_hits": int(remote),
                "cross_replica_hit_ratio": round(cross_ratio, 4),
                "prefill_chunks_run_warm": int(chunks),
                "prefill_chunks_cold_equiv": int(cold_equiv),
                "prefill_chunks_avoided_frac": round(
                    1.0 - chunks / cold_equiv, 4) if cold_equiv else 0.0,
                "migrated_blocks": int(migrated_blocks),
                "migrated_bytes_total": int(migrated_bytes),
                "migrated_bytes_per_request": round(per_request_bytes),
                "row_handoff_baseline_bytes": row_handoff_bytes,
                "token_parity": bool(parity),
                "lease_expired_in_ring": bool(expired),
                "lease_reclaimed_in_ring": bool(reclaimed),
                "donor_death_fallback": "kvtier_fallback"
                                        in dead_status,
                "donor_death_parity":
                    pre_kill.tolist() == post_kill.tolist(),
                "zero_leaked_blocks": bool(zero_leaks),
                "rings_dump": dump,
                "ok_cross_hit": bool(ok_cross),
                "ok_ttft": bool(ok_ttft),
                "ok_bytes": bool(ok_bytes),
                "ok_parity": bool(parity),
                "ok_chaos": bool(chaos_ok),
                "ok": bool(ok_cross and ok_ttft and ok_bytes
                           and parity and chaos_ok),
                # replica children are pinned JAX_PLATFORMS=cpu (a chip
                # belongs to one process): the measured serving ran on
                # cpu whatever this parent process sees
                "platform": "cpu",
                "round_substrate": "cpu",
            })
        finally:
            if rstop is not None:
                rstop()
            rset.stop()
    return row


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--assert", dest="do_assert", action="store_true")
    ap.add_argument("--light", action="store_true",
                    help="shortened legs (smoke use; the acceptance "
                         "configuration is the full run)")
    ap.add_argument("--require-substrate", choices=["tpu", "cpu"],
                    default=os.environ.get("DNN_TPU_REQUIRE_SUBSTRATE")
                    or None)
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
        print("ASSERT FAILED: "
              f"cross_hit={row.get('cross_replica_hit_ratio')} "
              f"(floor {CROSS_HIT_FLOOR}), "
              f"ttft_ratio={row.get('ttft_cold_over_warm')} "
              f"(floor {TTFT_RATIO_FLOOR}), "
              f"bytes={row.get('ok_bytes')}, "
              f"parity={row.get('ok_parity')}, "
              f"chaos={row.get('ok_chaos')}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
