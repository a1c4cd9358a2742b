"""Decode MBU probe: the regression-asserted number for ISSUE 6.

PR 5's goodput gauges put the serving decode path at MBU 2.34% on this
host (benchmarks/STUDIES.md §10) and PR 1's HLO audit said why: the
decode lowering moved whole-cache copies per step. This probe turns the
gap into a bench contract, mirroring `obs_overhead`'s <2% row: measure
live `dnn_tpu_mbu` on the DECODE HOT PATH configuration this repo now
ships, and fail (`--assert` / the run_all `decode_mbu` row) when it
regresses below the floor.

Methodology (the §10 recipe, made reproducible):

  * rooflines — on TPU, the per-generation table (utils/flops.py); on a
    CPU host they are MEASURED at probe start (jitted f32 1024^3 matmul
    for FLOPs; preallocated np.copyto, read+write charged, for memory
    bandwidth) unless DNN_TPU_PEAK_FLOPS / DNN_TPU_PEAK_HBM_BW state
    them. §10's original numbers (125.8 GFLOP/s, 15.8 GB/s) came from
    this same pair of probes; an alloc-in-loop copy probe reads ~8x low
    (page faults), which is why the copy target is preallocated;
  * four legs, same model (the §10 shape — 4L/256d GPT, 4 slots,
    4 x 120-token greedy requests, steady-state warm), each with a fresh
    GoodputTracker constructed at the timed round's start. That
    construction point is load-bearing: the tracker's Throughput
    divides by LIFETIME when it is younger than its window, so a
    tracker built before warmup (the LMServer-installed gauge §10
    scraped) silently deflates every rate it reports by the
    construction-to-scrape gap — a measurement artifact this probe
    corrects and STUDIES §11 quantifies:
      - `mbu` (ASSERTED): the ISSUE 12 decode hot path — the §10 dense
        bucketed f32 pool with interleaved chunked prefill + double-
        buffered dispatch live (`prefill_chunk_tokens=16, overlap=True`);
      - `convoy_mbu`: the same pool WITHOUT the overlap machinery (the
        pre-ISSUE-12 path), apples-to-apples with the 2.34% baseline;
      - `dense_mbu`: the plain dense pool (the pre-flag default path);
      - `paged_int8_mbu`: the serving-default paged pool with int8 KV
        and the unrolled decode scan — the quantized rung (its MBU is
        NOT comparable to the f32 legs: int8 legitimately streams
        fewer accounted bytes per position, so equal speed reads
        LOWER; its tokens/sec is the comparable number).
  * the floor applies only where it was calibrated — CPU-substrate
    rooflines (measured or env-stated); a TPU row reports but does not
    gate until a healthy chip recalibrates it (the table peaks are 2
    orders of magnitude above any toy-model CPU figure, so a shared
    floor would be meaningless on both sides).

Standalone:  python benchmarks/decode_mbu_probe.py [--assert]
Suite row:   benchmarks/run_all.py config `decode_mbu` (cpu-runnable).
bench.py attaches measure(light=True)'s gauges to every round's JSON row.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# floor for the asserted leg's MBU on CPU-substrate rooflines.
# Re-calibrated for ISSUE 12 (the overlap/fusion PR): the asserted leg
# is now the serving hot path WITH the overlap machinery live (dense
# bucketed f32 + interleaved chunked prefill + double-buffered
# dispatch) at STEADY-STATE warmup (two warm rounds — the single-warm
# design let bucket-rung recompiles land in the timed round and
# deflate the §11-recorded 15.8%), measuring ~28-29% quiet on this
# host. The floor ratchets 5% -> 10%: ~3x under the measured value so
# scheduler noise can't flake the gate, 2x above the old floor so a
# regression to the pre-overlap path under load still FAILS.
MBU_FLOOR = 0.10

SLOTS = 4
NEW_TOKENS = 120
PROMPT = 8


def host_rooflines():
    """(peak_flops, peak_bytes, source): table on TPU, env override, or
    measured on this host (the §10 probes)."""
    import jax

    from dnn_tpu.utils.flops import device_peak_flops, device_peak_hbm_bw

    if jax.default_backend() == "tpu" or (
            os.environ.get("DNN_TPU_PEAK_FLOPS")
            and os.environ.get("DNN_TPU_PEAK_HBM_BW")):
        pf, pb = device_peak_flops(), device_peak_hbm_bw()
        if pf and pb:
            return pf, pb, ("table" if jax.default_backend() == "tpu"
                            else "env")
    import numpy as np

    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024))
    mm = jax.jit(lambda a, b: a @ b)
    mm(x, x).block_until_ready()
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < 0.5:
        mm(x, x).block_until_ready()
        n += 1
    peak_f = n * 2 * 1024 ** 3 / (time.perf_counter() - t0)
    a = np.random.rand(1 << 25)
    b = np.empty_like(a)
    np.copyto(b, a)  # fault the pages OUTSIDE the timed loop
    t0 = time.perf_counter()
    m = 0
    while time.perf_counter() - t0 < 0.5:
        np.copyto(b, a)
        m += 1
    peak_b = m * a.nbytes * 2 / (time.perf_counter() - t0)
    return peak_f, peak_b, "measured"


def _build(cfg, prepared, **kw):
    from dnn_tpu.runtime.serving import ContinuousBatcher

    return ContinuousBatcher(cfg, prepared, slots=SLOTS,
                             max_len=cfg.block_size, prompt_pad=16, **kw)


def _leg(cfg, prepared, peak_f, peak_b, *, new_tokens, kv_dtype=None,
         reps: int = 3, warm: int = 2, **kw):
    """One serving leg: `warm` rounds (two by default — the first grows
    the bucket ladder, the second compiles the admission programs at
    the grown rungs, so the timed rounds measure serving rather than
    one-time compiles), then `reps` timed rounds, each with a FRESH
    GoodputTracker whose lifetime IS its timed window; the best round
    is the leg's number (utilization is a capability measure — a
    scheduler-noise-slowed round under-reports the path, it doesn't
    refute it; the §8 lesson applied to rates)."""
    import jax.numpy as jnp
    import numpy as np

    from dnn_tpu.obs.goodput import GoodputTracker, model_cost

    srv = _build(cfg, prepared, kv_dtype=kv_dtype, **kw)

    def round_():
        for i in range(SLOTS):
            srv.submit(np.arange(1, PROMPT + 1), new_tokens, seed=i)
        srv.drain()
        srv.results.clear()
        srv.finish_reasons.clear()

    for _ in range(warm):  # compile + absorb first-dispatch overheads
        round_()
    best = None
    for _ in range(reps):
        tracker = GoodputTracker(
            model_cost(cfg, prepared, kv_dtype=kv_dtype or jnp.float32),
            peak_flops=peak_f, peak_bytes=peak_b, window_s=1e9)
        srv.goodput = tracker
        t0 = time.perf_counter()
        round_()
        dt = time.perf_counter() - t0
        row = {
            "mbu": tracker.mbu(),
            "mfu": tracker.mfu(),
            "tokens_per_sec": round(tracker.tokens_per_sec(), 1),
            "round_s": round(dt, 3),
        }
        if best is None or row["mbu"] > best["mbu"]:
            best = row
    return best


def measure(light: bool = False) -> dict:
    """Both legs -> one row. `light` (bench.py's per-round attachment)
    runs a shorter decode round and skips the baseline leg."""
    import jax

    from dnn_tpu import obs
    from dnn_tpu.models import gpt

    was = obs.enabled()
    obs.set_enabled(True)  # the tracker is fed from obs-gated blocks
    try:
        peak_f, peak_b, src = host_rooflines()
        cfg = gpt.GPTConfig(block_size=256, vocab_size=512, n_layer=4,
                            n_head=4, n_embd=256)
        prepared = gpt.prepare_stacked(
            gpt.init(jax.random.PRNGKey(0), cfg), cfg)
        new_tokens = 40 if light else NEW_TOKENS
        # the asserted leg is the post-ISSUE-12 decode hot path: the
        # s10 shape with the overlap machinery live — interleaved
        # chunked prefill + double-buffered dispatch
        s10 = _leg(cfg, prepared, peak_f, peak_b, new_tokens=new_tokens,
                   reps=2 if light else 3, decode_buckets=True,
                   prefill_chunk_tokens=16, overlap=True)
        row = {
            "mbu": round(s10["mbu"], 4),
            "mfu": round(s10["mfu"], 4),
            "tokens_per_sec": s10["tokens_per_sec"],
            "peak_flops": round(peak_f, 1),
            "peak_hbm_bw": round(peak_b, 1),
            "rooflines": src,
            "platform": jax.default_backend(),
            "slots": SLOTS, "new_tokens": new_tokens,
            "asserted_leg": "decode_buckets=True f32 + "
                            "prefill_chunk_tokens=16 + overlap (the s10 "
                            "config on the ISSUE 12 hot path)",
            "vs_studies_s10": round(s10["mbu"] / 0.0234, 2),
        }
        if not light:
            convoy = _leg(cfg, prepared, peak_f, peak_b,
                          new_tokens=new_tokens, decode_buckets=True)
            row["convoy_mbu"] = round(convoy["mbu"], 4)
            row["convoy_tokens_per_sec"] = convoy["tokens_per_sec"]
            dense = _leg(cfg, prepared, peak_f, peak_b,
                         new_tokens=new_tokens, kv="dense")
            pq = _leg(cfg, prepared, peak_f, peak_b,
                      new_tokens=new_tokens, kv="paged", kv_dtype="int8")
            row["dense_mbu"] = round(dense["mbu"], 4)
            row["paged_int8_mbu"] = round(pq["mbu"], 4)
            row["paged_int8_tokens_per_sec"] = pq["tokens_per_sec"]
        # the floor gates only the substrate it was calibrated on (see
        # module docstring); a TPU row reports honestly without gating
        gated = src != "table"
        row["floor"] = MBU_FLOOR if gated else None
        row["ok"] = bool(s10["mbu"] >= MBU_FLOOR) if gated else True
        return row
    finally:
        obs.set_enabled(was)


def main(argv=None) -> int:
    args = set(argv if argv is not None else sys.argv[1:])
    row = measure()
    print(json.dumps(row), flush=True)
    if "--assert" in args and not row["ok"]:
        print(f"FAIL: decode MBU {row['mbu'] * 100:.2f}% < "
              f"{MBU_FLOOR * 100:.0f}% floor (§10-config leg)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
