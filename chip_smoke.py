#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that dnn_tpu still starts on the chip.

    python chip_smoke.py [--seed N]            # one chip: the serving daemon
    python chip_smoke.py --chips 4 [--seed N]  # four chips: the staged pipeline

One chip (what the driver runs): GPT-2 at its published widths (12L, d=768,
12 heads, vocab 50257, ctx 1024), bf16 compute, random weights from
`spec.init(PRNGKey(seed))`, served by the real daemon — `python -m
dnn_tpu.node --serve_lm --slots 4` with every other serving flag at its
default (paged pool, max_len 1024, attn_kernel="auto"). A client sends
concurrent greedy requests of different prompt lengths (one past 512
tokens, so the chunked prefill runs many times), reads /statusz after three
watchdog periods, and SIGTERMs the daemon, which must drain with rc=0.
Then the served tokens are teacher-forced through the plain un-cached f32
forward on the same weights: each must sit within MARGIN_BOUND of that
position's maximum logit (exact token equality across two programs is not
a sound test under bf16; this is). The attention kernels are run once
against their jnp references on random inputs, and each of the daemon's
three step programs is rebuilt by the daemon's own constructor call and its
compiled text read for `tpu_custom_call`: Pallas kernel or XLA must agree
with the attn_kernel="auto" policy for the served shape.

Four chips (`--chips 4`, run by the builder): only the staged pipeline and
what it is compared with — GPT-2 as four stages on a `runtime: "spmd"`
engine, logits of an (8, 512) batch against the un-partitioned forward on
one device, proof that the weights are spread over the four devices, then
`node --generate 16` through the pipeline's KV-cache decode.

WHO HOLDS THE CHIP. A chip belongs to one process at a time. With one chip
this script initializes NO JAX backend while the daemon child lives
(importing dnn_tpu imports jax, which is fine; initializing is not, and
`_assert_off_device` enforces it); it takes the chip only after the child
has exited, for the reference check, and the device on the last line is
read then — by a process that really holds it. With `--chips 4` everything
runs in this one process.

Every earlier line of stdout is one JSON object worth keeping (device,
compile cache, program kinds, set-up times, margins). The last line is
exactly {"ok": true, "device": {"platform", "kind", "count"}}. Any phase
that fails raises: there is no path to the last line around a failure, and
no accelerator means a non-zero exit with no "ok" line. Times printed here
are set-up observations, not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

MODEL = "gpt2"
DTYPE = "bfloat16"
SLOTS = 4
WATCHDOG_S = 10.0
PROMPT_LENS = (5, 37, 130, 300, 600)  # one >= 512: many prefill chunks
MAX_NEW = 24
# A served token's f32 reference logit may trail the reference maximum by
# at most this much. At these widths random-init logits are near-tied (top
# two ~0.1 apart at a sigma of ~0.55), so bf16 rounding through 12 layers
# (~1e-2 on a logit; worst margin seen on the chip 0.013) does move the
# argmax; a token picked at random sits ~4 sigma (~2 logit units) below
# the maximum, and only a handful of 50257 sit within 0.1 of it.
MARGIN_BOUND = 0.1
# Pallas kernel vs its jnp reference (f32 math at "highest" precision) on
# unit-variance bf16 inputs: 2e-3 seen on the chip; a wrong block or mask
# is an error of order 1.
KERNEL_TOL = 2e-2
# Four-stage pipeline (8 microbatches) vs the un-partitioned forward, both
# bf16 compute, max abs difference over all 8x512x50257 logits: 0.027 seen
# on the chip at a logit sigma of ~0.55; a misplaced stage is of order 1.
PIPELINE_TOL = 0.1
PIPELINE_BATCH, PIPELINE_SEQ, PIPELINE_NEW = 8, 512, 16


def emit(**row):
    print(json.dumps(row), flush=True)


def _assert_off_device(where: str):
    """The ownership rule: no JAX backend in this process yet."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"{where}: this process has initialized a JAX backend while "
            "the daemon child needs the chip")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cache_entries(path: str) -> int:
    try:
        return sum(n.endswith("-cache") for n in os.listdir(path))
    except FileNotFoundError:
        return 0


def _cache_counts():
    """(cache directory, entries in it, entries in the in-checkout default
    where JAX_COMPILATION_CACHE_DIR placed the cache somewhere else)."""
    from dnn_tpu.utils.compile_cache import compile_cache_dir

    cache_dir = compile_cache_dir()
    default = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".jax_cache")
    outside = 0 if cache_dir == default else _cache_entries(default)
    return cache_dir, _cache_entries(cache_dir), outside


def _emit_cache_after(outside_before: int):
    cache_dir, entries, outside = _cache_counts()
    emit(phase="cache", compile_cache_dir=cache_dir,
         cache_entries_after=entries,
         written_outside_it=outside - outside_before)
    if outside != outside_before:
        raise RuntimeError("compile-cache entries were written outside "
                           f"{cache_dir}")


def _write_config(workdir: str, *, model: str, device_type, dtype: str,
                  stages: int = 1, runtime: str = "auto", port=None) -> str:
    cfg = {
        "nodes": [{"id": f"node{i + 1}", "part_index": i,
                   "address": f"127.0.0.1:{port or 0}"}
                  for i in range(stages)],
        "num_parts": stages, "model": model, "dtype": dtype,
        "runtime": runtime,
    }
    if device_type is not None:
        cfg["device_type"] = device_type
    path = os.path.join(workdir, "config.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    return path


def serve_argv(config_path: str, *, seed: int, slots: int = SLOTS):
    """The daemon's command line after `python -m dnn_tpu.node` — every
    serving flag but --slots at its default."""
    return ["--node_id", "node1", "--config", config_path, "--serve_lm",
            "--slots", str(slots), "--seed", str(seed),
            "--log_level", "WARNING"]


def make_prompts(seed: int, vocab: int, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lens]


# ----------------------------------------------------------------------
# phase 1 — the daemon, driven from a process that stays off the device
# ----------------------------------------------------------------------

def serve_phase(workdir, *, model=MODEL, device_type="tpu", dtype=DTYPE,
                seed=0, prompt_lens=PROMPT_LENS, max_new=MAX_NEW, vocab=50257,
                watchdog_s=WATCHDOG_S, ready_deadline_s=600.0):
    """Spawn `node --serve_lm`, send concurrent greedy requests, read
    /statusz after three watchdog periods under traffic, SIGTERM, require
    rc=0. Returns prompts, tokens and set-up times. Runs with no JAX
    backend in this process."""
    from dnn_tpu.comm.client import NodeClient

    _assert_off_device("before spawning the daemon")
    port, mport = _free_port(), _free_port()
    config_path = _write_config(workdir, model=model, device_type=device_type,
                                dtype=dtype, port=port)
    argv = serve_argv(config_path, seed=seed) + [
        "--metrics_port", str(mport), "--watchdog_s", str(watchdog_s)]
    log_path = os.path.join(workdir, "daemon.log")
    addr = f"127.0.0.1:{port}"
    prompts = make_prompts(seed, vocab, prompt_lens)
    t_spawn = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dnn_tpu.node"] + argv,
            stdout=log, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    try:
        probe = NodeClient(addr, breaker=False)
        while not probe.health_check(timeout=2.0):
            if proc.poll() is not None:
                raise RuntimeError(
                    f"daemon exited rc={proc.returncode} before it was "
                    f"ready:\n{_tail(log_path)}")
            if time.monotonic() - t_spawn > ready_deadline_s:
                raise RuntimeError(
                    f"daemon not ready after {ready_deadline_s:.0f}s:\n"
                    f"{_tail(log_path)}")
            time.sleep(0.5)
        t_ready = time.monotonic()
        # first request, streamed: pays the daemon's three compiles
        first = probe.generate_stream(prompts[0], max_new_tokens=4,
                                      timeout=ready_deadline_s)
        next(first)
        t_first = time.monotonic()
        for _ in first:  # let the request finish and free its slot
            pass
        probe.close()

        results: list = [None] * len(prompts)
        errors: list = []

        def one(i):
            try:
                c = NodeClient(addr, breaker=False)
                results[i] = c.generate(prompts[i], max_new_tokens=max_new,
                                        timeout=ready_deadline_s)
                c.close()
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"generate failed: {errors[0]!r}\n"
                               f"{_tail(log_path)}")
        # three probe periods under traffic before /statusz is read
        keep = NodeClient(addr, breaker=False)
        while time.monotonic() - t_ready < 3.5 * watchdog_s:
            keep.generate(prompts[0], max_new_tokens=4)
        keep.close()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/statusz", timeout=10) as r:
            statusz = json.load(r)
        _assert_off_device("while the daemon was serving")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    if rc != 0:
        raise RuntimeError(f"daemon drained with rc={rc}, want 0:\n"
                           f"{_tail(log_path)}")
    comps = statusz.get("components", {})
    if statusz.get("state") != "ok" or any(
            comps.get(k, {}).get("state") != "ok"
            for k in ("device", "decode_heartbeat")):
        raise RuntimeError(f"/statusz after 3 probe periods: {statusz}")
    for toks in results:
        if len(toks) != max_new or toks.min() < 0 or toks.max() >= vocab:
            raise RuntimeError(f"bad tokens from the daemon: {toks}")
    return {
        "config_path": config_path, "prompts": prompts,
        "tokens": results, "drain_rc": rc,
        "statusz": {"state": statusz["state"],
                    "device": comps["device"]["detail"]},
        "spawn_to_ready_s": round(t_ready - t_spawn, 2),
        "spawn_to_first_token_s": round(t_first - t_spawn, 2),
    }


def _tail(path: str, n: int = 4000) -> str:
    with open(path, errors="replace") as f:
        return f.read()[-n:]


# ----------------------------------------------------------------------
# phase 2 — this process takes the chip: reference checks
# ----------------------------------------------------------------------

def take_device(need: int = 1):
    """First backend use of this process; no accelerator is an error."""
    import jax

    from dnn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        raise RuntimeError(
            f"need {need} TPU device(s); JAX found {len(devs)} x "
            f"{devs[0].platform} ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def teacher_forced_margins(cfg, params, prompts, tokens):
    """Worst (reference max logit - reference logit of the served token)
    over every served position, through the plain un-cached f32 forward
    (`gpt.make_apply`) at "highest" matmul precision. Also the mean logit
    sigma, which is what the margin is to be read against."""
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt

    seqs = [np.concatenate([p, t]) for p, t in zip(prompts, tokens)]
    width = min(-(-max(len(s) for s in seqs) // 128) * 128, cfg.block_size)
    ids = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    apply = gpt.make_apply(cfg)

    @jax.jit
    def margins(params, ids):
        logits = apply(params, ids)[:, :-1]
        chosen = jnp.take_along_axis(
            logits, ids[:, 1:, None], axis=-1)[..., 0]
        return logits.max(-1) - chosen, logits.std(-1)

    with jax.default_matmul_precision("highest"):
        margin, sigma = (np.asarray(x) for x in margins(params, ids))
    worst, sig = 0.0, []
    for i, (p, t) in enumerate(zip(prompts, tokens)):
        served = slice(len(p) - 1, len(p) + len(t) - 1)
        worst = max(worst, float(margin[i, served].max()))
        sig.append(float(sigma[i, served].mean()))
    if not np.isfinite(worst):
        raise RuntimeError("reference margins are not finite")
    return worst, float(np.mean(sig))


def kernel_parity(cfg, *, slots=SLOTS, block_len=16, max_len=1024,
                  prompt_pad=64, seed=0):
    """The two attention kernels of the serving path at the served shapes,
    each against its jnp reference on random bf16 inputs: the paged
    decode kernel over a shuffled block table (the pool's rows stored
    128 lanes wide, as the daemon's pool stores them), and the
    chunked-prefill kernel at a runtime start position. Returns max abs
    differences."""
    import jax
    import jax.numpy as jnp

    from dnn_tpu.ops.pallas import cached_attention as ca
    from dnn_tpu.runtime.paged_kvcache import lane_padded

    h, d = cfg.n_head, cfg.n_embd // cfg.n_head
    nb = max_len // block_len
    n_blocks = slots * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (slots, h, 1, d), bf)
    lanes = [(0, 0)] * 3 + [(0, lane_padded(d) - d)]
    kp = jnp.pad(jax.random.normal(ks[1], (n_blocks, h, block_len, d), bf),
                 lanes)
    vp = jnp.pad(jax.random.normal(ks[2], (n_blocks, h, block_len, d), bf),
                 lanes)
    tables = (jax.random.permutation(ks[3], n_blocks - 1)[: slots * nb] + 1
              ).reshape(slots, nb).astype(jnp.int32)
    pos = jnp.asarray([3, block_len, max_len // 2 + 5, max_len - 1][:slots],
                      jnp.int32)
    qc = jax.random.normal(ks[4], (1, h, prompt_pad, d), bf)
    kc = jax.random.normal(ks[5], (1, h, max_len, d), bf)
    start = jnp.asarray([max_len // 2], jnp.int32)
    got_p = jax.jit(ca.paged_decode_attention)(q, kp, vp, tables, pos)
    got_c = jax.jit(ca.cached_attention)(qc, kc, kc, start)
    with jax.default_matmul_precision("highest"):
        ref_p = jax.jit(ca.reference_paged_decode_attention)(
            q, kp, vp, tables, pos)
        ref_c = jax.jit(ca.reference_cached_attention)(qc, kc, kc, start)
    return {"paged_decode": float(jnp.abs(got_p - ref_p).max()),
            "chunked_prefill": float(jnp.abs(got_c - ref_c).max())}


def serving_program_kinds(argv):
    """Pallas kernel or XLA, per step program of the daemon.

    The batcher is built by the SAME constructor call the daemon makes:
    `node.main(argv)` runs in this process with `serve_lm` replaced by a
    function that constructs the `LMServer` from the arguments
    `_serve_lm` hands it, and keeps its batcher instead of serving. One
    short request is then driven through that batcher with each program
    lowered and compiled from its real arguments just before its first
    call. Returns {program: {"pallas": bool, "policy_says_pallas": bool}}.
    """
    import jax

    import dnn_tpu.runtime.lm_server as lms
    from dnn_tpu import node
    from dnn_tpu.runtime.kvcache import AUTO_KERNEL_MIN_S

    kept = {}

    async def keep_batcher(cfg, prepared, *, port, **server_kwargs):
        srv = lms.LMServer(cfg, prepared, **server_kwargs)
        srv.close()  # stop its worker: this thread drives the batcher
        kept["batcher"] = srv.batcher
        return 0

    real, lms.serve_lm = lms.serve_lm, keep_batcher
    try:
        rc = node.main(argv)
    finally:
        lms.serve_lm = real
    if rc != 0 or "batcher" not in kept:
        raise RuntimeError(f"node.main{argv} did not reach serve_lm "
                           f"(rc={rc})")
    b = kept["batcher"]
    texts = {}

    def lowered_first(name):
        fn = getattr(b, name)

        def call(*args):
            if name not in texts:
                texts[name] = fn.lower(*args).compile().as_text()
            return fn(*args)

        setattr(b, name, call)

    for name in ("_prefill_chunk", "_prefill_finish", "_decode"):
        lowered_first(name)
    b.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    b.drain()

    # the attn_kernel="auto" policy for the served shape (kvcache.py /
    # paged_kvcache.py _kernel_on, cached_attention's tiling guard)
    auto_on = (jax.default_backend() == "tpu"
               and getattr(b.family, "attn_kernel", False) == "auto")
    policy = {
        "_prefill_chunk": (auto_on and b._row_len >= AUTO_KERNEL_MIN_S
                           and b._row_len % 128 == 0),
        "_prefill_finish": False,  # samples and installs; no attention
        "_decode": auto_on and b.max_len >= AUTO_KERNEL_MIN_S,
    }
    kinds = {}
    for name, text in texts.items():
        kinds[name.lstrip("_")] = {
            "pallas": "tpu_custom_call" in text,
            "policy_says_pallas": bool(policy[name])}
    bad = {k: v for k, v in kinds.items()
           if v["pallas"] != v["policy_says_pallas"]}
    if len(kinds) != 3 or bad:
        raise RuntimeError(f"compiled programs disagree with the "
                           f"attn_kernel policy: {bad or kinds}")
    return kinds, {"paged": b._paged, "max_len": b.max_len,
                   "block_len": getattr(b, "_block_len", None),
                   "prompt_pad": b.prompt_pad, "slots": b.slots}


def run_one_chip(seed: int, workdir: str):
    from dnn_tpu.native import native_available

    cache_dir, entries, outside = _cache_counts()
    emit(phase="setup", compile_cache_dir=cache_dir,
         cache_entries_before=entries, native_codec=native_available())
    served = serve_phase(workdir, seed=seed)
    emit(phase="serve", model=MODEL, dtype=DTYPE, slots=SLOTS,
         requests=len(served["tokens"]),
         prompt_lens=[len(p) for p in served["prompts"]],
         tokens_returned=int(sum(len(t) for t in served["tokens"])),
         spawn_to_ready_s=served["spawn_to_ready_s"],
         spawn_to_first_token_s=served["spawn_to_first_token_s"],
         drain_rc=served["drain_rc"], statusz=served["statusz"])

    # the daemon has exited: this process takes the chip
    device = take_device(1)
    import jax

    from dnn_tpu.registry import get_model
    from dnn_tpu.utils import flops

    emit(phase="device", **device,
         peak_bf16_flops=flops.device_peak_flops(),
         peak_hbm_bytes_per_s=flops.device_peak_hbm_bw())
    spec = get_model(MODEL)
    params = spec.init(jax.random.PRNGKey(seed))
    worst, sigma = teacher_forced_margins(
        spec.config, params, served["prompts"], served["tokens"])
    emit(phase="teacher_forced", worst_margin=worst, bound=MARGIN_BOUND,
         mean_logit_sigma=sigma)
    if worst > MARGIN_BOUND:
        raise RuntimeError(f"served token {worst:.4f} below the reference "
                           f"maximum, bound {MARGIN_BOUND}")
    del params
    diffs = kernel_parity(spec.config, seed=seed)
    emit(phase="kernel_parity", max_abs_diff=diffs, tol=KERNEL_TOL)
    if max(diffs.values()) > KERNEL_TOL:
        raise RuntimeError(f"Pallas kernel vs reference: {diffs}")
    kinds, shape = serving_program_kinds(
        serve_argv(served["config_path"], seed=seed))
    emit(phase="programs", served_shape=shape, **kinds)
    _emit_cache_after(outside)
    return device


# ----------------------------------------------------------------------
# --chips 4 — the staged pipeline, one process on four devices
# ----------------------------------------------------------------------

def pipeline_phase(workdir, *, model=MODEL, device_type="tpu", dtype=DTYPE,
                   seed=0, stages=4, batch=PIPELINE_BATCH, seq=PIPELINE_SEQ,
                   n_new=PIPELINE_NEW, tol=PIPELINE_TOL):
    """GPT as `stages` pipeline stages on an explicit spmd engine: where
    the weights live, logits against the un-partitioned forward on one
    device, then `node --generate` through the pipeline's KV-cache
    decode, teacher-forced like the served tokens."""
    import gc

    import jax
    import jax.numpy as jnp

    from dnn_tpu import node
    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.engine import PipelineEngine

    config_path = _write_config(workdir, model=model, device_type=device_type,
                                dtype=dtype, stages=stages, runtime="spmd")
    engine = PipelineEngine(TopologyConfig.from_json(config_path),
                            role="full", rng_seed=seed)
    if engine.runtime != "spmd" or len(engine.mesh.devices.flat) != stages:
        raise RuntimeError(f"engine runtime={engine.runtime}, want spmd "
                           f"over {stages} devices")
    cfg = engine.spec.config
    devs = list(engine.mesh.devices.flat)

    # where the model lives, before anything else is put on the devices
    gc.collect()
    stage_blocks, _ = engine._gen_parts
    for leaf in jax.tree.leaves(stage_blocks):
        where = {s.index[0].start: s.device for s in leaf.addressable_shards}
        if leaf.shape[0] != stages or where != dict(enumerate(devs)):
            raise RuntimeError(f"stage weights not one stage per device: "
                               f"{leaf.shape} on {where}")
    model_bytes = sum(l.nbytes for l in jax.tree.leaves(engine.params))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    spread = {"model_bytes": int(model_bytes), "bytes_in_use": in_use,
              "blocks_per_stage": cfg.n_layer // stages}
    if in_use[0] is not None:  # the CPU rehearsal has no memory_stats
        if not all(in_use) or in_use[0] >= model_bytes:
            raise RuntimeError(f"model is not spread over the devices: "
                               f"{spread}")

    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    got = engine.run(ids)
    ref_params = jax.device_put(engine.params, devs[0])
    ref = jax.jit(gpt.make_apply(cfg, compute_dtype=engine.compute_dtype))(
        ref_params, jax.device_put(ids, devs[0]))
    if got.shape != (batch, seq, cfg.vocab_size):
        raise RuntimeError(f"pipeline logits have shape {got.shape}")
    diff = float(jnp.abs(jax.device_put(got, devs[0]) - ref).max())
    if not diff <= tol:  # also catches NaN
        raise RuntimeError(f"pipeline vs un-partitioned forward: max abs "
                           f"logit difference {diff}, tolerance {tol}")
    del got, ref

    # `node --generate N` through the pipeline-parallel KV-cache decode
    prompt = make_prompts(seed, cfg.vocab_size, (12,))[0]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = node.main([
            "--node_id", "node1", "--config", config_path,
            "--generate", str(n_new), "--seed", str(seed),
            "--prompt_ids", ",".join(map(str, prompt)),
            "--log_level", "WARNING"])
    m = re.search(r"GENERATED TOKENS: ([\d,]+)", out.getvalue())
    if rc != 0 or m is None:
        raise RuntimeError(f"node --generate rc={rc}: {out.getvalue()!r}")
    toks = np.asarray([int(t) for t in m.group(1).split(",")], np.int32)
    if len(toks) != n_new:
        raise RuntimeError(f"node --generate returned {len(toks)} tokens")
    worst, sigma = teacher_forced_margins(cfg, ref_params, [prompt], [toks])
    if worst > MARGIN_BOUND:
        raise RuntimeError(f"pipeline-decoded token {worst:.4f} below the "
                           f"reference maximum, bound {MARGIN_BOUND}")
    return {"spread": spread, "logits_max_abs_diff": diff, "tol": tol,
            "microbatches": engine._effective_microbatches(batch),
            "generated": len(toks), "worst_margin": worst,
            "bound": MARGIN_BOUND, "mean_logit_sigma": sigma}


def run_four_chips(seed: int, workdir: str):
    device = take_device(4)
    cache_dir, entries, outside = _cache_counts()
    emit(phase="device", **device, compile_cache_dir=cache_dir,
         cache_entries_before=entries)
    emit(phase="pipeline", model=MODEL, dtype=DTYPE,
         batch=[PIPELINE_BATCH, PIPELINE_SEQ],
         **pipeline_phase(workdir, seed=seed))
    _emit_cache_after(outside)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and sampling all derive from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the staged pipeline on four chips")
    args = ap.parse_args(argv)
    run = run_four_chips if args.chips == 4 else run_one_chip
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        device = run(args.seed, workdir)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
