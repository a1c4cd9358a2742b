"""Full benchmark suite: measures every config in BASELINE.md.

The reference publishes no numbers (SURVEY §6), so this suite produces the
framework's own measured table — one JSON line per config plus a markdown
table written to benchmarks/RESULTS.md.

Two sections:

  * device:  whatever `jax.devices()` resolves to (the TPU chip where
    one is attached; CPU elsewhere) — single-chip model throughput. EACH
    device config runs in its OWN subprocess with its own timeout, one
    at a time (a chip belongs to one process; this parent never touches
    JAX): one config that hangs or dies must cost exactly that config,
    never the tail (VERDICT r4 weak #2 — sectioned retry lost the same
    tail twice, deterministically).
    Each config's rows persist to benchmarks/.bench_rows.jsonl the
    moment the config finishes (ok OR failed-with-salvage); `--resume`
    skips configs that completed ok and RETRIES failed ones.
  * cpu-mesh: 8 virtual CPU devices — the multi-stage pipeline forms and
    p50 inter-stage hop latency. These validate the parallel machinery;
    their absolute numbers are CPU numbers and are labeled as such. The
    <2 ms hop target is a v5e-8 ICI claim the single-chip environment
    cannot measure (BASELINE.md "north star"). This section cannot wedge
    (no chip involved), so it keeps the coarser one-subprocess salvage.

Usage:
    python benchmarks/run_all.py                   # both sections + RESULTS.md
    python benchmarks/run_all.py --resume          # skip completed configs
    python benchmarks/run_all.py --section device --config gpt2_fwd  # one
    python benchmarks/run_all.py --section cpu_mesh
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # script lives in benchmarks/; import dnn_tpu from root
    sys.path.insert(0, REPO)

STATE_PATH = os.path.join(REPO, "benchmarks", ".bench_rows.jsonl")


def _emit(results, **row):
    # provenance (ISSUE 8/12): every row that knows its platform also
    # carries the contract-named `round_substrate` alias bench.py rows
    # use, so `--require-substrate`-style trajectory filters read one
    # key across both artifacts
    if "platform" in row and "round_substrate" not in row:
        row["round_substrate"] = row["platform"]
    results.append(row)
    print(json.dumps(row), flush=True)


# ----------------------------------------------------------------------
# section: device (single chip / default platform) — one config per
# subprocess; each function stands alone and re-creates what it needs
# ----------------------------------------------------------------------

DEVICE_CONFIGS = []  # [(name, fn, tpu_only)] in table order


def device_config(name, tpu_only=False):
    def deco(fn):
        DEVICE_CONFIGS.append((name, fn, tpu_only))
        return fn
    return deco


def _platform():
    import jax

    return jax.default_backend()


def _with_mfu(row, flops_per_item, items_per_sec):
    from dnn_tpu.utils.flops import mfu

    m = mfu(flops_per_item, items_per_sec)
    if m is not None:
        row["mfu"] = round(m, 4)
    return row


@device_config("cifar_cnn_fwd")
def dev_cifar_fwd():
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import cifar
    from dnn_tpu.registry import get_model
    from dnn_tpu.utils.flops import (
        cifar_forward_bytes, cifar_forward_flops, mfu,
        roofline_items_per_sec,
    )
    from dnn_tpu.utils.timing import device_time

    results = []
    # config 1 (full-model form): CIFAR CNN forward — bf16 operands like
    # the GPT rows, so the mfu column divides a bf16-executed workload by
    # the bf16 peak table
    spec = get_model("cifar_cnn")
    params = spec.init(jax.random.PRNGKey(0))
    # B=1024: below ~1024 images a forward is so short (<0.2 ms) that the
    # host's dispatch floor dominates and the row measures host
    # overhead, not the chip (benchmarks/cifar_mfu_probe.py batch sweep)
    batch = 1024
    x = jnp.asarray(spec.example_input(batch_size=batch))
    fn = jax.jit(cifar.make_apply(compute_dtype=jnp.bfloat16))
    # sub-ms per batch: needs many reps per sample or the slope drowns in
    # sync jitter
    dt = device_time(fn, params, x, n1=100, n2=400, trials=5)
    ips = batch / dt
    row = _with_mfu({}, cifar_forward_flops(1), ips)
    # arithmetic intensity (~60 FLOPs/byte) is far below the TPU ridge
    # point, so the MFU ceiling is the ROOFLINE cap, not 100% — report
    # both (dnn_tpu/utils/flops.cifar_forward_bytes has the accounting)
    cap = roofline_items_per_sec(
        cifar_forward_flops(1), cifar_forward_bytes(batch) / batch)
    if cap is not None:
        row["mfu_roofline_cap"] = round(mfu(cifar_forward_flops(1), cap), 4)
        row["roofline_frac"] = round(ips / cap, 4)
    _emit(results, config="cifar_cnn_fwd", metric="images_per_sec",
          value=round(ips, 1), platform=_platform(), batch=batch,
          dtype="bf16", **row)
    return results


@device_config("gpt_fwd")
def dev_gpt_fwd():
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.utils.flops import gpt_forward_flops
    from dnn_tpu.utils.timing import device_time

    results = []
    # config 4/5 (full-model form): GPT-2 small + medium forward, bf16
    # operands + bf16 logit store (the serving configuration — gpt.head)
    for preset, b, s in (("gpt2", 8, 512), ("gpt2-medium", 4, 512)):
        cfg = gpt.PRESETS[preset]
        p = gpt.init(jax.random.PRNGKey(0), cfg)
        prepared = gpt.prepare_stacked(p, cfg)
        fn = jax.jit(gpt.make_apply_stacked(
            cfg, compute_dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16))
        ids = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
        dt = device_time(fn, prepared, ids)
        tps = b * s / dt
        _emit(results, config=f"{preset}_fwd", metric="tokens_per_sec",
              value=round(tps, 1), platform=_platform(), batch=b, seq=s,
              logits="bf16",
              **_with_mfu({}, gpt_forward_flops(cfg, b, s) / (b * s), tps))
    return results


@device_config("tinyllama_fwd", tpu_only=True)
def dev_tinyllama_fwd():
    # TPU-only: a 1.1B bf16 forward on a CPU host would blow the budget
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt, llama
    from dnn_tpu.utils.flops import llama_forward_flops
    from dnn_tpu.utils.timing import device_time

    results = []
    ll_cfg = llama.PRESETS["tinyllama-1.1b"]
    ll_prep = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(0), ll_cfg, dtype=jnp.bfloat16),
        ll_cfg)
    ll_fn = jax.jit(llama.make_apply_stacked(
        ll_cfg, compute_dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16))
    b, s = 8, 512
    ll_ids = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                ll_cfg.vocab_size, dtype=jnp.int32)
    dt = device_time(ll_fn, ll_prep, ll_ids, n1=1, n2=3)
    tps = b * s / dt
    _emit(results, config="tinyllama_fwd", metric="tokens_per_sec",
          value=round(tps, 1), platform=_platform(), batch=b, seq=s,
          logits="bf16",
          **_with_mfu({}, llama_forward_flops(ll_cfg, b, s) / (b * s), tps))
    return results


@device_config("tinyllama_decode", tpu_only=True)
def dev_tinyllama_decode():
    # TinyLlama decode matrix — the GQA bandwidth claim, measured. The
    # cache is stored at KV-head width (llama.init_cache): KV*D = 256
    # floats/position/layer vs model width 2048, so at equal batch/seq
    # TinyLlama streams 8x fewer cache bytes per step than an MHA model
    # of its width. Rows mirror the GPT-2 matrix (same batch/new_tokens)
    # so bytes/token and MBU are directly comparable across families.
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt, llama
    from dnn_tpu.quant import param_bytes, quantize_tree
    from dnn_tpu.utils.flops import mbu
    from dnn_tpu.utils.timing import device_time

    results = []
    ll_cfg = llama.PRESETS["tinyllama-1.1b"]
    ll_prep = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(0), ll_cfg, dtype=jnp.bfloat16),
        ll_cfg)
    db, dprompt, dnew = 8, 16, 128
    d_ids = jax.random.randint(jax.random.PRNGKey(4), (db, dprompt), 0,
                               ll_cfg.vocab_size, dtype=jnp.int32)
    d_smax = dprompt + dnew
    ll_cache_elems = (2 * ll_cfg.n_layer * db
                      * ll_cfg.n_kv_head * ll_cfg.head_dim * d_smax)
    ll_q = quantize_tree(ll_prep)
    rng_d = jax.random.PRNGKey(5)
    for name, weights, kvd, itemsize in (
            ("w_bf16_kv_bf16", ll_prep, jnp.bfloat16, 2),
            ("w_int8_kv_int8", ll_q, "int8", 1)):
        gfn = llama.make_generate(
            ll_cfg, max_new_tokens=dnew, compute_dtype=jnp.bfloat16,
            kv_dtype=kvd)
        dt = device_time(gfn, weights, d_ids, rng_d, n1=1, n2=3)
        tps = db * dnew / dt
        # int8 cache rides per-(position, kv-head) f32 scales for K and
        # V: cache_elems / head_dim scale entries x 4 bytes
        bpt = (param_bytes(weights) + ll_cache_elems * itemsize
               + (ll_cache_elems // ll_cfg.head_dim * 4
                  if kvd == "int8" else 0)) / db
        row = {"bytes_per_token_mb": round(bpt / 1e6, 2)}
        u = mbu(bpt, tps)
        if u is not None:
            row["mbu"] = round(u, 4)
        _emit(results, config=f"tinyllama_decode_{name}",
              metric="tokens_per_sec", value=round(tps, 1),
              platform=_platform(), batch=db, new_tokens=dnew, **row)
    return results


@device_config("llama_longctx_decode", tpu_only=True)
def dev_llama_longctx_decode():
    # Sliding-window ring decode (models/llama.py rolling path) vs dense
    # long-context decode — the Mistral-class long-context claim,
    # measured as a mechanism bench: at s_max = 3x the window the ring
    # streams W cache positions per step while the dense cache streams
    # s_max. GQA caches are small next to the weights, so the comparison
    # runs an MHA-width variant (n_kv_head = n_head) of the TinyLlama
    # shape where the cache is ~half the decode traffic — random-init
    # throughput probe, labeled as such.
    #
    # The dense leg runs BOTH attention paths: the XLA einsum and the
    # Pallas streaming decode kernel (ops/pallas/cached_attention
    # decode_attention) — the round-4 table showed the einsum path at 13%
    # MBU here (VERDICT r5 ask #3); the kernel leg measures whether
    # streaming the cache in few-big-DMA form closes the gap.
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt, llama
    from dnn_tpu.quant import param_bytes
    from dnn_tpu.utils.flops import mbu
    from dnn_tpu.utils.timing import device_time

    results = []
    ll_cfg = llama.PRESETS["tinyllama-1.1b"]
    swb, swprompt, swnew, sww = 8, 1024, 512, 512
    sw_smax = swprompt + swnew
    mha_cfg = _dc.replace(ll_cfg, n_kv_head=ll_cfg.n_head, block_size=2048)
    sw_prep = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(7), mha_cfg, dtype=jnp.bfloat16),
        mha_cfg)
    sw_ids = jax.random.randint(jax.random.PRNGKey(8), (swb, swprompt),
                                0, mha_cfg.vocab_size, dtype=jnp.int32)
    rng_d = jax.random.PRNGKey(5)
    for name, cfg_v, cache_pos, kernel in (
            ("dense", mha_cfg, sw_smax, False),
            ("dense_kernel", mha_cfg, sw_smax, True),
            ("ring", _dc.replace(mha_cfg, sliding_window=sww), sww, False)):
        gfn = llama.make_generate(
            cfg_v, max_new_tokens=swnew, compute_dtype=jnp.bfloat16,
            kv_dtype=jnp.bfloat16, attn_kernel=kernel)
        # the 1024-token prefill would dilute a whole-call rate (~10% of
        # the call): subtract a max_new=1 run so tps counts DECODE steps
        # against decode time
        gfn1 = llama.make_generate(
            cfg_v, max_new_tokens=1, compute_dtype=jnp.bfloat16,
            kv_dtype=jnp.bfloat16, attn_kernel=kernel)
        dt_full = device_time(gfn, sw_prep, sw_ids, rng_d, n1=1, n2=2)
        dt_pre = device_time(gfn1, sw_prep, sw_ids, rng_d, n1=1, n2=2)
        dt = max(dt_full - dt_pre, 1e-9)
        tps = swb * (swnew - 1) / dt
        cache_bytes = (2 * cfg_v.n_layer * swb * cfg_v.n_kv_head
                       * cfg_v.head_dim * cache_pos) * 2
        bpt = (param_bytes(sw_prep) + cache_bytes) / swb
        row = {"bytes_per_token_mb": round(bpt / 1e6, 2)}
        u = mbu(bpt, tps)
        if u is not None:
            row["mbu"] = round(u, 4)
        _emit(results, config=f"llama_mha_longctx_decode_{name}",
              metric="tokens_per_sec", value=round(tps, 1),
              platform=_platform(), batch=swb, prompt=swprompt,
              new_tokens=swnew,
              window=(sww if cfg_v.sliding_window else 0), **row)
    return results


@device_config("gpt2_train_step")
def dev_gpt2_train_step():
    # Training step (fwd + bwd + adamw update) — nothing else in the
    # table measures the backward pass. bf16 compute, f32 params/
    # optimizer, the single-chip form of train.make_train_step.
    import jax
    import jax.numpy as jnp
    import optax

    from dnn_tpu.models import gpt
    from dnn_tpu.train import cross_entropy, make_train_step
    from dnn_tpu.utils.flops import gpt_train_step_flops
    from dnn_tpu.utils.timing import device_time

    results = []
    t_cfg = gpt.PRESETS["gpt2"]
    t_prep = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), t_cfg),
                                 t_cfg)
    t_apply = gpt.make_apply_stacked(t_cfg, compute_dtype=jnp.bfloat16)

    def t_loss(p, batch):
        inp, tgt = batch
        return cross_entropy(t_apply(p, inp), tgt)

    t_opt = optax.adamw(1e-4)
    t_state = t_opt.init(t_prep)
    t_step = make_train_step(t_loss, t_opt)
    tb, ts = 8, 512
    t_inp = jax.random.randint(jax.random.PRNGKey(1), (tb, ts), 0,
                               t_cfg.vocab_size, dtype=jnp.int32)
    t_tgt = jax.random.randint(jax.random.PRNGKey(2), (tb, ts), 0,
                               t_cfg.vocab_size, dtype=jnp.int32)

    def t_run(p, s, b):  # time the whole step; updates discarded
        p2, s2, loss = t_step(p, s, b)
        return loss

    dt = device_time(t_run, t_prep, t_state, (t_inp, t_tgt), n1=1, n2=3)
    tps = tb * ts / dt
    _emit(results, config="gpt2_train_step", metric="tokens_per_sec",
          value=round(tps, 1), platform=_platform(), batch=tb, seq=ts,
          optimizer="adamw",
          **_with_mfu({}, gpt_train_step_flops(t_cfg, tb, ts) / (tb * ts),
                      tps))
    return results


@device_config("gpt2_generate_kvcache")
def dev_gpt2_generate_kvcache():
    # KV-cache generation throughput (the serving path the reference
    # lacks)
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    b, prompt_len, new_tokens = 8, 16, 128
    gen_fn = gen.make_generate(
        cfg, max_new_tokens=new_tokens, compute_dtype=jnp.bfloat16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    dt = device_time(gen_fn, prepared, ids, rng, n1=1, n2=3)
    _emit(results, config="gpt2_generate_kvcache", metric="tokens_per_sec",
          value=round(b * new_tokens / dt, 1), platform=_platform(),
          batch=b, new_tokens=new_tokens)
    return results


def _to_bf16(tree):
    import jax.numpy as jnp
    import jax.tree as jtree

    return jtree.map(
        lambda a: a.astype(jnp.bfloat16)
        if hasattr(a, "dtype") and a.dtype == jnp.float32 and a.ndim >= 2
        else a, tree)


@device_config("gpt2_decode_matrix")
def dev_gpt2_decode_matrix():
    # quantized decode matrix: weight-storage x cache-storage. Decode is
    # HBM-bandwidth-bound (every token streams weights + cache once —
    # dnn_tpu/quant.py:1-9), so each row reports bytes/token and MBU
    # alongside tok/s: the speedup should track the byte ratio.
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.quant import param_bytes, quantize_gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.utils.flops import mbu
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    b, prompt_len, new_tokens = 8, 16, 128
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    s_max = prompt_len + new_tokens
    head_dim = cfg.n_embd  # per layer: H * D = C
    cache_elems = 2 * cfg.n_layer * b * head_dim * s_max  # K and V
    q_prepared = quantize_gpt(prepared)
    q4_prepared = quantize_gpt(prepared, bits=4)  # group-wise int4
    bf16_prepared = _to_bf16(prepared)
    variants = (
        # kv dtype must be EXPLICIT f32 for the baseline: with kv=None,
        # make_generate follows compute_dtype (bf16 here) and the "f32
        # cache" row would silently run a bf16 cache
        ("w_f32_kv_f32", prepared, jnp.float32, 4),
        ("w_bf16_kv_bf16", bf16_prepared, jnp.bfloat16, 2),
        ("w_int8_kv_bf16", q_prepared, jnp.bfloat16, 2),
        ("w_int8_kv_int8", q_prepared, "int8", 1),
        # int4 weights (dnn_tpu/quant.py quantize_tensor_int4): halves
        # the weight-byte term again IF the S4 operand read really packs
        # two-per-byte on this chip — this row is the measurement that
        # decides (param_bytes charges 0.5 B/wt; a tok/s that does not
        # beat int8 falsifies the packing assumption, which the docs
        # state as a claim-to-measure, not a fact)
        ("w_int4_kv_int8", q4_prepared, "int8", 1),
    )
    for name, weights, kv, cache_itemsize in variants:
        gfn = gen.make_generate(
            cfg, max_new_tokens=new_tokens, compute_dtype=jnp.bfloat16,
            kv_dtype=kv)
        dt = device_time(gfn, weights, ids, rng, n1=1, n2=3)
        tps = b * new_tokens / dt
        # bytes one token streams: its share of the weights + the full
        # static cache allocation (int8 scales ride along at 1/D per elem)
        bpt = (param_bytes(weights)
               + cache_elems * cache_itemsize
               + (cache_elems // (cfg.n_embd // cfg.n_head)
                  * 4 if kv == "int8" else 0)) / b
        row = {"bytes_per_token_mb": round(bpt / 1e6, 2)}
        u = mbu(bpt, tps)
        if u is not None:
            row["mbu"] = round(u, 4)
        _emit(results, config=f"gpt2_decode_{name}",
              metric="tokens_per_sec", value=round(tps, 1),
              platform=_platform(), batch=b, new_tokens=new_tokens, **row)
    return results


@device_config("gpt2_decode_attnkernel", tpu_only=True)
def dev_gpt2_decode_attnkernel():
    # Pallas cached-attention decode kernel, before/after: same weights,
    # same cache dtype, einsum vs kernel attention. Shapes chosen so the
    # cache tiles the kernel's 128-blocks (prompt 128 + 128 new = S 256).
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.quant import param_bytes, quantize_gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.utils.flops import mbu
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    q_prepared = quantize_gpt(prepared)
    bf16_prepared = _to_bf16(prepared)
    rng = jax.random.PRNGKey(2)
    head_dim = cfg.n_embd
    kb, kprompt, knew = 8, 128, 128
    k_ids = jax.random.randint(jax.random.PRNGKey(3), (kb, kprompt), 0,
                               cfg.vocab_size, dtype=jnp.int32)
    k_smax = kprompt + knew
    k_cache_elems = 2 * cfg.n_layer * kb * head_dim * k_smax
    for name, weights, kv, cache_itemsize in (
            ("w_bf16_kv_bf16", bf16_prepared, jnp.bfloat16, 2),
            ("w_int8_kv_int8", q_prepared, "int8", 1)):
        row = {}
        for mode, ak in (("einsum", False), ("kernel", True)):
            gfn = gen.make_generate(
                cfg, max_new_tokens=knew, compute_dtype=jnp.bfloat16,
                kv_dtype=kv, attn_kernel=ak)
            dt = device_time(gfn, weights, k_ids, rng, n1=1, n2=3)
            row[f"tps_{mode}"] = round(kb * knew / dt, 1)
        bpt = (param_bytes(weights) + k_cache_elems * cache_itemsize
               + (k_cache_elems // (cfg.n_embd // cfg.n_head) * 4
                  if kv == "int8" else 0)) / kb
        u = mbu(bpt, row["tps_kernel"])
        if u is not None:
            row["mbu_kernel"] = round(u, 4)
        _emit(results, config=f"gpt2_decode_attnkernel_{name}",
              metric="kernel_vs_einsum_speedup",
              value=round(row["tps_kernel"] / row["tps_einsum"], 3),
              platform=_platform(), batch=kb, prompt=kprompt,
              new_tokens=knew,
              bytes_per_token_mb=round(bpt / 1e6, 2), **row)
    return results


@device_config("gpt2_decode_top_p_tax")
def dev_gpt2_decode_top_p_tax():
    # top_p decode tax: nucleus sampling rides a static top-k prefilter
    # (generate.TOP_P_PREFILTER_K ranked candidates + an O(V) logsumexp
    # instead of a full-vocab sort per step). Both legs sample at
    # temperature=1.0 so the delta isolates the FILTER's cost.
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    bf16_prepared = _to_bf16(prepared)
    b, prompt_len, new_tokens = 8, 16, 128
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    tps_by_mode = {}
    for mode, tp in (("off", None), ("on", 0.9)):
        gfn = gen.make_generate(
            cfg, max_new_tokens=new_tokens, compute_dtype=jnp.bfloat16,
            kv_dtype=jnp.bfloat16, temperature=1.0, top_p=tp)
        dt = device_time(gfn, bf16_prepared, ids, rng, n1=1, n2=3)
        tps_by_mode[mode] = b * new_tokens / dt
    overhead = tps_by_mode["off"] / tps_by_mode["on"] - 1.0
    _emit(results, config="gpt2_decode_top_p_tax", metric="overhead_pct",
          value=round(overhead * 100, 2), platform=_platform(), batch=b,
          new_tokens=new_tokens,
          tps_top_p_off=round(tps_by_mode["off"], 1),
          tps_top_p_on=round(tps_by_mode["on"], 1),
          note=f"top_p=0.9 via top-{gen.TOP_P_PREFILTER_K} prefilter "
               "(bit-identical to the full-vocab filter when the nucleus "
               "fits inside k)")
    return results


@device_config("obs_overhead")
def dev_obs_overhead():
    # observability tax on the continuous-batching decode step:
    # instrumented (traced requests + per-step metrics) vs the
    # DNN_TPU_OBS=off gate, alternating the gate EVERY step and
    # comparing the two step-time populations' medians
    # (benchmarks/obs_overhead_probe.py documents why coarser A/B
    # designs all produced measurement artifacts on this host). The
    # layer's contract is < 2% (ISSUE 3); `ok` records the verdict.
    from benchmarks.obs_overhead_probe import (
        measure,
        measure_caplens,
        measure_kvlens,
        measure_kvtier,
    )

    results = []
    row = measure()
    overhead = row.pop("overhead_frac")
    # the KV-tier admission leg (ISSUE 15): the radix lookup + its
    # block-granular counters/gauges in the admission path, same
    # contract — all legs must hold or the row is red
    kv = measure_kvtier()
    kv_overhead = kv.pop("kvtier_admit_overhead_frac")
    row.update(kv)
    row["kvtier_admit_overhead_pct"] = round(kv_overhead * 100, 2)
    # the kvlens leg (ISSUE 18): the same admission wall with the
    # reuse-distance tracker LIVE — blake2s chunk digests + SHARDS
    # sampling + LRU-stack bookkeeping in the ON population, one gate
    # check in the OFF population; same contract
    kl = measure_kvlens()
    kl_overhead = kl.pop("kvlens_admit_overhead_frac")
    row.update(kl)
    row["kvlens_admit_overhead_pct"] = round(kl_overhead * 100, 2)
    # the caplens leg (ISSUE 20): the router admission wall with the
    # capacity observatory LIVE — arrival ring + dispersion window +
    # conditioned service reservoir in the ON population; same contract
    cl = measure_caplens()
    cl_overhead = cl.pop("caplens_admit_overhead_frac")
    row.update(cl)
    row["caplens_admit_overhead_pct"] = round(cl_overhead * 100, 2)
    _emit(results, config="obs_overhead", metric="overhead_pct",
          value=round(overhead * 100, 2), platform=_platform(),
          ok=bool(overhead < 0.02 and kv_overhead < 0.02
                  and kl_overhead < 0.02 and cl_overhead < 0.02),
          note="serving decode step, obs on (traced) vs off, per-step "
               "interleave; + kvtier radix-admission leg "
               "(per-admission interleave); + kvlens reuse-distance "
               "leg (tracker live on admission); + caplens router-"
               "admission leg (demand estimator live); contract < 2% "
               "on all",
          **row)
    return results


@device_config("fleet_overhead")
def dev_fleet_overhead():
    # fleet-era observability tax: the obs_overhead loop with the PR-5
    # surface live — per-step goodput (MFU/MBU/SLO window) updates on
    # the pool, and a real FleetCollector polling this process's own
    # /metrics + /statusz + /trace.jsonl endpoint every 200 ms through
    # the timed window. Same <2% decode-step contract.
    from benchmarks.obs_overhead_probe import measure_fleet

    results = []
    row = measure_fleet()
    overhead = row.pop("overhead_frac")
    _emit(results, config="fleet_overhead", metric="overhead_pct",
          value=round(overhead * 100, 2), platform=_platform(),
          ok=bool(overhead < 0.02),
          note="obs_overhead + goodput tracker + in-process fleet "
               "poller @200ms; contract < 2%", **row)
    return results


@device_config("relay_transport")
def dev_relay_transport():
    # ISSUE 7: the pluggable-transport A-B contract on the 2-stage cifar
    # config — real stage-server subprocesses, per-hop latency off the
    # stages' own /metrics summaries and the stitched bubble fraction
    # off the fleet collector's critical-path arithmetic (never ad-hoc
    # timers). Asserted floors: negotiated-auto streamed hop p50 <= 1/5
    # of the nested-grpc hop p50, and the stitched warm bubble <= 1/2 of
    # the nested leg's (STUDIES §10 recorded 75.9% for the baseline).
    from benchmarks.relay_transport_probe import (
        BUBBLE_DROP_FLOOR,
        HOP_RATIO_FLOOR,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    ratio = row.pop("hop_p50_ratio")
    _emit(results, config="relay_transport", metric="hop_p50_ratio",
          value=ratio, ok=ok,
          note=f"negotiated-auto ({row['auto']['negotiated']}+streamed) "
               f"vs nested-grpc per-hop p50; floors: hop ratio >= "
               f"{HOP_RATIO_FLOOR:.0f}x, stitched bubble drop >= "
               f"{BUBBLE_DROP_FLOOR:.0f}x (recorded §10 baseline 75.9%)",
          **row)
    return results


@device_config("decode_mbu")
def dev_decode_mbu():
    # ISSUE 6: live MBU of the decode hot path from the goodput gauges,
    # asserted against an absolute floor on CPU-substrate rooflines —
    # the MBU analog of the obs_overhead <2% contract. The asserted leg
    # is STUDIES §10's exact configuration (dense bucketed f32) so the
    # number is apples-to-apples with the recorded 2.34% baseline; the
    # dense and paged-int8 legs ride along unasserted. A TPU row
    # reports without gating until a healthy chip recalibrates the
    # floor (benchmarks/decode_mbu_probe.py documents the methodology).
    from benchmarks.decode_mbu_probe import MBU_FLOOR, measure

    results = []
    row = measure()
    ok = row.pop("ok")
    mbu = row.pop("mbu")
    _emit(results, config="decode_mbu", metric="mbu_pct",
          value=round(mbu * 100, 2), ok=ok,
          note=f"decode hot path live dnn_tpu_mbu (ISSUE 12: asserted "
               f"leg now runs interleaved prefill + overlap at steady-"
               f"state warm); floor {MBU_FLOOR * 100:.0f}% (ratcheted "
               "5%->10%) on CPU-substrate rooflines (report-only on "
               "TPU table peaks); §10 baseline 2.34%",
          **row)
    return results


# ISSUE 17: the gate's wall-time budget is now a RATCHET, not a note —
# ledger.py reads this ceiling against the analysis_gate row. Measured
# ~22 s CPU with the sharded-program audit live (the four compiled
# sharded programs cost ~6 s of it); the ceiling leaves headroom for
# slower CI hosts, and any future pass that blows it must either pay
# down the gate or raise the number in review, on the record.
ANALYSIS_GATE_WALL_CEIL_S = 60.0


@device_config("analysis_gate")
def dev_analysis_gate():
    # ISSUE 10: the static-analysis CI gate as a run_all row — wall
    # time (ratcheted against ANALYSIS_GATE_WALL_CEIL_S; ~22 s CPU
    # since the ISSUE 17 sharded-program audit joined) plus the
    # finding counts, nonzero subprocess exit (an UNJUSTIFIED finding)
    # recorded as ok=False. Runs the full gate: AST lint (TPU+CON+SHD
    # rules), protocol state-machine pass, jaxpr program pass, and the
    # compiled sharded-program audit (SHD007-009).
    results = []
    t0 = time.perf_counter()
    rc, stdout, stderr = None, "", ""
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "dnn_tpu.analysis", "--json"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=REPO + os.pathsep
                     + os.environ.get("PYTHONPATH", "")),
            timeout=300)
        rc, stdout, stderr = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        # a hung gate must still emit an ok=False row, never lose the
        # config to an uncaught exception
        rc, stderr = -1, f"gate exceeded 300s: {e}"
    wall_s = time.perf_counter() - t0
    counts = {"new": -1, "suppressed": -1, "stale": -1}
    try:
        rep = json.loads(stdout)
        counts = {"new": len(rep.get("new", ())),
                  "suppressed": len(rep.get("suppressed", ())),
                  "stale": len(rep.get("stale_baseline", ()))}
    except (json.JSONDecodeError, ValueError):
        pass  # ok=False below carries the failure; stderr in the note
    _emit(results, config="analysis_gate", metric="gate_wall_s",
          value=round(wall_s, 2), platform=_platform(),
          ok=bool(rc == 0),
          findings_new=counts["new"],
          findings_suppressed=counts["suppressed"],
          baseline_stale=counts["stale"],
          exit_code=rc,
          note="python -m dnn_tpu.analysis (AST lint TPU001-006 + "
               "CON001-006 + SHD001-006, protocol machines PRO001-004, "
               "jaxpr program pass PRG001-004, sharded-program audit "
               "SHD007-009); wall ratcheted <= "
               f"{ANALYSIS_GATE_WALL_CEIL_S:.0f}s (ledger.py); nonzero "
               "exit = unjustified finding"
               + ("" if rc == 0 else f"; stderr: {stderr[-200:]}"))
    return results


@device_config("chaos_resilience")
def dev_chaos_resilience():
    # ISSUE 8: availability + p99 TTFT under the STANDARD FaultPlan
    # (one stage kill + one injected wedge) against a real supervised
    # 2-stage pipeline with open-loop load — the resilience contract as
    # a regression-asserted row, like obs_overhead's <2% and
    # relay_transport's hop floors. Floors: >=99% of requests
    # completed-or-explicitly-rejected with ZERO silently lost,
    # post-recovery p99 TTFT <= 10x quiet p99, and every injected fault
    # paired with its supervisor_restart recovery event in the dumped
    # flight ring (benchmarks/chaos_probe.py).
    from benchmarks.chaos_probe import (
        AVAILABILITY_FLOOR,
        TTFT_RATIO_CEIL,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    avail = row.pop("availability")
    _emit(results, config="chaos_resilience", metric="availability_pct",
          value=round(avail * 100, 3), ok=ok,
          note=f"open-loop load through a supervised 2-stage pipeline "
               f"under kill+wedge injection; floors: availability >= "
               f"{AVAILABILITY_FLOOR:.0%} (zero silent losses), "
               f"recovery p99 TTFT <= {TTFT_RATIO_CEIL:.0f}x quiet, "
               "inject/recovery flight events paired", **row)
    return results


@device_config("fleet_serving")
def dev_fleet_serving():
    # ISSUE 13: the fleet front door's measured contract — open-loop
    # load through the router over 2 REAL `node --serve_lm` replica
    # subprocesses (gpt2), one SIGKILLed mid-measurement. Floors: fleet-leg
    # availability >= 99% completed-or-explicitly-rejected with ZERO
    # silently lost, fleet delivered tokens/sec >= 1.5x the unfronted
    # single-replica leg at the same demand (on a 1-core host the win
    # is admission-control goodput — the single leg collapses into
    # admit-then-deadline-cancel waste; on real chips width adds on
    # top), and the kill paired with its supervisor_restart in the
    # dumped flight ring. Honors --require-substrate (PR 11's
    # trajectory contract) via $DNN_TPU_REQUIRE_SUBSTRATE.
    from benchmarks.fleet_serving_probe import (
        AVAILABILITY_FLOOR,
        FLEET_SPEEDUP_FLOOR,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    require = os.environ.get("DNN_TPU_REQUIRE_SUBSTRATE")
    note = (f"router over 2 supervised replica subprocesses, one "
            f"killed mid-run; floors: availability >= "
            f"{AVAILABILITY_FLOOR:.0%} (zero silent losses), fleet "
            f"tokens/sec >= {FLEET_SPEEDUP_FLOOR}x the single-replica "
            "leg, kill/restart flight events paired")
    if require:
        row["required_substrate"] = require
        if row.get("round_substrate") != require:
            ok = False
            note += (f"; required substrate '{require}' but the probe "
                     f"ran on '{row.get('round_substrate')}'")
    tps = row.pop("fleet_tokens_per_sec")
    _emit(results, config="fleet_serving",
          metric="fleet_tokens_per_sec", value=tps, ok=ok,
          note=note, **row)
    return results


@device_config("kv_tier")
def dev_kv_tier():
    # ISSUE 15: the fleet KV tier's measured contract — router + 2
    # real paged-radix replica subprocesses under the multi-turn-chat
    # arrival schedule with affinity deliberately broken (round-robin
    # placement, kvtier="pull"): cross-replica block-hit ratio >= 0.5,
    # adopted-vs-local token parity exact (greedy + seeded-sampled),
    # warm-turn TTFT p95 >= 2x forced-cold, migrated bytes under the
    # full-KV row-handoff baseline, and the donor-death chaos leg
    # (lease expiry + kvtier_fallback read back from the dumped rings,
    # zero token divergence, zero leaked blocks).
    from benchmarks.kv_tier_probe import (
        CROSS_HIT_FLOOR,
        TTFT_RATIO_FLOOR,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    require = os.environ.get("DNN_TPU_REQUIRE_SUBSTRATE")
    note = (f"router + 2 paged-radix replicas, anti-affinity chat; "
            f"floors: cross-replica block-hit >= {CROSS_HIT_FLOOR}, "
            f"warm TTFT p95 >= {TTFT_RATIO_FLOOR}x vs cold, migrated "
            "bytes < row-handoff baseline, parity exact, donor-death "
            "leg green")
    if require:
        row["required_substrate"] = require
        if row.get("round_substrate") != require:
            ok = False
            note += (f"; required substrate '{require}' but the probe "
                     f"ran on '{row.get('round_substrate')}'")
    ratio = row.pop("cross_replica_hit_ratio")
    _emit(results, config="kv_tier",
          metric="cross_replica_hit_ratio", value=ratio, ok=ok,
          note=note, cross_replica_hit_ratio=ratio, **row)
    return results


@device_config("kv_economy")
def dev_kv_economy():
    # ISSUE 18: kvlens's miss-ratio curve validated against ground
    # truth — replay the deterministic chat-arrival schedule (working
    # set 3x the pool) at capacity A, record the curve's 0.5x
    # prediction, re-run the identical trace at capacity B = A/2, and
    # assert |predicted − measured| <= MRC_ERROR_CEIL on the real
    # store's per-block hit tally. The pressured run must also bill a
    # non-zero evict→refetch thrash tax (the forensics leg).
    from benchmarks.kv_economy_probe import MRC_ERROR_CEIL, measure

    results = []
    row = measure()
    ok = row.pop("ok")
    err = row.pop("mrc_prediction_error")
    _emit(results, config="kv_economy",
          metric="mrc_prediction_error", value=err, ok=ok,
          platform=_platform(),
          note=f"curve@{row['cap_A_blocks']}blk predicts hit ratio at "
               f"{row['cap_B_blocks']}blk; ceiling "
               f"{MRC_ERROR_CEIL} absolute; thrash refetches > 0 "
               "required at the pressured capacity",
          mrc_prediction_error=err, **row)
    return results


@device_config("capacity_plan")
def dev_capacity_plan():
    # ISSUE 20: caplens's what-if planner validated against ground
    # truth — observe a 1-replica fleet under the seeded bursty trace,
    # take the lens's 2-replica prediction, then measure a REAL
    # 2-replica fleet replaying the identical trace. Floors:
    # |predicted − measured| availability <= PRED_ERROR_CEIL, wall-p95
    # ratio inside the documented bound, cold-start ledger coverage >=
    # 95% of spawn→first-token wall with compile as its own bucket,
    # zero silent losses. Honors --require-substrate via
    # $DNN_TPU_REQUIRE_SUBSTRATE.
    from benchmarks.capacity_plan_probe import (
        COLDSTART_COVERAGE_FLOOR,
        PRED_ERROR_CEIL,
        WAIT_RATIO_BOUND,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    row.pop("coldstart_entries", None)  # per-spawn detail: JSONL bloat
    require = os.environ.get("DNN_TPU_REQUIRE_SUBSTRATE")
    note = (f"1-replica observations predict the 2-replica fleet on "
            f"the identical seeded trace; floors: abs(pred-measured) "
            f"availability <= {PRED_ERROR_CEIL}, wall-p95 ratio <= "
            f"{WAIT_RATIO_BOUND}x, cold-start coverage >= "
            f"{COLDSTART_COVERAGE_FLOOR:.0%} with compile bucketed, "
            "zero silent losses")
    if require:
        row["required_substrate"] = require
        if row.get("round_substrate") != require:
            ok = False
            note += (f"; required substrate '{require}' but the probe "
                     f"ran on '{row.get('round_substrate')}'")
    err = row.pop("value")
    _emit(results, config="capacity_plan",
          metric="capacity_prediction_error", value=err, ok=ok,
          note=note, **row)
    return results


@device_config("train_goodput")
def dev_train_goodput():
    # ISSUE 19: trainlens — the training-step observatory, judged
    # before the training PR it will grade. One fit() run on the
    # pinned gpt-mini with the TrainClock attached. Asserted in the
    # probe: phase accounting (data/dispatch/wait/ckpt/eval/obs)
    # covers >= COVERAGE_FLOOR of the externally measured fit wall,
    # MFU against the PINNED roofline clears the (deliberately low)
    # floor, an injected data-loader sleep lands in data_stall within
    # STALL_TOLERANCE, an injected NaN batch fires loss_nan within
    # SENTINEL_MAX_STEPS steps with the event in the dumped flight
    # ring, and the whole observatory (clock + sentinel) costs
    # <= OVERHEAD_BUDGET of step wall under ABBA pairing.
    from benchmarks.train_goodput_probe import (
        COVERAGE_FLOOR,
        MFU_FLOOR,
        OVERHEAD_BUDGET,
        PINNED_PEAK_FLOPS,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    _emit(results, config="train_goodput",
          metric="train_mfu", value=row.pop("mfu"),
          platform=_platform(), ok=ok,
          note=f"model FLOP utilization of the probe fit() against the "
               f"PINNED {PINNED_PEAK_FLOPS:.0e} FLOP/s roofline (floor "
               f"{MFU_FLOOR:g} guards the estimator, not the hardware); "
               f"ASSERTED: phase coverage >= {COVERAGE_FLOOR:.0%}, "
               f"injected stall attributed, NaN caught <= 2 steps, "
               f"observatory overhead <= {OVERHEAD_BUDGET:.0%}",
          **row)
    return results


@device_config("step_timeline")
def dev_step_timeline():
    # ISSUE 11: step-timeline attribution baseline — the §10/§11 decode
    # configuration with the StepClock attached. Asserted: phase
    # accounting (admit/host/dispatch/wait/commit/obs) covers >= 95% of
    # the externally measured round wall (no unattributed dark time).
    # Recorded: the host-serialization fraction — the number the item-4
    # overlap/fusion PR must ratchet DOWN, the way decode_mbu ratchets
    # up — plus the device-view cross-check from a real profiler
    # capture analyzed by obs/timeline.analyze().
    from benchmarks.step_timeline_probe import (
        COVERAGE_FLOOR,
        HOST_FRACTION_CEIL,
        measure,
    )

    results = []
    row = measure()
    ok = row.pop("ok")
    host_frac = row.pop("host_serialization_fraction")
    _emit(results, config="step_timeline",
          metric="host_serialization_pct",
          value=round(host_frac * 100, 2), platform=_platform(), ok=ok,
          note=f"share of decode-round wall NOT inside a decode step "
               f"program, measured on the ISSUE 12 hot path "
               f"(interleaved prefill + overlap); ASSERTED: phase "
               f"coverage >= {COVERAGE_FLOOR:.0%} of measured wall AND "
               f"host fraction <= {HOST_FRACTION_CEIL:.2f} (the item-4 "
               "ratchet, down from the PR 10 baseline 0.549; the "
               "convoy leg re-measures alongside)", **row)
    return results


@device_config("constrained_hotpath")
def dev_constrained_hotpath():
    # ISSUE 16: constrained decoding on the interleaved+overlap hot
    # path (on-device DFA walk). Paired legs, both fully grammar-
    # constrained: convoy admission (the only path constraints had
    # before the transition-table pool) vs interleave+overlap. Asserted
    # in the probe: exact token parity between the legs AND against a
    # pure-host DFA replay, hot tokens/sec >= SPEEDUP_FLOOR x convoy,
    # and host fraction <= the step_timeline ceiling — constraints
    # answer to the SAME 0.40 ratchet as unconstrained decode.
    from benchmarks.constrained_hotpath_probe import (
        SPEEDUP_FLOOR,
        measure,
    )
    from benchmarks.step_timeline_probe import HOST_FRACTION_CEIL

    results = []
    row = measure()
    ok = row.pop("ok")
    _emit(results, config="constrained_hotpath",
          metric="vs_convoy_tps", value=row.pop("vs_convoy_tps"),
          platform=_platform(), ok=ok,
          note=f"constrained hot-path tokens/sec over the convoy-"
               f"admission control, all slots grammar-constrained; "
               f"ASSERTED: token parity (cross-leg + host DFA oracle), "
               f"speedup >= {SPEEDUP_FLOOR}, host fraction <= "
               f"{HOST_FRACTION_CEIL:.2f} (the ISSUE 16 ratchet pair)",
          **row)
    return results


@device_config("substrate")
def dev_substrate():
    # ROADMAP 5a prep: ONE preflight row that probes the device (the
    # watchdog's subprocess probe via bench._backend_alive, which
    # invokes the supervisor's recover_backend on a WEDGED attempt and
    # counts a recovery as success), stamps honest provenance (commit +
    # the substrate the round will actually run on), and carries the
    # substrate contract for the WHOLE round: with --require-substrate
    # set, this row's ok says whether the round's trajectory may join
    # the on-chip trend — one gate instead of per-probe require checks.
    # Registered FIRST (see the insert below) so a full round learns
    # its substrate before spending hours measuring on it.
    from bench import _backend_alive

    from dnn_tpu import obs

    results = []
    t0 = time.perf_counter()
    # shorter ladder than bench.py's headline probe: a preflight must
    # not spend 10+ min deciding; the second attempt still allows the
    # longest healthy cold init and rides the recover_backend path
    alive = _backend_alive(deadlines_s=(60.0, 240.0))
    probe_s = time.perf_counter() - t0
    if not alive:
        import jax

        jax.config.update("jax_platforms", "cpu")
    platform = _platform()
    events = obs.flight.recorder().events()
    outcomes = {}
    for kind in ("probe_fail", "probe_recovered", "probe_exhausted"):
        n = sum(1 for e in events if e["kind"] == kind)
        if n:
            outcomes[kind] = n
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=REPO,
            timeout=10).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — provenance is best-effort
        rev = "unknown"
    require = os.environ.get("DNN_TPU_REQUIRE_SUBSTRATE")
    ok = True
    note = ("device probe "
            + ("ok" if alive else "exhausted -> CPU fallback")
            + "; recover_backend consulted on wedged attempts; this "
              "row's substrate is the round's provenance stamp")
    row = {"probe_alive": bool(alive),
           "probe_wall_s": round(probe_s, 1), "commit": rev,
           **outcomes}
    if require:
        row["required_substrate"] = require
        ok = platform == require
        if not ok:
            note += (f"; required substrate '{require}' but the round "
                     f"runs on '{platform}' — the whole round's rows "
                     "are off-contract")
    _emit(results, config="substrate", metric="probe_alive",
          value=bool(alive), platform=platform, ok=ok, note=note,
          **row)
    return results


# run the preflight FIRST: it was necessarily defined after the model
# configs above, but the round must learn its substrate before
# measuring on it
DEVICE_CONFIGS.insert(0, DEVICE_CONFIGS.pop(
    next(i for i, c in enumerate(DEVICE_CONFIGS)
         if c[0] == "substrate")))


# ----------------------------------------------------------------------
# the workload suite (ISSUE 14): one asserted row per scenario
# ----------------------------------------------------------------------

WORKLOAD_SCENARIOS = ("chat", "longcontext", "json_mode",
                      "json_mode_fast", "spec_mix", "lora",
                      "breach_chaos")


def _workload_config(scen: str):
    def run():
        # each scenario's SLO is asserted IN-RUN by the verdict engine
        # (obs/slo.py); the breach scenario is green only when it
        # breaches AND its incident bundle reconstructs off disk
        # (benchmarks/workload_probe.py)
        from benchmarks.workload_probe import measure

        results = []
        row = measure(scen)
        ok = row.pop("ok")
        # measure() carries its own note on some paths (e.g. a breach
        # scenario whose injection did not bite) — fold it in rather
        # than colliding on the kwarg
        extra = row.pop("note", None)
        if row.pop("expect_breach", False):
            note = ("chaos-injected breach: asserted by reading the "
                    "incident bundle back (manifest verdict + "
                    "chaos_inject events in the dumped timeline + "
                    "CLI render)")
            _emit(results, config=f"workload_{scen}",
                  metric="breach_reconstructed",
                  value=bool(row.pop("reconstructed", False)), ok=ok,
                  note=note + (f"; {extra}" if extra else ""), **row)
        else:
            note = ("open-loop scenario vs its declared SLO "
                    "(dnn_tpu/workloads); ok IS the verdict")
            _emit(results, config=f"workload_{scen}",
                  metric="goodput_tokens_per_sec",
                  value=row.pop("goodput_tokens_per_sec"), ok=ok,
                  note=note + (f"; {extra}" if extra else ""), **row)
        return results
    run.__name__ = f"dev_workload_{scen}"
    return run


for _scen in WORKLOAD_SCENARIOS:
    DEVICE_CONFIGS.append((f"workload_{_scen}",
                           _workload_config(_scen), False))


def _serve_round(srv_x, cfg, sb_new, n_requests, plen_fn, constraint=None,
                 key=9):
    """Admit-when-a-slot-frees over the pool, then drain — the
    continuous-batching arrival pattern, shared by the e2e and
    constrained-tax configs."""
    import jax
    import jax.numpy as jnp

    rng_np = jax.random.PRNGKey(key)
    rids = []
    for i in range(n_requests):
        p = jax.random.randint(jax.random.fold_in(rng_np, i),
                               (plen_fn(i),), 0, cfg.vocab_size,
                               dtype=jnp.int32)
        while srv_x.free_slots() == 0:
            srv_x.step()
        rids.append(srv_x.submit(
            jnp.asarray(p), max_new_tokens=sb_new, constraint=constraint))
    out = srv_x.drain()
    return sum(len(out[r]) for r in rids)


@device_config("gpt2_serving_e2e", tpu_only=True)
def dev_gpt2_serving_e2e():
    # Continuous-batching END-TO-END serving throughput: mixed-length
    # prompts through the slot pool (chunked prefill + per-row decode +
    # retirement), wall-clock including the host-side scheduler — the
    # number a serving user actually gets. TPU-only: the wall-clock of
    # the host loop on a CPU backend measures nothing interesting.
    import time as _time

    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    bf16_prepared = _to_bf16(prepared)
    sb_new = 64
    # ONE batcher for warmup + timed round: the three step programs are
    # per-instance jit closures, so a fresh instance would recompile
    # inside the timed window and the row would measure XLA, not serving
    srv = ContinuousBatcher(cfg, bf16_prepared, slots=8, max_len=256,
                            prompt_pad=128, kv_dtype=jnp.bfloat16,
                            compute_dtype=jnp.bfloat16)
    mixed_plen = lambda i: 16 + (i * 7) % 112  # noqa: E731 — 16..121
    _serve_round(srv, cfg, sb_new, 24, mixed_plen)  # compile the programs
    t0 = _time.perf_counter()
    total = _serve_round(srv, cfg, sb_new, 24, mixed_plen)
    dt = _time.perf_counter() - t0
    _emit(results, config="gpt2_serving_e2e", metric="tokens_per_sec",
          value=round(total / dt, 1), platform=_platform(), slots=8,
          requests=24, new_tokens_per_req=sb_new,
          note="wall-clock drain of 24 mixed-length requests through the "
               "continuous batcher (chunked prefill + decode + host "
               "scheduler)")
    return results


@device_config("gpt2_serving_constrained_tax", tpu_only=True)
def dev_gpt2_serving_constrained_tax():
    # Constrained-decoding tax: every slot carries a grammar, so each
    # step pays the host-side DFA advance + the device-side bias path.
    # The [0-9]+ grammar (2 DFA states) isolates the PER-STEP mechanism
    # cost — table compile is a one-time artifact outside the window.
    import time as _time

    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.constrain import TokenConstraint, byte_vocab
    from dnn_tpu.runtime.serving import ContinuousBatcher

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    bf16_prepared = _to_bf16(prepared)
    cons = TokenConstraint.from_regex(r"[0-9]+", byte_vocab(cfg.vocab_size))
    tps_c = {}
    for name, con in (("off", None), ("on", cons)):
        # one batcher per leg, REUSED for warmup + timed round (fresh
        # instances would recompile inside the timed window). Both legs
        # run allow_constraints=True (device mask pool allocated, bool
        # gather in the program), so the on/off delta isolates the
        # per-step host DFA walk + (slots,) state-vector flush — the
        # whole marginal cost of a live grammar in the new design.
        srv_c = ContinuousBatcher(
            cfg, bf16_prepared, slots=8, max_len=256, prompt_pad=128,
            kv_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
            allow_constraints=True, temperature=1.0)
        _serve_round(srv_c, cfg, 64, 16, lambda i: 32, constraint=con,
                     key=11)  # compile/warm
        t0 = _time.perf_counter()
        total = _serve_round(srv_c, cfg, 64, 16, lambda i: 32,
                             constraint=con, key=11)
        tps_c[name] = total / (_time.perf_counter() - t0)
    c_overhead = tps_c["off"] / tps_c["on"] - 1.0
    _emit(results, config="gpt2_serving_constrained_tax",
          metric="overhead_pct", value=round(c_overhead * 100, 2),
          platform=_platform(), slots=8,
          tps_unconstrained=round(tps_c["off"], 1),
          tps_constrained=round(tps_c["on"], 1),
          note="all 8 slots grammar-constrained ([0-9]+): per-step DFA "
               "advance + device-resident mask table")
    return results


@device_config("mixtral_decode", tpu_only=True)
def dev_mixtral_decode():
    # Mixtral-style MoE decode vs its dense-equivalent (same ACTIVE FLOPs
    # per token: top-2 of 8 experts at d_ff F == dense at 2F) — the MoE
    # serving trade measured, with int8 expert stacks as the third leg.
    # Random-init mechanism bench at a mid-size shape that fits one chip;
    # bytes/token charges the FULL expert stacks (at B=8 top-2 routing
    # touches essentially all 8 experts per layer, so the worst case IS
    # the steady state — stated, not hidden).
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt, llama, llama_moe
    from dnn_tpu.quant import param_bytes, quantize_tree
    from dnn_tpu.utils.flops import mbu
    from dnn_tpu.utils.timing import device_time

    results = []
    mx_cfg = llama_moe.MixtralConfig(
        block_size=512, vocab_size=32000, n_layer=8, n_head=16,
        n_kv_head=4, n_embd=1024, d_ff=3584, n_expert=8, router_top_k=2,
        capacity_factor=4.0)
    dense_cfg = llama.LlamaConfig(
        block_size=512, vocab_size=32000, n_layer=8, n_head=16,
        n_kv_head=4, n_embd=1024, d_ff=2 * 3584)
    b, prompt_len, new_tokens = 8, 16, 64
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             mx_cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    s_max = prompt_len + new_tokens
    cache_elems = (2 * mx_cfg.n_layer * b * mx_cfg.n_kv_head
                   * mx_cfg.head_dim * s_max)

    mx_prep = gpt.prepare_stacked(
        llama_moe.init(jax.random.PRNGKey(0), mx_cfg, dtype=jnp.bfloat16),
        mx_cfg)
    mx_q = quantize_tree(mx_prep)
    dense_prep = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(0), dense_cfg, dtype=jnp.bfloat16),
        dense_cfg)

    def _decode_row(config_name, make, weights, extra):
        gfn = make()
        dt = device_time(gfn, weights, ids, rng, n1=1, n2=3)
        tps = b * new_tokens / dt
        bpt = (param_bytes(weights) + cache_elems * 2) / b  # bf16 cache
        row = {"bytes_per_token_mb": round(bpt / 1e6, 2)}
        u = mbu(bpt, tps)
        if u is not None:
            row["mbu"] = round(u, 4)
        _emit(results, config=config_name, metric="tokens_per_sec",
              value=round(tps, 1), platform=_platform(), batch=b,
              new_tokens=new_tokens, **row, **extra)

    _decode_row(
        "mixtral_decode_w_bf16",
        lambda: llama_moe.make_generate(
            mx_cfg, max_new_tokens=new_tokens, compute_dtype=jnp.bfloat16,
            kv_dtype=jnp.bfloat16),
        mx_prep, {"experts": "8x top-2",
                  "note": "bytes charge ALL expert stacks (B=8 touches "
                          "~every expert per layer)"})
    _decode_row(
        "mixtral_decode_w_int8",
        lambda: llama_moe.make_generate(
            mx_cfg, max_new_tokens=new_tokens, compute_dtype=jnp.bfloat16,
            kv_dtype=jnp.bfloat16),
        mx_q, {"experts": "8x top-2 int8"})
    _decode_row(
        "mixtral_dense_equiv_decode_w_bf16",
        lambda: llama.make_generate(
            dense_cfg, max_new_tokens=new_tokens,
            compute_dtype=jnp.bfloat16, kv_dtype=jnp.bfloat16),
        dense_prep, {"note": "dense MLP at 2*d_ff = the MoE's ACTIVE "
                             "FLOPs per token"})
    return results


@device_config("speculative_decode", tpu_only=True)
def dev_speculative_decode():
    # Speculative decoding measured: acceptance rate + END-TO-END speedup
    # vs plain decode — the number the feature exists for (VERDICT r5 ask
    # #2). Random-init weights make a smaller independent draft useless
    # (near-zero agreement), so the pairs are QUANTIZED SELF-DRAFTS — the
    # target's own weights at int8/int4 (a real deployment pattern:
    # the draft shares the target's distribution but streams half/quarter
    # the bytes per proposal on a bandwidth-bound decode).
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.quant import quantize_gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.runtime.speculative import make_speculative_generate
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    bf16_prepared = _to_bf16(prepared)
    q8 = quantize_gpt(prepared)
    q4 = quantize_gpt(prepared, bits=4)
    prompt_len, new_tokens, k = 32, 128, 4
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)

    # plain-decode baseline at the same (batch-1) shape, greedy + sampled
    base_tps = {}
    for mode, temp in (("greedy", 0.0), ("sampled", 1.0)):
        gfn = gen.make_generate(
            cfg, max_new_tokens=new_tokens, compute_dtype=jnp.bfloat16,
            kv_dtype=jnp.bfloat16, temperature=temp)
        dt = device_time(gfn, bf16_prepared, ids, rng, n1=1, n2=3)
        base_tps[mode] = new_tokens / dt

    pairs = (("int8_draft_greedy", q8, 0.0),
             ("int8_draft_sampled", q8, 1.0),
             ("int4_draft_greedy", q4, 0.0))
    for name, draft_w, temp in pairs:
        sfn = make_speculative_generate(
            cfg, cfg, max_new_tokens=new_tokens, k=k, temperature=temp,
            compute_dtype=jnp.bfloat16, return_stats=True)
        toks, stats = sfn(bf16_prepared, draft_w, ids, rng)
        jax.block_until_ready(toks)
        accept = float(stats["accepted"]) / max(float(stats["proposed"]), 1)
        if temp == 0.0:
            # greedy speculative must equal plain greedy token-for-token
            plain = gen.make_generate(
                cfg, max_new_tokens=new_tokens,
                compute_dtype=jnp.bfloat16, kv_dtype=jnp.bfloat16)(
                bf16_prepared, ids, rng)
            assert (jnp.asarray(toks) == jnp.asarray(plain)).all(), (
                "speculative greedy diverged from plain greedy")

        def run(tw, dw, ii, rr):
            t, _ = sfn(tw, dw, ii, rr)
            return t

        dt = device_time(run, bf16_prepared, draft_w, ids, rng, n1=1, n2=3)
        tps = new_tokens / dt
        base = base_tps["greedy" if temp == 0.0 else "sampled"]
        _emit(results, config=f"speculative_{name}",
              metric="speedup_vs_plain", value=round(tps / base, 3),
              platform=_platform(), k=k, new_tokens=new_tokens,
              acceptance_rate=round(accept, 4),
              tps_speculative=round(tps, 1), tps_plain=round(base, 1),
              note="quantized self-draft (target weights at reduced "
                   "precision); greedy output token-identical to plain")
    return results


@device_config("embeddings_throughput", tpu_only=True)
def dev_embeddings_throughput():
    # Embeddings endpoint throughput: mean-pooled hidden states over
    # padded batches (runtime/embeddings.py) — the encode-side serving
    # number.
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.embeddings import make_embed
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    bf16_prepared = _to_bf16(prepared)
    b, t = 32, 512
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    lengths = jnp.asarray([t - (i * 13) % 256 for i in range(b)],
                          jnp.int32)
    fn = make_embed(cfg, pooling="mean", compute_dtype=jnp.bfloat16)
    dt = device_time(fn, bf16_prepared, ids, lengths, n1=1, n2=3)
    _emit(results, config="embeddings_throughput",
          metric="sequences_per_sec", value=round(b / dt, 1),
          platform=_platform(), batch=b, seq=t, pooling="mean",
          tokens_per_sec=round(b * t / dt, 1))
    return results


@device_config("beam_vs_greedy", tpu_only=True)
def dev_beam_vs_greedy():
    # Beam search cost: beam_size=4 vs greedy on the same model/batch —
    # the quality/throughput trade quantified (beams share the prompt
    # cache; each step scores K continuations).
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.runtime.beam import make_beam_generate
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.PRESETS["gpt2"]
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    bf16_prepared = _to_bf16(prepared)
    b, prompt_len, new_tokens, k = 4, 16, 64, 4
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    gfn = gen.make_generate(cfg, max_new_tokens=new_tokens,
                            compute_dtype=jnp.bfloat16,
                            kv_dtype=jnp.bfloat16)
    dt_g = device_time(gfn, bf16_prepared, ids, rng, n1=1, n2=3)
    bfn = make_beam_generate(cfg, max_new_tokens=new_tokens, beam_size=k,
                             compute_dtype=jnp.bfloat16,
                             kv_dtype=jnp.bfloat16)
    dt_b = device_time(bfn, bf16_prepared, ids, n1=1, n2=3)
    tps_g = b * new_tokens / dt_g
    tps_b = b * new_tokens / dt_b  # committed tokens (best hypothesis)
    _emit(results, config="beam_vs_greedy", metric="beam_cost_ratio",
          value=round(dt_b / dt_g, 3), platform=_platform(), batch=b,
          beam_size=k, new_tokens=new_tokens,
          tps_greedy=round(tps_g, 1), tps_beam=round(tps_b, 1),
          note="cost of beam_size=4 per COMMITTED token vs greedy; beams "
               "share the prompt cache")
    return results


@device_config("decode_bucketing")
def dev_decode_bucketing():
    # Length-aware bucketed decode (runtime/decode_buckets.py), measured
    # where it matters: a serving-style max_len allocation decoded at a
    # live position <= max_len/8. The unbucketed leg is the SAME host
    #-dispatched decoder with a single max_len bucket, so the delta
    # isolates the cache-view length; greedy token identity between the
    # two programs is asserted in-run (bucket-boundary crossings
    # included). CPU-runnable: the win is bytes-per-step proportionality,
    # not a chip feature.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.decode_buckets import make_bucketed_generate
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = gpt.GPTConfig(block_size=1024, vocab_size=512, n_layer=4,
                        n_head=8, n_embd=256)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    b, prompt_len, new_tokens, max_len = 8, 16, 56, 1024
    # live positions run 16..71 — all <= max_len/8 = 128; the bucketed
    # leg crosses the 64-bucket edge mid-decode (parity must hold there)
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    legs = {}
    for name_l, buckets in (("bucketed", None), ("unbucketed", (max_len,))):
        # attn_kernel pinned OFF for both legs: on TPU the "auto" policy
        # would route only the max_len-sized unbucketed leg through the
        # Pallas kernel and the A/B would no longer isolate the cache
        # -view length (the kernel-vs-einsum A/B is its own config,
        # gpt2_decode_attnkernel)
        gen = make_bucketed_generate(
            cfg, max_len=max_len, max_new_tokens=new_tokens,
            buckets=buckets, attn_kernel=False)
        gen1 = make_bucketed_generate(
            cfg, max_len=max_len, max_new_tokens=1, buckets=buckets,
            attn_kernel=False)
        toks = np.asarray(gen(prepared, ids, rng))
        # subtract a max_new=1 run so the rate charges DECODE steps
        # against decode time (the longctx config's technique)
        dt_full = device_time(gen, prepared, ids, rng, n1=1, n2=3)
        dt_pre = device_time(gen1, prepared, ids, rng, n1=1, n2=3)
        dt = max(dt_full - dt_pre, 1e-9)
        legs[name_l] = {"toks": toks, "dt": dt,
                        "tps": b * (new_tokens - 1) / dt,
                        "buckets": gen.buckets}
    np.testing.assert_array_equal(
        legs["bucketed"]["toks"], legs["unbucketed"]["toks"],
        err_msg="bucketed decode diverged from the unbucketed program")
    # modeled cache bytes/step: mean live bucket vs the full allocation
    # (f32 K+V, all layers)
    per_pos = 2 * cfg.n_layer * b * cfg.n_embd * 4
    steps = range(prompt_len + 1, prompt_len + new_tokens)
    ladder = legs["bucketed"]["buckets"]
    mean_bucket = sum(next(x for x in ladder if x >= s) for s in steps) \
        / len(steps)
    _emit(results, config="decode_bucketing",
          metric="decode_speedup_at_live_le_max_len_div_8",
          value=round(legs["unbucketed"]["dt"] / legs["bucketed"]["dt"], 3),
          platform=_platform(), batch=b, prompt=prompt_len,
          new_tokens=new_tokens, max_len=max_len,
          buckets=str(ladder),
          tps_bucketed=round(legs["bucketed"]["tps"], 1),
          tps_unbucketed=round(legs["unbucketed"]["tps"], 1),
          modeled_cache_mb_per_step_bucketed=round(
              per_pos * mean_bucket / 1e6, 2),
          modeled_cache_mb_per_step_unbucketed=round(
              per_pos * max_len / 1e6, 2),
          note="greedy token identity bucketed==unbucketed asserted "
               "in-run, incl. a bucket-edge crossing")
    return results


# --- platform-independent legs of the former tpu_only configs (VERDICT
# r5 weak #2): acceptance rates and RELATIVE costs are properties of the
# models/algorithms, not the chip — measured on whatever backend this
# host resolves, at shapes small enough for a CPU leg. The tpu_only
# wall-clock twins above keep the absolute numbers. ---

def _small_gpt():
    from dnn_tpu.models import gpt

    return gpt.GPTConfig(block_size=512, vocab_size=512, n_layer=4,
                         n_head=4, n_embd=128)


@device_config("speculative_relative")
def dev_speculative_relative():
    # Acceptance rate + relative speedup of quantized self-draft
    # speculation (greedy + sampled) — the pair property the tpu_only
    # config left unmeasured for two rounds.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dnn_tpu.quant import quantize_gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.runtime.speculative import make_speculative_generate
    from dnn_tpu.utils.timing import device_time

    results = []
    from dnn_tpu.models import gpt

    cfg = _small_gpt()
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    q8 = quantize_gpt(prepared)
    prompt_len, new_tokens, k = 32, 64, 4
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    for mode, temp in (("greedy", 0.0), ("sampled", 1.0)):
        gfn = gen.make_generate(cfg, max_new_tokens=new_tokens,
                                temperature=temp)
        dt_plain = device_time(gfn, prepared, ids, rng, n1=1, n2=3)
        sfn = make_speculative_generate(
            cfg, cfg, max_new_tokens=new_tokens, k=k, temperature=temp,
            return_stats=True)
        toks, stats = sfn(prepared, q8, ids, rng)
        jax.block_until_ready(toks)
        accept = float(stats["accepted"]) / max(float(stats["proposed"]), 1)
        if temp == 0.0:
            np.testing.assert_array_equal(
                np.asarray(toks), np.asarray(gfn(prepared, ids, rng)),
                err_msg="speculative greedy diverged from plain greedy")

        def run(tw, dw, ii, rr, _s=sfn):
            t, _ = _s(tw, dw, ii, rr)
            return t

        dt_spec = device_time(run, prepared, q8, ids, rng, n1=1, n2=3)
        _emit(results, config=f"speculative_relative_{mode}",
              metric="speedup_vs_plain",
              value=round(dt_plain / dt_spec, 3), platform=_platform(),
              k=k, new_tokens=new_tokens,
              acceptance_rate=round(accept, 4),
              note="int8 self-draft on a small random-init GPT; "
                   "acceptance is a pair property, speedup is relative "
                   "on this host's backend")
    return results


@device_config("beam_vs_greedy_relative")
def dev_beam_vs_greedy_relative():
    # beam k=4 cost per committed token RELATIVE to greedy — meaningful
    # as a ratio on any backend.
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime import generate as gen
    from dnn_tpu.runtime.beam import make_beam_generate
    from dnn_tpu.utils.timing import device_time

    results = []
    cfg = _small_gpt()
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    b, prompt_len, new_tokens, k = 4, 16, 32, 4
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    gfn = gen.make_generate(cfg, max_new_tokens=new_tokens)
    dt_g = device_time(gfn, prepared, ids, rng, n1=1, n2=3)
    bfn = make_beam_generate(cfg, max_new_tokens=new_tokens, beam_size=k)
    dt_b = device_time(bfn, prepared, ids, n1=1, n2=3)
    _emit(results, config="beam_vs_greedy_relative",
          metric="beam_cost_ratio", value=round(dt_b / dt_g, 3),
          platform=_platform(), batch=b, beam_size=k,
          new_tokens=new_tokens,
          note="relative cost of beam_size=4 per committed token on "
               "this host's backend (small random-init GPT)")
    return results


@device_config("mixtral_vs_dense_relative")
def dev_mixtral_vs_dense_relative():
    # MoE decode vs its active-FLOPs dense equivalent, as a RELATIVE
    # tokens/s ratio — the routing tax is an algorithmic property.
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt, llama, llama_moe
    from dnn_tpu.utils.timing import device_time

    results = []
    mx_cfg = llama_moe.PRESETS["mixtral-test"]
    dense_cfg = llama.LlamaConfig(
        block_size=mx_cfg.block_size, vocab_size=mx_cfg.vocab_size,
        n_layer=mx_cfg.n_layer, n_head=mx_cfg.n_head,
        n_kv_head=mx_cfg.n_kv_head, n_embd=mx_cfg.n_embd,
        d_ff=mx_cfg.router_top_k * mx_cfg.d_ff)
    b, prompt_len, new_tokens = 8, 8, 32
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_len), 0,
                             mx_cfg.vocab_size, dtype=jnp.int32)
    rng = jax.random.PRNGKey(2)
    mx_prep = gpt.prepare_stacked(
        llama_moe.init(jax.random.PRNGKey(0), mx_cfg), mx_cfg)
    dense_prep = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(0), dense_cfg), dense_cfg)
    mx_fn = llama_moe.make_generate(mx_cfg, max_new_tokens=new_tokens)
    dn_fn = llama.make_generate(dense_cfg, max_new_tokens=new_tokens)
    dt_mx = device_time(mx_fn, mx_prep, ids, rng, n1=1, n2=3)
    dt_dn = device_time(dn_fn, dense_prep, ids, rng, n1=1, n2=3)
    _emit(results, config="mixtral_vs_dense_relative",
          metric="moe_vs_dense_decode_ratio",
          value=round(dt_dn / dt_mx, 3), platform=_platform(), batch=b,
          new_tokens=new_tokens, experts=f"{mx_cfg.n_expert}x "
          f"top-{mx_cfg.router_top_k}",
          tps_moe=round(b * new_tokens / dt_mx, 1),
          tps_dense=round(b * new_tokens / dt_dn, 1),
          note="dense twin at router_top_k*d_ff = the MoE's ACTIVE "
               "FLOPs per token; >1 means MoE decodes faster than its "
               "dense equivalent on this backend")
    return results


@device_config("serving_constrained_tax_relative")
def dev_serving_constrained_tax_relative():
    # constrained-decoding tax as a ratio: per-step host DFA advance +
    # device mask gather vs the same pool unconstrained.
    import time as _time

    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.constrain import TokenConstraint, byte_vocab
    from dnn_tpu.runtime.serving import ContinuousBatcher

    results = []
    cfg = _small_gpt()
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    cons = TokenConstraint.from_regex(r"[0-9]+", byte_vocab(cfg.vocab_size))
    tps_c = {}
    for name, con in (("off", None), ("on", cons)):
        srv_c = ContinuousBatcher(
            cfg, prepared, slots=4, max_len=64, prompt_pad=16,
            allow_constraints=True, temperature=1.0)
        _serve_round(srv_c, cfg, 16, 8, lambda i: 12, constraint=con,
                     key=11)  # compile/warm
        t0 = _time.perf_counter()
        total = _serve_round(srv_c, cfg, 16, 8, lambda i: 12,
                             constraint=con, key=11)
        tps_c[name] = total / (_time.perf_counter() - t0)
    _emit(results, config="serving_constrained_tax_relative",
          metric="overhead_pct",
          value=round((tps_c["off"] / tps_c["on"] - 1.0) * 100, 2),
          platform=_platform(), slots=4,
          note="all slots grammar-constrained ([0-9]+) vs none, same "
               "allow_constraints=True pool — the marginal per-step cost "
               "of a live grammar, as a backend-relative ratio")
    return results


def run_device_config(name):
    """Child-process entry: run exactly one device config."""
    from dnn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile
    for cfg_name, fn, tpu_only in DEVICE_CONFIGS:
        if cfg_name == name:
            if tpu_only and _platform() != "tpu":
                _emit([], config=name, metric="skipped", value="tpu_only",
                      platform=_platform(),
                      note="TPU-only config; this process resolved a "
                           f"{_platform()} backend")
                return
            fn()
            return
    raise SystemExit(f"unknown device config {name!r}")


def run_device_section():
    """All device configs sequentially in one process (healthy-machine /
    debugging path; the orchestrated default isolates per config)."""
    for name, _, _ in DEVICE_CONFIGS:
        run_device_config(name)


# ----------------------------------------------------------------------
# section: cpu-mesh (8 virtual devices — pipeline forms)
# ----------------------------------------------------------------------

def run_cpu_mesh_section():
    # must precede first backend init: 8 virtual CPU devices
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    # this section measures the pipeline forms on 8 VIRTUAL devices, so
    # it takes the CPU backend even where a chip is attached; jax may
    # already be imported here, so the config option, not the env var
    jax.config.update("jax_platforms", "cpu")

    from dnn_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile

    import jax.numpy as jnp
    import numpy as np

    from dnn_tpu.models import gpt
    from dnn_tpu.parallel.mesh import STAGE_AXIS, make_mesh
    from dnn_tpu.parallel.pipeline import (
        RelayExecutor, spmd_pipeline, spmd_pipeline_stacked,
    )
    from dnn_tpu.registry import get_model
    from dnn_tpu.utils.timing import device_time

    assert len(jax.devices()) >= 8, "need 8 virtual CPU devices"
    results = []

    # configs 2 & 3: CIFAR 2-part / 4-part SPMD pipeline, microbatched
    spec = get_model("cifar_cnn")
    params = spec.init(jax.random.PRNGKey(0))
    batch = 64
    x = jnp.asarray(spec.example_input(batch_size=batch))
    for parts, mbs in ((2, 4), (4, 8)):
        stages = spec.partition(parts)
        mesh = make_mesh({STAGE_AXIS: parts}, jax.devices()[:parts])
        sparams = [st.slice_params(params) for st in stages]
        sfns = [st.apply for st in stages]
        # param_placement matches what engine auto policy serves for these
        # sub-threshold models (replicated; see engine.PLACEMENT_AUTO_BYTES)
        fn = lambda xx, _s=sfns, _p=sparams, _m=mesh, _mb=mbs: spmd_pipeline(
            _s, _p, xx, mesh=_m, num_microbatches=_mb,
            param_placement="replicated",
        )
        # parity guard: the pipeline must equal the full model before we
        # publish its number
        np.testing.assert_allclose(
            np.asarray(fn(x)), np.asarray(spec.apply(params, x)),
            atol=1e-4, rtol=1e-4,
        )
        dt = device_time(fn, x, n1=2, n2=6)
        _emit(results, config=f"cifar_{parts}stage_pipeline",
              metric="images_per_sec", value=round(batch / dt, 1),
              platform="cpu-mesh", batch=batch, microbatches=mbs)

    # config 5 (pipeline form): 8-stage stacked-block GPT pipeline
    cfg = gpt.GPTConfig(block_size=128, vocab_size=1024, n_layer=8,
                        n_head=4, n_embd=128)
    p = gpt.init(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh({STAGE_AXIS: 8}, jax.devices()[:8])
    stacked = gpt.stack_blocks(p, range(8))
    aux = {k: v for k, v in p.items() if not k.startswith("h_")}
    b, s, mbs = 16, 64, 4
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                             cfg.vocab_size, dtype=jnp.int32)

    def pipe(ids_in):
        xx = gpt.embed(aux, ids_in, cfg=cfg)
        h = spmd_pipeline_stacked(
            lambda bp, a: gpt.block_apply(bp, a, cfg=cfg),
            stacked, xx, mesh=mesh, num_microbatches=mbs,
        )
        return gpt.head(aux, h.astype(jnp.float32), cfg=cfg)

    full = gpt.make_apply(cfg)
    np.testing.assert_allclose(
        np.asarray(pipe(ids)), np.asarray(full(p, ids)), atol=1e-4, rtol=1e-4
    )
    dt = device_time(pipe, ids, n1=2, n2=6)
    _emit(results, config="gpt_8stage_pipeline", metric="tokens_per_sec",
          value=round(b * s / dt, 1), platform="cpu-mesh", batch=b, seq=s,
          microbatches=mbs)

    # interleaved vs GPipe schedule: same 8-layer model on 4 stages, V=2.
    # The structural win is the schedule length — sub-step equivalents
    # V*(M+S-1) vs VM+S-1 — reported alongside measured wall clock (CPU
    # timings carry dispatch noise; the sub-step ratio is the claim)
    from dnn_tpu.parallel.pipeline import (
        interleaved_schedule_steps, spmd_pipeline_interleaved,
    )

    s_stages, v, mbs2 = 4, 2, 8
    mesh4 = make_mesh({STAGE_AXIS: s_stages}, jax.devices()[:s_stages])
    x_emb = gpt.embed(aux, ids, cfg=cfg)
    per_st = cfg.n_layer // s_stages
    st4 = gpt.stack_blocks(p, range(cfg.n_layer))
    stage_form = jax.tree.map(
        lambda q: q.reshape(s_stages, per_st, *q.shape[1:]), st4)
    chunk_form = jax.tree.map(
        lambda q: q.reshape(v * s_stages, cfg.n_layer // (v * s_stages),
                            *q.shape[1:]), st4)

    def run_gpipe(xx):
        return spmd_pipeline_stacked(
            lambda bp, a: gpt.blocks_scan(bp, a, cfg=cfg),
            stage_form, xx, mesh=mesh4, num_microbatches=mbs2)

    def run_inter(xx):
        return spmd_pipeline_interleaved(
            lambda bp, a: gpt.blocks_scan(bp, a, cfg=cfg),
            chunk_form, xx, mesh=mesh4, num_microbatches=mbs2,
            virtual_stages=v)

    np.testing.assert_allclose(
        np.asarray(run_inter(x_emb)), np.asarray(run_gpipe(x_emb)),
        atol=1e-4, rtol=1e-4)
    dt_g = device_time(run_gpipe, x_emb, n1=2, n2=6)
    dt_i = device_time(run_inter, x_emb, n1=2, n2=6)
    gpipe_substeps = v * (mbs2 + s_stages - 1)
    inter_substeps = interleaved_schedule_steps(s_stages, v, mbs2)
    _emit(results, config="interleaved_vs_gpipe",
          metric="substep_ratio",
          value=round(inter_substeps / gpipe_substeps, 4),
          platform="cpu-mesh", stages=s_stages, virtual=v,
          microbatches=mbs2,
          gpipe_ms=round(dt_g * 1e3, 2), interleaved_ms=round(dt_i * 1e3, 2),
          note="schedule length V(M+S-1) -> VM+S-1. CPU wall-clock "
               "typically favors gpipe: interleaving doubles the scan "
               "steps and ring hops (per-sub-step dispatch + dynamic "
               "chunk gather dominate on CPU); the bubble win needs "
               "stage COMPUTE to dominate, i.e. real chips + real models")

    # LLaMA seq-sharded decode on a 4-device "seq" mesh: each device owns
    # a contiguous block of cache positions at GQA KV-head width; decode
    # steps combine per-shard attention with the exact distributed online
    # softmax (llama.make_generate_seq_sharded). Parity-guarded against
    # the solo decoder before the number is published.
    from dnn_tpu.models import llama
    from dnn_tpu.parallel.mesh import SEQ_AXIS

    ll_cfg = llama.PRESETS["llama-test"]
    ll_p = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(0), ll_cfg), ll_cfg)
    smesh = make_mesh({SEQ_AXIS: 4}, jax.devices()[:4])
    lb, lt, lnew = 2, 8, 16
    l_ids = jax.random.randint(jax.random.PRNGKey(2), (lb, lt), 0,
                               ll_cfg.vocab_size, dtype=jnp.int32)
    l_rng = jax.random.PRNGKey(3)
    gen_seq = llama.make_generate_seq_sharded(
        ll_cfg, smesh, max_new_tokens=lnew)
    np.testing.assert_array_equal(
        np.asarray(gen_seq(ll_p, l_ids, l_rng)),
        np.asarray(llama.make_generate(ll_cfg, max_new_tokens=lnew)(
            ll_p, l_ids, l_rng)))
    dt = device_time(gen_seq, ll_p, l_ids, l_rng, n1=1, n2=3)
    _emit(results, config="llama_seq_sharded_decode",
          metric="tokens_per_sec", value=round(lb * lnew / dt, 1),
          platform="cpu-mesh", batch=lb, new_tokens=lnew, seq_shards=4,
          note="each shard holds ceil(S_max/4) cache positions at "
               "KV-head width; token-parity with the solo decoder "
               "asserted in-run")

    # Mixtral EP decode on a 4-device "expert" mesh: batch + KV cache
    # shard over the expert axis, expert stacks shard on E, tokens reach
    # their experts via all_to_all inside every decode step
    # (llama_moe.make_generate_ep). Token-parity vs the solo grouped
    # decoder asserted before the number is published; cpu-mesh value
    # validates the machinery, not the speed (VERDICT r5 ask #2/#7).
    from dnn_tpu.models import llama_moe
    from dnn_tpu.parallel.mesh import EXPERT_AXIS

    mx_cfg = llama_moe.PRESETS["mixtral-test"]
    mx_p = gpt.prepare_stacked(
        llama_moe.init(jax.random.PRNGKey(4), mx_cfg), mx_cfg)
    emesh = make_mesh({EXPERT_AXIS: 4}, jax.devices()[:4])
    mb, mt, mnew = 8, 8, 16
    m_ids = jax.random.randint(jax.random.PRNGKey(5), (mb, mt), 0,
                               mx_cfg.vocab_size, dtype=jnp.int32)
    m_rng = jax.random.PRNGKey(6)
    gen_ep = llama_moe.make_generate_ep(mx_cfg, emesh, max_new_tokens=mnew)
    np.testing.assert_array_equal(
        np.asarray(gen_ep(mx_p, m_ids, m_rng)),
        np.asarray(llama.make_generate(
            mx_cfg, max_new_tokens=mnew,
            ffn=llama_moe.make_ffn(mx_cfg, groups=4))(mx_p, m_ids, m_rng)))
    dt = device_time(gen_ep, mx_p, m_ids, m_rng, n1=1, n2=3)
    _emit(results, config="mixtral_ep_decode",
          metric="tokens_per_sec", value=round(mb * mnew / dt, 1),
          platform="cpu-mesh", batch=mb, new_tokens=mnew, expert_shards=4,
          note="all_to_all expert dispatch per decode step; token-parity "
               "with the solo grouped decoder asserted in-run")

    # p50 inter-stage hop latency (relay executor, device-to-device)
    stages = spec.partition(2)
    relay = RelayExecutor(
        [st.apply for st in stages],
        [st.slice_params(params) for st in stages],
        devices=jax.devices()[:2],
    )
    hops = []
    for _ in range(9):
        hops.extend(relay.measure_hop_latency(x))
    p50 = float(np.percentile(hops, 50))
    _emit(results, config="interstage_hop", metric="p50_latency_ms",
          value=round(p50 * 1e3, 4), platform="cpu-mesh",
          note="v5e ICI target <2ms not measurable single-chip")
    return results


# ----------------------------------------------------------------------
# orchestration
# ----------------------------------------------------------------------

class _State:
    """Append-only row store at STATE_PATH: a config's rows persist as
    soon as that config finishes (the config is the resume unit — rows a
    child streamed before ITS death are salvaged by the orchestrator and
    land here with the failure marker); a `done` marker per config
    records completion. `--resume` replays markers to skip ok configs
    and retry failed ones — the crash-resume contract VERDICT r4 asked
    for."""

    def __init__(self, path=STATE_PATH, resume=False):
        self.path = path
        self.rows = []        # [(config_key, row)] in arrival order
        self.done = {}        # config_key -> status
        if resume and os.path.exists(path):
            with open(path) as f:
                for line in f:
                    try:
                        obj = json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn final line from a killed run
                    if "_done" in obj:
                        self.done[obj["_done"]] = obj.get("status", "ok")
                    elif "_reset" in obj:
                        # a later run retried this config: its earlier
                        # rows (failure marker included) are superseded
                        key = obj["_reset"]
                        self.done.pop(key, None)
                        self.rows = [(k, r) for k, r in self.rows
                                     if k != key]
                    elif "_row" in obj:
                        self.rows.append((obj.get("_cfg", "?"), obj["_row"]))
        elif os.path.exists(path):
            os.remove(path)
        self._f = open(path, "a")

    def add_rows(self, key, rows):
        for r in rows:
            self.rows.append((key, r))
            self._f.write(json.dumps({"_cfg": key, "_row": r}) + "\n")
        self._f.flush()

    def mark_done(self, key, status):
        self.done[key] = status
        self._f.write(json.dumps({"_done": key, "status": status}) + "\n")
        self._f.flush()

    def reset(self, key):
        """Forget a config's rows and completion marker (before a resume
        retries a previously-failed config)."""
        self.done.pop(key, None)
        self.rows = [(k, r) for k, r in self.rows if k != key]
        self._f.write(json.dumps({"_reset": key}) + "\n")
        self._f.flush()

    def all_rows(self):
        return [r for _, r in self.rows]


def _spawn_streaming(argv, extra_env, timeout):
    """Run a child, streaming stdout lines so a mid-run death keeps every
    completed measurement; returns (rows, status) with status in
    {"ok", "timeout", "crash"}. Rows are captured as they are emitted
    (_emit flushes one JSON line per row) and survive the kill — a
    child killed mid-device-op can sit in uninterruptible device I/O, so
    nothing here waits on it beyond a best-effort reap."""
    import threading

    env = dict(os.environ, **extra_env)
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__)] + argv,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO,
    )
    out_lines, err_chunks = [], []

    def _drain(stream, sink):
        for line in stream:
            sink.append(line)

    threads = [
        threading.Thread(target=_drain, args=(proc.stdout, out_lines),
                         daemon=True),
        threading.Thread(target=_drain, args=(proc.stderr, err_chunks),
                         daemon=True),
    ]
    for t in threads:
        t.start()
    timed_out = False
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.kill()  # best-effort; D-state children cannot be reaped
        try:
            proc.wait(timeout=10)  # reap the killed child (no zombie)
        except subprocess.TimeoutExpired:
            pass
    for t in threads:
        t.join(timeout=30)
    rows = []
    for l in out_lines:
        if not l.startswith("{"):
            continue
        try:
            rows.append(json.loads(l))
        except json.JSONDecodeError:
            pass  # SIGKILL mid-write truncates the final line; skip it
    if timed_out:
        print(f"[run_all] {' '.join(argv)} timed out after {timeout}s "
              f"with {len(rows)} completed rows. Child stderr tail:\n"
              + "".join(err_chunks[-30:]), file=sys.stderr)
        return rows, "timeout"
    if proc.returncode != 0:
        print(f"[run_all] {' '.join(argv)} child died rc={proc.returncode} "
              f"with {len(rows)} completed rows. Child stderr tail:\n"
              + "".join(err_chunks[-30:]), file=sys.stderr)
        return rows, "crash"
    return rows, "ok"


def _run_device_configs(state):
    """Each device config in its own subprocess: bounded retries, rows
    persisted as they land, and — the round-5 fix — a failure costs ONLY
    its config; the loop continues to the next one, naming the wedger in
    a per-config failure row."""
    attempts = int(os.environ.get("DNN_BENCH_CONFIG_ATTEMPTS", "2"))
    backoff = int(os.environ.get("DNN_BENCH_CONFIG_BACKOFF", "45"))
    # 1800 s: the longctx config alone compiles six decode programs
    # (3 legs x full+prefill-1) at 20-40 s each on a cold chip before
    # its timed runs even start
    timeout = int(os.environ.get("DNN_BENCH_CONFIG_TIMEOUT", "1800"))
    for name, _, _ in DEVICE_CONFIGS:
        key = f"device:{name}"
        if state.done.get(key) == "ok":
            print(f"[run_all] {name}: already ok (resume) — skipping",
                  file=sys.stderr)
            continue
        if key in state.done:
            # failed last run: a resume RETRIES it (that is the point of
            # resuming past a wedger) — supersede its salvage rows
            print(f"[run_all] {name}: failed last run — retrying",
                  file=sys.stderr)
            state.reset(key)
        best_rows, last_status = [], "unknown"
        for i in range(attempts):
            rows, status = _spawn_streaming(
                ["--section", "device", "--config", name], {}, timeout)
            if status == "ok":
                state.add_rows(key, rows)
                state.mark_done(key, "ok")
                break
            last_status = status
            if len(rows) >= len(best_rows):
                best_rows = rows
            more = i + 1 < attempts
            print(f"[run_all] config {name} attempt {i + 1}/{attempts} "
                  f"ended with {status} ({len(rows)} rows); "
                  + (f"retrying in {backoff}s" if more
                     else "salvaging and moving on"), file=sys.stderr)
            if more:
                time.sleep(backoff)
        else:
            # no attempt completed: keep the best partial rows and record
            # WHICH config failed — later configs still run
            best_rows.append({
                "config": name, "metric": "failed", "value": last_status,
                "platform": "meta",
                "note": (f"config {name!r} {last_status} on all "
                         f"{attempts} attempts; rows above it are "
                         "complete, later configs still ran — re-run "
                         "with --resume to retry only this one"),
            })
            state.add_rows(key, best_rows)
            state.mark_done(key, "failed")


def _run_cpu_mesh(state):
    key = "cpu_mesh"
    if state.done.get(key) == "ok":
        print("[run_all] cpu_mesh: already ok (resume) — skipping",
              file=sys.stderr)
        return
    if key in state.done:
        print("[run_all] cpu_mesh: failed last run — retrying",
              file=sys.stderr)
        state.reset(key)
    attempts = int(os.environ.get("DNN_BENCH_SECTION_ATTEMPTS", "2"))
    backoff = int(os.environ.get("DNN_BENCH_SECTION_BACKOFF", "60"))
    timeout = int(os.environ.get("DNN_BENCH_SECTION_TIMEOUT", "3600"))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=8").strip(),
    }
    best_rows, last_status = [], "unknown"
    for i in range(attempts):
        rows, status = _spawn_streaming(["--section", "cpu_mesh"], env,
                                        timeout)
        if status == "ok":
            state.add_rows(key, rows)
            state.mark_done(key, "ok")
            return
        last_status = status
        if len(rows) >= len(best_rows):
            best_rows = rows
        if i + 1 < attempts:
            time.sleep(backoff)
    best_rows.append({
        "config": "cpu_mesh_section", "metric": "truncated", "value": True,
        "platform": "meta",
        "note": (f"section {last_status} on all {attempts} attempts; the "
                 "rows above are complete measurements, later configs "
                 "are missing"),
    })
    state.add_rows(key, best_rows)
    state.mark_done(key, "failed")


# row-name -> device-config-name for configs that emit multiple /
# differently-named rows (used only when seeding resume state from an
# existing RESULTS.md; new configs that emit rows under their own name
# need no entry)
_ROW_TO_CONFIG = {
    "gpt2_fwd": "gpt_fwd", "gpt2-medium_fwd": "gpt_fwd",
    "tinyllama_decode_w_bf16_kv_bf16": "tinyllama_decode",
    "tinyllama_decode_w_int8_kv_int8": "tinyllama_decode",
    "llama_mha_longctx_decode_dense": "llama_longctx_decode",
    "llama_mha_longctx_decode_ring": "llama_longctx_decode",
    "gpt2_decode_w_f32_kv_f32": "gpt2_decode_matrix",
    "gpt2_decode_w_bf16_kv_bf16": "gpt2_decode_matrix",
    "gpt2_decode_w_int8_kv_bf16": "gpt2_decode_matrix",
    "gpt2_decode_w_int8_kv_int8": "gpt2_decode_matrix",
    "gpt2_decode_w_int4_kv_int8": "gpt2_decode_matrix",
    "gpt2_decode_attnkernel_w_bf16_kv_bf16": "gpt2_decode_attnkernel",
    "gpt2_decode_attnkernel_w_int8_kv_int8": "gpt2_decode_attnkernel",
    "speculative_int8_draft_greedy": "speculative_decode",
    "speculative_int8_draft_sampled": "speculative_decode",
    "speculative_int4_draft_greedy": "speculative_decode",
    "speculative_relative_greedy": "speculative_relative",
    "speculative_relative_sampled": "speculative_relative",
}


def seed_state_from_results(results_path=None, state_path=STATE_PATH):
    """Reconstruct .bench_rows.jsonl DEVICE-section entries from an
    existing RESULTS.md, so an OFF-CHIP host can `--resume` and refresh
    only what it can honestly measure (the cpu-mesh section plus
    cpu-runnable device configs) while the committed on-chip rows ride
    along UNCHANGED — each carried row gains a `provenance` detail
    naming the commit/date it was measured at, so old numbers can never
    masquerade as fresh ones. Without this, a full re-run on a CPU host
    would overwrite the tpu table with cpu-substrate values under the
    same config names — exactly the cross-substrate mixing bench.py's
    metric keys exist to prevent. Overwrites `state_path`."""
    import re

    results_path = results_path or os.path.join(REPO, "benchmarks",
                                                "RESULTS.md")
    with open(results_path) as f:
        text = f.read()
    head = re.search(r"Generated at commit `([^`]+)` on ([^;]+);", text)
    prov = (f"{head.group(1)} {head.group(2).strip()}" if head
            else "unknown")
    known = {name for name, _, _ in DEVICE_CONFIGS}
    seeded, done_keys = 0, []
    with open(state_path, "w") as out:
        for line in text.splitlines():
            cells = [c.strip() for c in line.split("|")][1:-1]
            if len(cells) != 6 or cells[0] in ("config", "---"):
                continue
            config, metric, value, mfu, platform, details = cells
            if platform in ("cpu-mesh", "cpu") or set(config) == {"-"}:
                # cpu-mesh AND cpu-substrate device rows refresh fresh —
                # carrying them "ok" would freeze exactly the rows this
                # host CAN honestly re-measure; separator rows skip
                continue
            if metric in ("failed", "skipped", "truncated"):
                # markers, not measurements: carrying one (and marking
                # its config ok) would pin a `failed | timeout` row in
                # the table forever while its own note says "re-run
                # with --resume to retry", and a carried `truncated`
                # note would keep asserting "later configs are missing"
                # after the refresh measures (or explicitly skips) them
                # — drop markers; the refresh re-establishes coverage
                continue
            if details.startswith("provenance="):
                # an already-carried row: keep its ORIGINAL measurement
                # stamp (restamping with this table's header commit
                # would let old numbers masquerade as fresh ones, and
                # the details cell would nest one level per cycle)
                emb, _, details = details.partition(", details=")
                row_prov = emb[len("provenance="):]
            else:
                row_prov = prov
            row = {"config": config, "metric": metric, "value": value,
                   "platform": platform, "provenance": row_prov}
            if details:
                row["details"] = details
            if mfu not in ("—", ""):
                try:
                    row["mfu"] = round(float(mfu.rstrip("%")) / 100, 4)
                except ValueError:
                    pass
            cfg_name = _ROW_TO_CONFIG.get(config, config)
            key = f"device:{cfg_name}" if cfg_name in known \
                else f"device:carried:{config}"
            out.write(json.dumps({"_cfg": key, "_row": row}) + "\n")
            seeded += 1
            if cfg_name in known and key not in done_keys:
                done_keys.append(key)
        for key in done_keys:
            out.write(json.dumps({"_done": key, "status": "ok"}) + "\n")
    print(f"[run_all] seeded {state_path} with {seeded} carried device "
          f"rows ({len(done_keys)} configs marked ok; provenance {prov}); "
          "now run with --resume", file=sys.stderr)
    return seeded


def _provenance():
    """Commit/date/platform stamp so a reader can always tell whether the
    table matches the harness that claims to produce it (round-3 lesson:
    RESULTS.md silently predated run_all.py's own additions)."""
    import datetime

    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=REPO, timeout=10).stdout.strip() or "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, cwd=REPO, timeout=10).stdout.strip()
        if dirty:
            rev += "-dirty"
    except Exception:
        rev = "unknown"
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%d %H:%M UTC")
    return rev, stamp


def write_results_md(rows, path):
    rev, stamp = _provenance()
    platforms = sorted({r.get("platform", "?") for r in rows
                        if r.get("platform") not in ("cpu-mesh", "meta")})
    lines = [
        "# Benchmark results (measured)",
        "",
        f"Generated at commit `{rev}` on {stamp}; device-section platform: "
        f"{', '.join(platforms) or 'none (device section skipped)'}.",
        "",
        "Produced by `python benchmarks/run_all.py`. The reference publishes",
        "no numbers (SURVEY §6); BASELINE.md maps these configs to its",
        "capability matrix. `cpu-mesh` rows run the multi-stage machinery on",
        "8 virtual CPU devices (no multi-chip TPU in this environment) — they",
        "validate the parallel path; absolute values are CPU-bound.",
        "",
        "| config | metric | value | mfu | platform | details |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        details = ", ".join(
            f"{k}={v}" for k, v in r.items()
            if k not in ("config", "metric", "value", "platform", "mfu")
        )
        mfu_cell = f"{r['mfu']:.1%}" if "mfu" in r else "—"
        lines.append(
            f"| {r['config']} | {r['metric']} | {r['value']} | {mfu_cell} | "
            f"{r['platform']} | {details} |"
        )
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


README_BEGIN = "<!-- PERF_TABLE:BEGIN (generated by benchmarks/run_all.py --sync-readme) -->"
README_END = "<!-- PERF_TABLE:END -->"


def sync_readme(results_path=None, readme_path=None):
    """Regenerate README.md's performance table FROM benchmarks/
    RESULTS.md (between the PERF_TABLE markers): the measurement
    commit/date are stamped from the table's own provenance header, and
    a staleness warning is emitted whenever HEAD differs from the bench
    commit — no hand-copied (hence silently aging) numbers in the README
    (VERDICT r5 weak #6/#8)."""
    results_path = results_path or os.path.join(REPO, "benchmarks",
                                                "RESULTS.md")
    readme_path = readme_path or os.path.join(REPO, "README.md")
    import re

    with open(results_path) as f:
        results = f.read()
    head = re.search(r"Generated at commit `([^`]+)` on ([^;]+);", results)
    bench_rev, bench_date = (head.group(1), head.group(2).strip()) if head \
        else ("unknown", "unknown")
    table = [l for l in results.splitlines() if l.startswith("|")]
    cur_rev, _ = _provenance()
    lines = [README_BEGIN, "",
             f"Measured at commit `{bench_rev}` ({bench_date}); generated "
             "from `benchmarks/RESULTS.md` — do not hand-edit this "
             "section.", ""]
    if cur_rev.replace("-dirty", "") != bench_rev.replace("-dirty", ""):
        lines += [
            f"> **Staleness warning:** HEAD is `{cur_rev}` but these "
            f"numbers were measured at `{bench_rev}` — re-run "
            "`python benchmarks/run_all.py` (or let a healthy-chip "
            "`bench.py` run refresh them) before quoting.", ""]
    lines += table + ["", README_END]
    with open(readme_path) as f:
        readme = f.read()
    if README_BEGIN not in readme or README_END not in readme:
        raise SystemExit(
            f"README markers not found; add {README_BEGIN!r} and "
            f"{README_END!r} around the perf table once")
    pre = readme.split(README_BEGIN)[0]
    post = readme.split(README_END, 1)[1]
    with open(readme_path, "w") as f:
        f.write(pre + "\n".join(lines) + post)
    return readme_path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", choices=["device", "cpu_mesh"])
    ap.add_argument("--config", help="one device config (child mode)")
    ap.add_argument("--resume", action="store_true",
                    help="skip configs already completed in "
                         "benchmarks/.bench_rows.jsonl")
    ap.add_argument("--out",
                    default=os.path.join(REPO, "benchmarks", "RESULTS.md"))
    ap.add_argument("--sync-readme", action="store_true",
                    help="regenerate README.md's perf table from the "
                         "existing RESULTS.md and exit (no measuring)")
    ap.add_argument("--seed-state", action="store_true",
                    help="reconstruct resume state from the existing "
                         "RESULTS.md device rows (marked with their "
                         "original provenance) and exit — an off-chip "
                         "host then refreshes only the sections it can "
                         "honestly measure via --resume")
    ap.add_argument("--scenarios", default=None,
                    help="run ONLY the workload suite: a comma list of "
                         "scenario names (or 'all') — each runs in its "
                         "own subprocess and lands in the row state "
                         "like any config, superseding its previous "
                         "row; RESULTS.md is NOT rewritten (a subset "
                         "run must not clobber the full table)")
    ap.add_argument("--require-substrate", choices=["tpu", "cpu"],
                    default=None,
                    help="substrate contract (PR 11's bench.py flag, "
                         "ROADMAP 5a): rows that honor it (the "
                         "fleet_serving probe) go ok=false when the "
                         "probe ran elsewhere — propagated to config "
                         "children via $DNN_TPU_REQUIRE_SUBSTRATE")
    args = ap.parse_args()

    if args.require_substrate:
        # children inherit the env (both the in-process config path and
        # the per-config subprocesses _spawn_streaming launches)
        os.environ["DNN_TPU_REQUIRE_SUBSTRATE"] = args.require_substrate

    if args.sync_readme:
        print(f"synced {sync_readme(results_path=args.out)}")
        return
    if args.seed_state:
        seed_state_from_results(results_path=args.out)
        return
    if args.section == "device":
        if args.config:
            run_device_config(args.config)
        else:
            run_device_section()
        return
    if args.section == "cpu_mesh":
        run_cpu_mesh_section()
        return

    if args.scenarios:
        known = {name for name, _, _ in DEVICE_CONFIGS}
        if args.scenarios.strip() == "all":
            sel = [f"workload_{s}" for s in WORKLOAD_SCENARIOS]
        else:
            sel = [s if s.startswith("workload_") else f"workload_{s}"
                   for s in (x.strip()
                             for x in args.scenarios.split(","))
                   if s]
        unknown = [s for s in sel if s not in known]
        if unknown:
            raise SystemExit(
                f"unknown scenario(s) {', '.join(unknown)}; known: "
                + ", ".join(s for s in WORKLOAD_SCENARIOS))
        # resume semantics against the existing row state, but the
        # SELECTED scenarios always re-measure (that is the point of
        # naming them). --require-substrate keeps its whole-round
        # meaning here too: the preflight row runs FIRST and gates the
        # subset run — without this, a scenario-only run would silently
        # drop the substrate contract the flag promises
        run_names = ((["substrate"] if args.require_substrate else [])
                     + sel)
        state = _State(resume=True)
        for name in run_names:
            state.reset(f"device:{name}")
        DEVICE_CONFIGS[:] = [c for c in DEVICE_CONFIGS
                             if c[0] in run_names]
        _run_device_configs(state)
        # judge ONLY the selected scenarios (a stale failing row from
        # an unselected one must not fail this run), and judge them by
        # the presence of an ok=True row — a child that crashed on all
        # attempts leaves a salvage meta-row with NO ok field, which
        # must read as failed, not green
        passed = {name: False for name in sel}
        for _, r in state.rows:
            if r.get("config") in passed and r.get("ok") is True:
                passed[r["config"]] = True
        bad = [name for name, good in passed.items() if not good]
        # the contract needs a POSITIVE substrate verdict: a preflight
        # child that crashed on every attempt leaves a salvage row with
        # no ok field, which must read as off-contract, not green
        if args.require_substrate and not any(
                r.get("config") == "substrate" and r.get("ok") is True
                for _, r in state.rows):
            bad.insert(0, "substrate (off-contract)")
        if bad:
            print("[run_all] scenario assert failed: " + ", ".join(bad),
                  file=sys.stderr)
            raise SystemExit(1)
        return

    state = _State(resume=args.resume)
    if args.resume and state.done:
        rev, _ = _provenance()
        print(f"[run_all] resuming with {len(state.done)} completed "
              f"configs at HEAD {rev}", file=sys.stderr)
    _run_device_configs(state)
    _run_cpu_mesh(state)
    write_results_md(state.all_rows(), args.out)
    sync_readme(results_path=args.out)
    print(f"wrote {args.out} (+ README perf table)")
    if args.require_substrate and not any(
            r.get("config") == "substrate" and r.get("ok") is True
            for r in state.all_rows()):
        # the preflight row IS the round gate (ROADMAP 5a): the table
        # is still written — honestly stamped — but the round fails.
        # Gated on a POSITIVE verdict: a crashed preflight child leaves
        # a salvage row with no ok field, which is not a pass
        raise SystemExit(1)


if __name__ == "__main__":
    main()
