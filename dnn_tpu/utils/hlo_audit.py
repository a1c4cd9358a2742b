"""Static HLO bytes audit for decode steps.

The question: does XLA materialize a cache-sized (transposed) copy per
decode step for the (B, H, 1, S) matvec layout of a dense cache — a 2x+
traffic multiplier at long context? This module answers it ON PAPER: `jax.jit(...).lower(...)` needs no healthy
backend (shapes ride `jax.eval_shape`, so even the 1.1B-parameter audit
costs no memory), and the resulting program text can be scanned for
cache-sized copies/transposes.

Two inspection levels, honestly distinct:

  * `optimize=False` — the StableHLO JAX emits. Platform-neutral: counts
    what the PROGRAM demands (an explicit transpose/copy of the cache in
    the traced math would be a framework bug, caught here).
  * `optimize=True` — the backend-optimized HLO after XLA's pipeline on
    THIS host's backend (CPU under the test suite). This is where
    materialization decisions live; a CPU count is a proxy for the TPU
    answer, and is labeled as such wherever it is recorded.

The counters are format-tolerant (StableHLO `tensor<8x12x256x64xf32>`
result types and classic HLO `f32[8,12,256,64]{...} opcode(...)` lines
alike), and "cache-sized" means >= one LAYER's K buffer — the layer scan
peels the leading L axis, so a per-step materialization shows up at
(B, H, S, D) scale while the hypothesis-(b) whole-cache copy shows up at
L times that. tests/test_hlo_audit.py pins both the parser and the
regression: the bucketed decode step lowers with ZERO cache-sized
transposes and ZERO cache-sized copies beyond the donated in-place
update.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

__all__ = ["lowered_text", "op_result_dims", "op_result_sizes",
           "count_cache_sized", "dus_updates", "count_aliased",
           "count_aliased_compiled", "gpt_decode_step", "llama_decode_step",
           "audit_decode_step"]

# `%3 = stablehlo.transpose %2 ... -> tensor<8x12x64x256xf32>` (the last
# tensor<...> on the line is the result type; rank-0 tensors have no dims)
_SHLO_OP = re.compile(r'=\s*"?(?:stablehlo|mhlo)\.([a-z_]+)')
_TENSOR = re.compile(r"tensor<((?:[0-9]+x)*)[a-z][a-z0-9]*>")
# `%copy.1 = f32[4,8,12,1040,64]{4,3,2,1,0} copy(...)`
_HLO_INST = re.compile(
    r"=\s*[a-z][a-z0-9]*\[([0-9,]*)\]\S*\s+([a-z][a-z0-9\-]*)\(")


def lowered_text(fn, *args, donate_argnums=(), optimize: bool = False) -> str:
    """Program text of jit(fn) at `args` (arrays OR ShapeDtypeStructs —
    pair with jax.eval_shape to audit shapes too big to build).
    optimize=False: the emitted StableHLO, no backend work; True: the
    backend-optimized HLO (compiles for THIS host's default backend)."""
    low = jax.jit(fn, donate_argnums=donate_argnums).lower(*args)
    if not optimize:
        return low.as_text()
    compiled = low.compile()
    return "\n".join(m.to_string() for m in compiled.runtime_executable()
                     .hlo_modules()) if hasattr(
        compiled, "runtime_executable") else compiled.as_text()


def op_result_dims(text: str):
    """[(opcode, result dims)] for every op in StableHLO or HLO text (see
    module docstring for the two formats)."""
    rows = []
    for line in text.splitlines():
        m = _SHLO_OP.search(line)
        if m:
            tensors = _TENSOR.findall(line)
            if tensors:
                rows.append((m.group(1), _dims(tensors[-1], "x")))
            continue
        m = _HLO_INST.search(line)
        if m:
            rows.append((m.group(2), _dims(m.group(1), ",")))
    return rows


def _dims(text: str, sep: str):
    return tuple(int(d) for d in text.split(sep) if d)


def op_result_sizes(text: str):
    """[(opcode, result_elem_count)] for every op in the text."""
    return [(op, math.prod(dims)) for op, dims in op_result_dims(text)]


def count_aliased(text: str) -> int:
    """Donated-input count in StableHLO program text: jit emits one
    `tf.aliasing_output` attribute per input buffer it aliases to an
    output. An arg passed via donate_argnums but NOT counted here was
    unusable (no shape/dtype-matching output) — the runtime pays a full
    copy of it per call. Consumed by the analyzer's donation-coverage
    check (dnn_tpu/analysis/program.donation_report)."""
    return text.count("tf.aliasing_output")


_ALIAS_PAIR = re.compile(r"\{[0-9,\s]*\}:\s*\(\d+,")


def count_aliased_compiled(hlo_text: str) -> int:
    """Donation aliasing at the COMPILED level: under GSPMD shardings
    jit lowers donations as `jax.buffer_donor` hints (no
    tf.aliasing_output at the StableHLO level — the aliasing decision
    belongs to XLA once partitioning is resolved), and the verdict lands
    in the optimized HLO's `input_output_alias={ {out}: (arg, ...) }`
    header. Counts those pairs; a donated sharded buffer missing here
    pays a full per-device copy every step. Consumed by the analyzer's
    sharded-donation check (dnn_tpu/analysis/shardcheck)."""
    m = re.search(r"input_output_alias=\{(.*?)\}\s*(?:\n|,\s*[a-z_]+=)",
                  hlo_text, re.S)
    if not m:
        return 0
    return len(_ALIAS_PAIR.findall(m.group(1)))


def count_cache_sized(text: str, min_elems: int,
                      ops: Sequence[str] = ("transpose", "copy"),
                      ) -> Dict[str, int]:
    """{opcode: count} of ops whose RESULT is at least `min_elems`
    elements — each one a cache-scale buffer the program materializes."""
    counts: Dict[str, int] = {}
    for op, n in op_result_sizes(text):
        if n >= min_elems and op in ops:
            counts[op] = counts.get(op, 0) + 1
    return counts


def dus_updates(text: str):
    """[(operand dims, update dims)] of every `dynamic_update_slice` in
    StableHLO text: what each in-place write MOVES is its update — the
    result has the operand's extent whatever was written (`stablehlo.
    dynamic_update_slice %a, %u, %i... : (tensor<a>, tensor<u>, ...)`)."""
    rows = []
    for line in text.splitlines():
        m = _SHLO_OP.search(line)
        if m and m.group(1) == "dynamic_update_slice":
            rows.append(tuple(_dims(t, "x") for t in _TENSOR.findall(
                line.split(" : ", 1)[1])[:2]))
    return rows


# ----------------------------------------------------------------------
# decode-step builders (abstract shapes — no weights are ever built)
# ----------------------------------------------------------------------

def _abstract(thunk):
    return jax.eval_shape(thunk)


def gpt_decode_step(cfg, *, batch: int, s_max: int, compute_dtype=None,
                    kv_dtype=None, attn_kernel=False):
    """(step_fn, abstract_args, layer_cache_elems) for ONE GPT-family
    decode step — the make_generate scan body at a traced position:
    step(prepared, cache, tok, pos) -> (last-token logits, cache)."""
    from dnn_tpu.models import gpt
    from dnn_tpu.runtime import generate as G

    def step(prepared, cache, tok, pos):
        logits, cache = G.forward_with_cache(
            prepared, tok[:, None], cache, pos, cfg=cfg,
            compute_dtype=compute_dtype, attn_kernel=attn_kernel)
        return logits[:, -1], cache

    cache_dtype = kv_dtype if kv_dtype is not None else (
        compute_dtype or jnp.float32)
    key = jax.random.PRNGKey(0)
    prepared = _abstract(
        lambda: gpt.prepare_stacked(gpt.init(key, cfg), cfg))
    cache = _abstract(lambda: G.init_cache(cfg, batch, s_max, cache_dtype))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    layer_elems = batch * cfg.n_head * s_max * (cfg.n_embd // cfg.n_head)
    return step, (prepared, cache, tok, pos), layer_elems


def llama_decode_step(cfg, *, batch: int, s_max: int, compute_dtype=None,
                      kv_dtype=None, attn_kernel=False):
    """Same contract for the LLaMA family (GQA cache at KV-head width) —
    the family behind the 13%-MBU row (run with an MHA-width cfg to
    reproduce that exact shape)."""
    from dnn_tpu.models import gpt, llama

    def step(prepared, cache, tok, pos):
        logits, cache = llama.forward_with_cache(
            prepared, tok[:, None], cache, pos, cfg=cfg,
            compute_dtype=compute_dtype, attn_kernel=attn_kernel)
        return logits[:, -1], cache

    cache_dtype = kv_dtype if kv_dtype is not None else (
        compute_dtype or jnp.float32)
    key = jax.random.PRNGKey(0)
    prepared = _abstract(
        lambda: gpt.prepare_stacked(
            llama.init(key, cfg, dtype=compute_dtype or jnp.float32), cfg))
    cache = _abstract(
        lambda: llama.init_cache(cfg, batch, s_max, cache_dtype))
    tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    layer_elems = batch * cfg.n_kv_head * s_max * cfg.head_dim
    return step, (prepared, cache, tok, pos), layer_elems


def audit_decode_step(step_fn, args, layer_cache_elems, *,
                      optimize: bool = False, donate_cache: bool = True,
                      ops: Sequence[str] = ("transpose", "copy")) -> dict:
    """Lower one decode step and count cache-sized materializations.
    `donate_cache=True` marks the cache argument (position 1) donated, as
    every real decode loop does — without it the cache update itself
    legitimately copies and the count answers a question nobody asked."""
    text = lowered_text(step_fn, *args,
                        donate_argnums=(1,) if donate_cache else (),
                        optimize=optimize)
    counts = count_cache_sized(text, layer_cache_elems, ops=ops)
    return {
        "counts": counts,
        "total": sum(counts.values()),
        "min_elems": layer_cache_elems,
        "optimized": bool(optimize),
        "backend": jax.default_backend() if optimize else "none (StableHLO)",
    }


def _main():
    """The long-context audit at one dense decode-step shape
    (TinyLlama widened to MHA, B=8, S=1536),
    StableHLO level plus this host's optimized HLO."""
    import dataclasses
    import json

    from dnn_tpu.models import llama

    mha_cfg = dataclasses.replace(
        llama.PRESETS["tinyllama-1.1b"],
        n_kv_head=llama.PRESETS["tinyllama-1.1b"].n_head, block_size=2048)
    step, args, layer = llama_decode_step(
        mha_cfg, batch=8, s_max=1536, compute_dtype=jnp.bfloat16,
        kv_dtype=jnp.bfloat16)
    out = {"shape": "tinyllama-mha B=8 S=1536 bf16",
           "stablehlo": audit_decode_step(step, args, layer),
           "optimized": audit_decode_step(step, args, layer,
                                          optimize=True)}
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    _main()
