"""Device-free program pass: jaxpr/lowering checks on REAL entrypoints.

Where analysis/lint.py reads source, this module reads programs: it
traces the framework's actual entrypoints (engine predict, the solo and
bucketed decode steps, the SPMD pipeline from parallel/pipeline.py) with
abstract shapes — `jax.eval_shape` avals, `jax.make_jaxpr`,
`jax.jit(...).lower(...)` — so auditing a 1.1B-parameter decode step
costs no weights, no devices, and no compile. It extends
utils/hlo_audit.py (which answers "does the lowered step copy the
cache?") with four whole-program questions:

  PRG001  do cond/switch branches issue identical collective sequences?
          (the jaxpr-level SPMD-deadlock check — catches dynamically
          built branch lists, e.g. spmd_pipeline's per-stage
          `lax.switch`, that the AST pass cannot resolve)
  PRG002  are allocation-sized constants baked into the program?
          (a closed-over concrete array = a private copy per compile)
  PRG003  do decode steps donate their cache? (aliasing audit on the
          lowered StableHLO — an undonated cache is a full copy/step)
  PRG004  how many distinct programs does a shape sweep compile?
          (recompile census; the bucketed decode must stay within its
          ladder bound)

CPU-only by design: jit signatures are (avals + static args), identical
on every backend, and StableHLO aliasing annotations are emitted before
any backend pipeline runs — so every verdict here transfers to TPU.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dnn_tpu.analysis.findings import Finding, assign_occurrences
from dnn_tpu.utils.hlo_audit import (
    count_aliased,
    count_cache_sized,
    dus_updates,
    gpt_decode_step,
    lowered_text,
    op_result_dims,
)

__all__ = [
    "collective_signature", "axis_collective_signature",
    "check_branch_collectives", "baked_constants",
    "donation_report", "recompile_census", "audit_decode_paths",
    "audit_serving_decode", "audit_pipeline_programs", "audit_engine",
    "check_decode_program", "check_chunk_program", "chunk_args",
    "run_program_audit",
]

_COLLECTIVE_PRIMS = {
    "psum", "ppermute", "all_gather", "all_to_all", "psum_scatter",
    "pmin", "pmax", "reduce_scatter", "collective_permute", "pgather",
    "all_gather_invariant", "psum_invariant",
}
# branch-holding / body-holding primitive params to recurse into
_SUBJAXPR_PARAMS = ("branches", "jaxpr", "call_jaxpr", "cond_jaxpr",
                    "body_jaxpr", "fun_jaxpr")


def _sub_jaxprs(eqn):
    """(param_name, jaxpr) pairs for every sub-program of one equation."""
    out = []
    for name in _SUBJAXPR_PARAMS:
        v = eqn.params.get(name)
        if v is None:
            continue
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for sub in vs:
            j = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
            if hasattr(j, "eqns"):
                out.append((name, j))
    return out


def collective_signature(jaxpr) -> Tuple[str, ...]:
    """Ordered tuple of collective primitive names in a jaxpr, recursing
    into scan/while/pjit/cond sub-programs in equation order. Two SPMD
    programs with different signatures cannot be deadlock-free on the
    same mesh step."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[str] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVE_PRIMS:
            out.append(eqn.primitive.name)
        for _, sub in _sub_jaxprs(eqn):
            out.extend(collective_signature(sub))
    return tuple(out)


def _eqn_axes(eqn) -> Tuple[str, ...]:
    """The mesh axes one collective equation operates over. psum-family
    primitives carry `axes`; gather/permute/scatter carry `axis_name`
    (either may be a bare name or a tuple)."""
    v = eqn.params.get("axes", eqn.params.get("axis_name"))
    if v is None:
        return ()
    if not isinstance(v, (tuple, list)):
        v = (v,)
    return tuple(str(a) for a in v)


def axis_collective_signature(jaxpr) -> Tuple[str, ...]:
    """collective_signature with the mesh axes each collective operates
    over: `psum@data`, `ppermute@stage`, ... Two branches can agree on
    primitive NAMES while reducing over different axes — that still
    deadlocks a real mesh, so PRG001 compares THIS signature."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[str] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVE_PRIMS:
            axes = ",".join(_eqn_axes(eqn))
            out.append(f"{eqn.primitive.name}@{axes}" if axes
                       else eqn.primitive.name)
        for _, sub in _sub_jaxprs(eqn):
            out.extend(axis_collective_signature(sub))
    return tuple(out)


def check_branch_collectives(jaxpr, where: str = "<program>"
                             ) -> List[Finding]:
    """PRG001: walk a jaxpr; at every cond/switch equation, compare the
    MESH-AXIS-AWARE collective signature of each branch. The stage
    programs of spmd_pipeline ARE these branches (lax.switch on the
    stage coord), so this is the 'collective sequences identical across
    pipeline stage programs' check of the paper-scale SPMD contract —
    and since ISSUE 17 it also fails two branches that agree on
    primitive names but reduce over DIFFERENT mesh axes (a dropped or
    re-axed psum deadlocks ranks just the same)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    findings: List[Finding] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            sigs = [axis_collective_signature(b)
                    for b in eqn.params.get("branches", ())]
            if len(set(sigs)) > 1:
                detail = " vs ".join(
                    "(" + (", ".join(s) or "none") + ")" for s in sigs)
                findings.append(Finding(
                    rule="PRG001", path=where, line=0,
                    message=f"cond/switch branches have different "
                            f"collective sequences: {detail}",
                    snippet=f"branches={len(sigs)}"))
        for _, sub in _sub_jaxprs(eqn):
            findings.extend(check_branch_collectives(sub, where))
    return findings


def baked_constants(closed_jaxpr, *, min_bytes: int = 1 << 20,
                    where: str = "<program>") -> List[Finding]:
    """PRG002: constants (closed-over concrete arrays) at allocation
    scale. Weights and caches must arrive as ARGUMENTS — a baked const
    is copied into every compiled executable that closes over it."""
    findings = []
    for c in getattr(closed_jaxpr, "consts", ()):
        nbytes = getattr(c, "nbytes", None)
        if nbytes is None and hasattr(c, "size"):
            nbytes = int(np.asarray(c).nbytes)
        if nbytes and nbytes >= min_bytes:
            findings.append(Finding(
                rule="PRG002", path=where, line=0,
                message=f"program bakes a {nbytes/1e6:.1f} MB constant "
                        f"(shape {getattr(c, 'shape', '?')}); pass it as "
                        "an argument instead of closing over it",
                snippet=f"const{tuple(getattr(c, 'shape', ()))}"))
    return findings


def donation_report(fn, args, donate_argnums: Sequence[int],
                    *, where: str = "<program>",
                    expect_aliased: Optional[int] = None) -> dict:
    """PRG003: lower jit(fn, donate_argnums=...) at `args` (arrays or
    ShapeDtypeStructs) and count aliased inputs in the StableHLO
    (`tf.aliasing_output` annotations). Returns
    {aliased, expected, findings}; a gap means the runtime pays a full
    copy of every un-aliased donated buffer per step."""
    text = lowered_text(fn, *args, donate_argnums=tuple(donate_argnums))
    aliased = count_aliased(text)
    if expect_aliased is None:
        expect_aliased = sum(
            len(jax.tree.leaves(args[i])) for i in donate_argnums)
    findings = []
    if aliased < expect_aliased:
        findings.append(Finding(
            rule="PRG003", path=where, line=0,
            message=f"only {aliased}/{expect_aliased} donated buffers "
                    "are aliased to outputs in the lowered program — "
                    "un-aliased donations copy every step",
            snippet=f"aliased={aliased} expected={expect_aliased}"))
    return {"aliased": aliased, "expected": expect_aliased,
            "findings": findings}


# ----------------------------------------------------------------------
# recompile census
# ----------------------------------------------------------------------

def _aval_signature(args) -> Tuple:
    """What jit keys its program cache on (per arg: shape+dtype, plus
    the declared sharding when the aval carries one — identical avals
    under DIFFERENT shardings compile different partitioned programs,
    so the sharded-program census must count them separately)."""
    leaves = jax.tree.leaves(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            jnp.shape(l), getattr(l, "dtype", jnp.result_type(l)),
            sharding=getattr(l, "sharding", None)), args),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return tuple((tuple(l.shape), str(l.dtype),
                  str(l.sharding) if l.sharding is not None else None)
                 for l in leaves)


def recompile_census(arg_sets: Sequence[Tuple], *, bound: Optional[int]
                     = None, where: str = "<program>") -> dict:
    """PRG004: distinct jit program signatures across a shape sweep.
    `arg_sets` is a sequence of argument tuples (arrays or
    ShapeDtypeStructs); the census counts unique aval signatures — one
    compile each. `bound` asserts the documented program-count ceiling
    (e.g. the bucket ladder length)."""
    sigs = {}
    for args in arg_sets:
        sigs.setdefault(_aval_signature(args), []).append(args)
    report = {"calls": len(arg_sets), "programs": len(sigs),
              "bound": bound, "findings": []}
    if bound is not None and len(sigs) > bound:
        report["findings"].append(Finding(
            rule="PRG004", path=where, line=0,
            message=f"shape sweep compiles {len(sigs)} distinct programs,"
                    f" over the documented bound {bound}",
            snippet=f"programs={len(sigs)} bound={bound}"))
    return report


# ----------------------------------------------------------------------
# entrypoint audits
# ----------------------------------------------------------------------

def _tiny_gpt_cfg():
    from dnn_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=64, block_size=128, n_layer=2, n_head=2,
                     n_embd=32)


def audit_decode_paths(cfg=None, *, batch: int = 2,
                       max_len: int = 128) -> dict:
    """Solo + bucketed decode steps (runtime/generate.py,
    runtime/decode_buckets.py): donation coverage, baked constants, and
    the recompile census that certifies the PR-1 bucketing contract —
    decode programs bounded by the LADDER length, vs one program per
    live length for exact-shape dispatch.
    """
    from dnn_tpu.runtime.decode_buckets import bucket_for, bucket_ladder

    cfg = cfg or _tiny_gpt_cfg()
    findings: List[Finding] = []

    step, args, layer_elems = gpt_decode_step(
        cfg, batch=batch, s_max=max_len)

    # PRG003: the decode step must alias its donated cache leaves
    don = donation_report(step, args, (1,),
                          where="runtime/generate.decode_step")
    findings += don["findings"]

    # PRG002: nothing cache- or weight-scale may be baked in
    closed = jax.make_jaxpr(step)(*args)
    findings += baked_constants(
        closed, min_bytes=max(layer_elems * 4, 1 << 20),
        where="runtime/generate.decode_step")

    # hlo_audit extension: the StableHLO must not transpose/copy the
    # cache outside the donated in-place update (PR-1 regression, now
    # part of the standing audit)
    text = lowered_text(step, *args, donate_argnums=(1,))
    copies = count_cache_sized(text, layer_elems)
    if copies:
        # hardened from transpose-only (ISSUE 6): with the cache donated,
        # the StableHLO must carry ZERO cache-sized copies too — a copy
        # here is a program-demanded materialization no backend can elide
        findings.append(Finding(
            rule="PRG002", path="runtime/generate.decode_step", line=0,
            message=f"decode step materializes cache-sized op(s) in "
                    f"StableHLO beyond the donated in-place update: "
                    f"{copies}",
            snippet=str(copies)))

    # PRG004: bucketed decode — simulate a generate() from prompt 8 to
    # max_len and count the step programs the bucket dispatch compiles.
    # Cache avals for each live length derive from the max_len template
    # (position axis 3, the codec layout contract) — one eval_shape
    # total instead of one per swept length.
    def at_len(n):
        prepared_s, cache_s, tok_s, pos_s = args

        def resize(l):
            s = list(l.shape)
            s[3] = n
            return jax.ShapeDtypeStruct(tuple(s), l.dtype)

        return (prepared_s, jax.tree.map(resize, cache_s), tok_s, pos_s)

    ladder = bucket_ladder(max_len)
    prompt = 8
    sweep = range(prompt, max_len - 1)
    census = recompile_census(
        [at_len(bucket_for(ladder, pos + 1)) for pos in sweep],
        bound=len(ladder),
        where="runtime/decode_buckets.make_bucketed_generate")
    findings += census["findings"]

    naive = recompile_census(
        [at_len(pos + 1) for pos in sweep],
        where="naive exact-length dispatch (counterfactual)")

    return {
        "donation": {k: don[k] for k in ("aliased", "expected")},
        "stablehlo_cache_ops": copies,
        "bucketed_census": {k: census[k]
                            for k in ("calls", "programs", "bound")},
        "naive_census": {k: naive[k] for k in ("calls", "programs")},
        "ladder": list(ladder),
        "findings": findings,
    }


def check_decode_program(name, jit_fn, args, donate_idx, layer_elems,
                         *, where_prefix: str = "runtime/serving.decode"
                         ) -> Tuple[dict, List[Finding]]:
    """Lower ONE serving decode-family program at `args` and apply the
    ISSUE 6 gate to it: (a) every leaf of every donated arg must be
    aliased to an output in the StableHLO (an un-aliased donation is a
    silent full copy per step), and (b) zero cache-sized copies/
    transposes beyond the aliased in-place update. Module-level so the
    gate itself is testable: tests/test_overlap.py lowers a
    deliberately un-aliased mixed-step variant through this helper and
    asserts the findings fire."""
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), args)
    text = jit_fn.lower(*avals).as_text()
    aliased = count_aliased(text)
    expected = sum(len(jax.tree.leaves(args[i])) for i in donate_idx)
    where = f"{where_prefix}[{name}]"
    findings: List[Finding] = []
    if aliased < expected:
        findings.append(Finding(
            rule="PRG003", path=where, line=0,
            message=f"only {aliased}/{expected} donated buffers are "
                    "aliased to outputs — un-aliased donations copy "
                    "every decode step",
            snippet=f"{name}: aliased={aliased} expected={expected}"))
    copies = count_cache_sized(text, layer_elems)
    if copies:
        findings.append(Finding(
            rule="PRG003", path=where, line=0,
            message=f"decode step materializes cache-sized op(s) "
                    f"beyond the donated in-place update: {copies}",
            snippet=f"{name}: {copies}"))
    return ({"aliased": aliased, "expected": expected,
             "cache_sized_ops": copies}, findings)


def check_chunk_program(name, jit_fn, args, state_leaves=()
                        ) -> Tuple[dict, List[Finding]]:
    """`check_decode_program`'s two rules turned to ONE prefill-chunk
    program (`b._prefill_chunk` at `chunk_args(b)`: its second argument is
    the donated transient row {leaf: (L, 1, H, S[, D])}): (a) every leaf
    of the row aliases a result, and (b) a chunk moves a chunk's positions
    — beyond the layer's one read (a `dynamic_slice`) the lowered text
    holds nothing of a layer's row or more: no copy, transpose or
    concatenate whose result is a layer's row of a leaf, or layers of
    them, and no `dynamic_update_slice` whose UPDATE is a leaf's whole
    layer or a layer row's elements (the row riding the layer loop as xs /
    ys writes each layer's whole cut-out back; `paged_kvcache.scan_rows`
    carries it and a block writes T positions). The exception is in the
    rule, not in a baseline: a state leaf (`state_leaves`: no position
    axis, models/state_kind.py) IS its layer's whole content and is written
    whole at the layer's index."""
    row = args[1]
    # shapes a caller describes (for a device it names) stay as they are
    avals = jax.tree.map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), args)
    text = jit_fn.lower(*avals).as_text()
    aliased, expected = count_aliased(text), len(jax.tree.leaves(row))
    where = f"runtime/serving.prefill_chunk[{name}]"
    findings: List[Finding] = []
    if aliased < expected:
        findings.append(Finding(
            rule="PRG003", path=where, line=0,
            message=f"only {aliased}/{expected} leaves of the donated "
                    "transient row are aliased to outputs — an un-aliased "
                    "leaf is copied every chunk",
            snippet=f"{name}: aliased={aliased} expected={expected}"))
    state = {tuple(row[n].shape) for n in state_leaves if n in row}
    positional = {tuple(x.shape): int(np.prod(x.shape[1:]))
                  for n, x in row.items() if n not in state_leaves}
    layer_elems = max(positional.values()) if positional else max(
        int(np.prod(x.shape[1:])) for x in row.values())
    # a layer's row, or layers of them: a result whose trailing extents
    # are a leaf's (a latent row's up-projection is larger than the row
    # and is the model's own arithmetic)
    rows_of = {shape[1:] for shape in positional}
    moved: Dict[str, int] = {}
    for op, dims in op_result_dims(text):
        if op in ("transpose", "copy", "concatenate") and any(
                dims[-len(r):] == r for r in rows_of):
            moved[op] = moved.get(op, 0) + 1
    whole = sum(
        1 for operand, update in dus_updates(text) if operand not in state
        and int(np.prod(update)) >= positional.get(operand, layer_elems))
    if whole:
        moved["dynamic_update_slice"] = whole
    if moved:
        findings.append(Finding(
            rule="PRG003", path=where, line=0,
            message=f"prefill chunk moves a layer's whole row (or more) "
                    f"beyond the layer's one read: {moved}",
            snippet=f"{name}: {moved}"))
    return ({"aliased": aliased, "expected": expected,
             "cache_sized_ops": moved}, findings)


def chunk_args(b) -> tuple:
    """The argument tuple of a ContinuousBatcher's prefill-chunk program
    (`b._prefill_chunk`): the prefill view, a fresh transient row (as
    shapes), one (1, prompt_pad) chunk, its start and — for a family that
    keeps a state — its count of real positions."""
    return (b._lora_prefill_view(0), jax.eval_shape(b._new_row),
            jnp.zeros((1, b.prompt_pad), jnp.int32), np.int32(0),
            *b._n_real(b.prompt_pad, 0))


def decode_step_args(b) -> tuple:
    """The argument tuple of a ContinuousBatcher's decode-step program
    (`b._decode`), from the batcher's own state. The one place the audit
    — and any test that lowers these programs — spells the signature."""
    return (b._decode_view, b.cache, b.pos, b.tok, b.active, b.keys,
            b._temp, b._topk, b._topp, b._minp, b._rep, b._seen,
            b._bias, b._crow, b._ctable, b._ctrans)


def mixed_step_args(b, chunk_tokens: int) -> tuple:
    """The argument tuple of the mixed-step program (`b._mixed`): the
    decode step's, with the prefill view first and a fresh row cache, one
    (1, chunk_tokens) prompt chunk and its start position last."""
    base = decode_step_args(b)
    return (base[0], base[0]) + base[1:] + (
        b._ilv_new_row(), jnp.zeros((1, chunk_tokens), jnp.int32),
        jnp.int32(0))


def finish_args(b, chunk_tokens: int) -> tuple:
    """The argument tuple of the finish-and-install program
    (`b._prefill_finish`, which ends every admission): the batcher's
    state, a fresh row cache, one chunk's hidden rows, the head's leaves,
    and a request's two number arrays, seen-mask, bias row and block
    ids."""
    v = b.cfg.vocab_size
    row = b._ilv_new_row() if b._ilv else b._new_row()
    blocks = (np.zeros((2, b.cache["tables"].shape[-1]), np.int32)
              if b._paged else b._no_blocks)
    return b._slot_state() + (
        row, jnp.zeros((1, chunk_tokens, b.cfg.n_embd), jnp.float32),
        b.family.head_leaves(b.prepared),
        np.zeros((7,), np.int32), np.ones((4,), np.float32),
        np.zeros((v,), np.bool_), b._no_bias, blocks,
        b._ctable, b._ctrans)


# name -> ContinuousBatcher options of each cache layout the decode-step
# gate lowers (tests/test_constrained_hotpath.py lowers the constrained
# ones again, to hold the step to `slots` rows of the mask pool)
SERVING_DECODE_VARIANTS = {
    "dense_f32": {},
    "dense_int8": {"kv_dtype": "int8"},
    "dense_int4": {"kv_dtype": "int4"},
    "bucketed": {"decode_buckets": True},
    "paged": {"kv": "paged"},
    # constrained decoding (ISSUE 16): the grammar DFA walk is carried
    # device state — crow joins the donate set, and the gate must see it
    # aliased (an un-aliased crow would copy per step). The pools are
    # read-only: the (S, W) uint32 bit-packed mask pool is read as one
    # one-row dynamic_slice a slot and the (S, V) int32 ctrans pool by a
    # one-word gather a slot, and neither may appear as a cache-sized copy
    "dense_constrained": {"allow_constraints": True, "constraint_rows": 8},
    "paged_constrained": {"kv": "paged", "allow_constraints": True,
                          "constraint_rows": 8},
}


#: the test presets of the families that prefill through
#: `llama.prefill_by_kind`: JoyAI, dots3, K-EXAONE, Solar Open 2, Brumby,
#: Falcon-H1, MiniCPM-SALA, Mellum2
CHUNK_BY_KIND_PRESETS = (
    "joyai-test", "dots3-test", "k-exaone-test", "solar-open2-test",
    "brumby-test", "falcon-h1-test", "minicpm-sala-test", "mellum2-test")


def audit_serving_decode(cfg=None, *, slots: int = 2,
                         max_len: int = 128) -> dict:
    """ISSUE 6 donation-coverage GATE over the SERVING decode programs:
    every cache layout the batcher ships (dense f32 / int8 / int4,
    bucketed, paged) plus the speculative step, each lowered at its live
    donate_argnums and checked for (a) FULL aliasing of every donated
    leaf — an un-aliased donation is a silent full copy per step
    (hlo_audit.count_aliased; PRG003) — and (b) ZERO cache-sized
    copies/transposes in the StableHLO beyond the aliased in-place
    update (the PR-1 three-copies-per-step diagnosis, now failed-on
    rather than documented). Exceptions go through the justified
    baseline like every other finding — there are none today.

    Constructor-only cost: the batchers are built at test-preset size
    and their step programs LOWERED (traced), never compiled or run."""
    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = cfg or _tiny_gpt_cfg()
    prepared = gpt.prepare_stacked(
        gpt.init(jax.random.PRNGKey(0), cfg), cfg)
    findings: List[Finding] = []
    report: Dict[str, dict] = {}

    def lower_and_check(name, jit_fn, args, donate_idx, layer_elems):
        entry, f = check_decode_program(name, jit_fn, args, donate_idx,
                                        layer_elems)
        findings.extend(f)
        report[name] = entry

    def check_chunk(name, b):
        # ISSUE 63 — the chunk program that every admission loops: its
        # donated transient row aliases leaf for leaf and a chunk writes
        # a chunk's positions (check_chunk_program)
        entry, f = check_chunk_program(name + "_chunk", b._prefill_chunk,
                                       chunk_args(b), b._slot_leaves)
        findings.extend(f)
        report[name + "_chunk"] = entry

    hd = cfg.n_embd // cfg.n_head
    for name, kw in SERVING_DECODE_VARIANTS.items():
        b = ContinuousBatcher(cfg, prepared, slots=slots, max_len=max_len,
                              prompt_pad=16, **kw)
        if b._paged:
            layer_elems = (b._allocator.n_blocks * cfg.n_head
                           * b._block_len * hd)
        else:
            layer_elems = slots * cfg.n_head * b._cache_len * hd
        # donated argnums mirror serving.py's jit construction
        # (cache, pos, tok, keys, seen — plus crow when constrained)
        lower_and_check(name, b._decode, decode_step_args(b),
                        b._decode_donate, layer_elems)
        # ISSUE 34 — the finish-and-install program that ends every
        # admission: the pool AND every per-slot vector it sets are
        # donated, and each must alias (an un-aliased one is a copy an
        # admission, and the eager scatters it replaced come back)
        lower_and_check(name + "_finish", b._prefill_finish,
                        finish_args(b, 16), b._finish_donate, layer_elems)
        check_chunk(name, b)

    # the same program over the other served families' paged pools: a
    # LLaMA-MoE (OLMoE's test preset) and one whose pool has a third leaf
    # (Keye's: the index key installs and aliases with K and V)
    from dnn_tpu.registry import get_model

    def family_batcher(preset, prompt_pad=16, **kw):
        spec = get_model(preset)
        return ContinuousBatcher(
            spec.config,
            gpt.prepare_stacked(dict(spec.init(jax.random.PRNGKey(0))),
                                spec.config),
            slots=slots, prompt_pad=prompt_pad,
            family=spec.extras["family_rows"](), **kw)

    for name, preset in {"paged_olmoe": "olmoe-test",
                         "paged_keye": "keye-test"}.items():
        b = family_batcher(preset, max_len=64, kv="paged",
                           allow_logit_bias=True, allow_constraints=True,
                           constraint_rows=8)
        lower_and_check(
            name + "_finish", b._prefill_finish, finish_args(b, 16),
            b._finish_donate,
            max(int(np.prod(x.shape[1:])) for kk, x in b.cache.items()
                if kk != "tables"))
        check_chunk(name, b)

    # the chunk program of the families whose transient row's leaves are
    # BY LAYER KIND (`llama.prefill_by_kind`: latents, K and V of two
    # kinds, a strided leaf, state leaves beside K and V or alone), each
    # stack over its own range of the one carried row; rows of sixteen
    # chunks, so that a layer's row is larger than any activation
    for preset in CHUNK_BY_KIND_PRESETS:
        # a chunk is one block: MiniCPM-SALA's must be its selection's
        bl = getattr(get_model(preset).config, "block_select", None)
        bl = 16 if bl is None else bl.block
        check_chunk(preset, family_batcher(
            preset, prompt_pad=bl, max_len=16 * bl, kv="auto",
            block_len=bl))

    # the speculative step (serving_spec.py): both caches + the per-slot
    # vectors it returns must all alias
    from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

    sb = SpeculativeBatcher(cfg, prepared, cfg, prepared, spec_k=2,
                            slots=slots, max_len=max_len, prompt_pad=16)
    sp_args = (sb.prepared, sb.draft_prepared, sb.cache, sb.d_cache,
               sb.tok, sb.pos, sb.active, sb.keys, sb.prev_chunk,
               sb.prev_pos)
    lower_and_check("speculative", sb._spec_step, sp_args,
                    (2, 3, 4, 5, 7, 8, 9),
                    slots * cfg.n_head * max_len * hd)

    # ISSUE 12 — the mixed-step programs: interleaved chunked prefill
    # folds a prompt chunk into the decode program, and the fused
    # admission finish installs + samples + scatters slot state on
    # device. Same gate as every other decode program: FULL aliasing of
    # every donated leaf, zero cache-sized copies.
    p_c = 16

    for name, kw in {"mixed_dense": {},
                     "mixed_paged": {"kv": "paged"},
                     "mixed_bucketed": {"decode_buckets": True},
                     # ISSUE 16: constrained requests ride the mixed/
                     # overlap hot path — both the mixed step (carried
                     # crow donated+aliased) and the fused finish (crow
                     # scatter-seeded on device) pass the same gate
                     "mixed_constrained": {"allow_constraints": True,
                                           "constraint_rows": 8}}.items():
        b = ContinuousBatcher(cfg, prepared, slots=slots, max_len=max_len,
                              prompt_pad=16, prefill_chunk_tokens=p_c,
                              **kw)
        if b._paged:
            layer_elems = (b._allocator.n_blocks * cfg.n_head
                           * b._block_len * hd)
        else:
            layer_elems = slots * cfg.n_head * b._cache_len * hd
        lower_and_check(name, b._mixed, mixed_step_args(b, p_c),
                        b._mixed_donate, layer_elems)
        lower_and_check(name + "_finish", b._prefill_finish,
                        finish_args(b, p_c), b._finish_donate, layer_elems)
        check_chunk(name, b)

    sbm = SpeculativeBatcher(cfg, prepared, cfg, prepared, spec_k=2,
                             slots=slots, max_len=max_len, prompt_pad=16,
                             prefill_chunk_tokens=p_c)
    row = sbm._ilv_new_row()
    d_row = sbm._d_family.init_cache(1, sbm._ilv_row_len,
                                     sbm.d_cache["k"].dtype)
    chunk = jnp.zeros((1, p_c), jnp.int32)
    spm_args = (sbm.prepared, sbm.draft_prepared, sbm.cache, sbm.d_cache,
                sbm.tok, sbm.pos, sbm.active, sbm.keys, sbm.prev_chunk,
                sbm.prev_pos, row, d_row, chunk, jnp.int32(0))
    spec_elems = slots * cfg.n_head * max_len * hd
    lower_and_check("mixed_speculative", sbm._spec_mixed, spm_args,
                    sbm._spec_mixed_donate, spec_elems)
    spf_args = finish_args(sbm, p_c) + (
        sbm.d_cache, sbm.prev_chunk, sbm.prev_pos, d_row,
        np.zeros((sbm.spec_k + 1,), np.int32))
    lower_and_check("mixed_speculative_finish", sbm._spec_ilv_finish,
                    spf_args, sbm._spec_ilv_finish_donate, spec_elems)

    return {"variants": report, "findings": findings}


def audit_pipeline_programs(num_stages: int = 2, *, feature: int = 8,
                            batch: int = 4) -> dict:
    """spmd_pipeline stage programs (parallel/pipeline.py): trace the
    heterogeneous-stage pipeline on a real mesh and verify every
    lax.switch branch (= every stage program) issues the same collective
    sequence, with no allocation-sized baked constants. Uses abstract
    tracing only — no compile, no execution."""
    from jax.sharding import Mesh

    from dnn_tpu.parallel.mesh import STAGE_AXIS
    from dnn_tpu.parallel.pipeline import spmd_pipeline

    devs = jax.devices()
    if len(devs) < num_stages:
        return {"skipped": f"need {num_stages} devices, have {len(devs)}",
                "findings": []}
    mesh = Mesh(np.array(devs[:num_stages]), (STAGE_AXIS,))

    # two deliberately heterogeneous stages (different widths/params) so
    # the switch branches are non-trivial
    def stage_a(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def stage_b(p, x):
        return x @ p["w"]

    params = [
        {"w": jnp.zeros((feature, feature * 2)),
         "b": jnp.zeros((feature * 2,))},
        {"w": jnp.zeros((feature * 2, feature))},
    ]
    stage_fns = [stage_a, stage_b][:num_stages]
    params = params[:num_stages]

    def run(sp, x):
        return spmd_pipeline(stage_fns, sp, x, mesh=mesh,
                             num_microbatches=2,
                             param_placement="replicated")

    x = jnp.zeros((batch, feature))
    closed = jax.make_jaxpr(run)(tuple(params), x)
    findings = check_branch_collectives(
        closed, "parallel/pipeline.spmd_pipeline")
    findings += baked_constants(
        closed, where="parallel/pipeline.spmd_pipeline")
    sig = collective_signature(closed)

    # PRG004 (ISSUE 17): the pipeline program count. Steps at the same
    # batch shape are ONE program — the stage coordinate and microbatch
    # index are traced, not static — so a repeated-call sweep must stay
    # at exactly one compile. The sharded serving PR cannot silently
    # start multiplying compilations per rung without tripping this.
    x_aval = jax.ShapeDtypeStruct(x.shape, x.dtype)
    p_avals = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tuple(params))
    census = recompile_census(
        [(p_avals, x_aval)] * 4, bound=1,
        where="parallel/pipeline.spmd_pipeline")
    findings += census["findings"]
    return {"collective_signature": list(sig),
            "stages": num_stages,
            "step_census": {k: census[k]
                            for k in ("calls", "programs", "bound")},
            "findings": findings}


def audit_transport_programs(num_stages: int = 4, *, feature: int = 8,
                             batch: int = 2) -> dict:
    """Device-transport send/recv programs (comm/transport.py
    make_hop_program): the compiled ppermute shuttle that moves a
    mesh-resident activation from stage i to stage i+1 is ONE program
    switching over the hop index — every switch branch must issue the
    IDENTICAL collective sequence (one ppermute) or ranks deadlock on a
    real pod, the same SPMD contract PRG001 enforces on the pipeline's
    stage switch. Traced abstractly on a real mesh — no compile, no
    execution."""
    from jax.sharding import Mesh

    from dnn_tpu.comm.transport import make_hop_program
    from dnn_tpu.parallel.mesh import STAGE_AXIS

    devs = jax.devices()
    if len(devs) < num_stages:
        return {"skipped": f"need {num_stages} devices, have {len(devs)}",
                "findings": []}
    mesh = Mesh(np.array(devs[:num_stages]), (STAGE_AXIS,))
    hop = make_hop_program(mesh, STAGE_AXIS)
    buf = jnp.zeros((num_stages, batch, feature))
    closed = jax.make_jaxpr(lambda h, b: hop(h, b))(jnp.int32(0), buf)
    findings = check_branch_collectives(
        closed, "comm/transport.make_hop_program")
    findings += baked_constants(
        closed, where="comm/transport.make_hop_program")
    # the traced signature concatenates over the switch's branches (one
    # branch per hop): it must be exactly one ppermute PER BRANCH — a
    # branch growing a second collective (or losing its ppermute) is a
    # deadlock on a real mesh even when the branches still AGREE with
    # each other (which check_branch_collectives pins above)
    sig = collective_signature(closed)
    if tuple(sig) != ("ppermute",) * (num_stages - 1):
        findings.append(Finding(
            rule="PRG001", path="comm/transport.make_hop_program", line=0,
            message=f"transport hop program must issue exactly one "
                    f"ppermute per hop branch ({num_stages - 1} hops), "
                    f"traced {list(sig) or 'none'}",
            snippet=f"stages={num_stages}"))

    # PRG004 (ISSUE 17): the hop INDEX is a traced int32 — all
    # num_stages-1 hops of a relay dispatch through ONE switch program.
    # Pin that a full hop sweep compiles exactly one program; a hop
    # index leaking into a static arg would show up here as n-1.
    hop_aval = jax.ShapeDtypeStruct((), jnp.int32)
    buf_aval = jax.ShapeDtypeStruct(buf.shape, buf.dtype)
    census = recompile_census(
        [(hop_aval, buf_aval) for _ in range(num_stages - 1)],
        bound=1, where="comm/transport.make_hop_program")
    findings += census["findings"]
    return {"collective_signature": list(sig),
            "stages": num_stages,
            "hop_census": {k: census[k]
                           for k in ("calls", "programs", "bound")},
            "findings": findings}


def audit_engine(*, batch_sweep: Sequence[int] = (1, 2, 4, 8)) -> dict:
    """PipelineEngine predict (runtime/engine.py): build the smallest
    registered pipeline model end to end, jaxpr-check its compiled
    pipeline callable (collective consistency + baked constants at
    activation scale), and run the recompile census over a batch sweep
    — the serving-shape question ('how many programs does this engine
    hold at steady state?') answered on paper."""
    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.runtime.engine import PipelineEngine

    config = TopologyConfig.from_dict({
        "nodes": [{"id": "a", "part_index": 0},
                  {"id": "b", "part_index": 1}],
        "num_parts": 2, "model": "mlp", "device_type": "cpu",
        "runtime": "spmd" if len(jax.devices()) >= 2 else "relay",
    })
    engine = PipelineEngine(config)
    findings: List[Finding] = []
    x = engine.spec.example_input()
    sig: List[str] = []
    if engine.runtime == "spmd":
        closed = jax.make_jaxpr(engine._pipeline_fn)(jnp.asarray(x))
        findings += check_branch_collectives(
            closed, "runtime/engine.PipelineEngine.run")
        # engine weights legitimately ride the wrapper closure (packed
        # once at load, passed as jit ARGS inside); only flag consts
        # beyond total weight size — a duplicate would exceed it
        weight_bytes = sum(
            l.size * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(engine._stage_params))
        findings += baked_constants(
            closed, min_bytes=max(2 * weight_bytes, 1 << 20),
            where="runtime/engine.PipelineEngine.run")
        sig = list(collective_signature(closed))

    x0 = np.asarray(x)
    sweep = []
    for b in batch_sweep:
        xb = np.broadcast_to(x0[:1], (b, *x0.shape[1:]))
        mb = engine._effective_microbatches(b)
        sweep.append((jax.ShapeDtypeStruct(xb.shape, xb.dtype),
                      jax.ShapeDtypeStruct((), jnp.dtype(np.int32)) if mb
                      else None))
    # REPORT-ONLY (bound=None): one program per distinct batch shape is
    # the engine's designed steady state, and an aval-level census can
    # never exceed the sweep size — a bound here would be a gate that
    # cannot fail. The enforced ceiling lives on the decode path, where
    # the ladder gives a real bound below the call count.
    census = recompile_census(
        sweep, where="runtime/engine.PipelineEngine.predict")
    return {"runtime": engine.runtime,
            "collective_signature": sig,
            "batch_census": {k: census[k]
                             for k in ("calls", "programs", "bound")},
            "findings": findings}


def run_program_audit(*, max_len: int = 128) -> Tuple[dict, List[Finding]]:
    """The full device-free program audit. Returns (report, findings)."""
    report: Dict[str, dict] = {}
    findings: List[Finding] = []
    report["decode"] = audit_decode_paths(max_len=max_len)
    report["serving_decode"] = audit_serving_decode(max_len=max_len)
    report["pipeline"] = audit_pipeline_programs()
    report["transport"] = audit_transport_programs()
    report["engine"] = audit_engine()
    for section in report.values():
        findings.extend(section.pop("findings", []))
    return report, assign_occurrences(findings)
