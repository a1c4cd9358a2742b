"""Mixture-of-Experts FFN: the grouped drop-free path every
single-device caller runs, and expert parallelism (EP).

The reference has no MoE anywhere (SURVEY.md §2: "no MoE modules exist" —
verified absence), so this module is pure capability extension, designed
TPU-first rather than ported. Two dispatches, one per place they fit:

  * `moe_ffn_grouped` — ONE device holds every expert: top-k routing,
    the S*k (token, expert) rows sorted by expert, the group sizes the
    router gave, and the expert FFN as grouped matmuls over the sorted
    rows: the serving layer loops' whole stacks through the Pallas kernel
    that reads them in place (ops/pallas/grouped_matmul.py), a matrix
    already cut out through `jax.lax.ragged_dot` (`_experts_grouped`).
    There is NO capacity: every routed row is computed, whatever the
    imbalance, and the shapes are static in S*k (for one chip's share of
    an expert-parallel layer, `held=`, in the rows an even routing gives
    it: `permutation_extent` a round, as many rounds as its rows need).
    The work is top_k
    expert FLOPs per token and each active expert's weights read once —
    what fine-grained experts (OLMoE: 64 experts, 8 per token) need;
    the one-hot dispatch below would run every expert on every slot.
    Serving (`llama_moe.make_ffn`, `generate_moe.moe_cache_ffn`,
    `gpt_moe.block_apply`) goes through it.
  * GShard-style top-k routing with STATIC capacity, where per-rank
    static shapes need it — the `all_to_all` path: dispatch/combine are
    dense one-hot tensors consumed by einsums, the expert FFNs run as
    one batched (E, cap, D) x (E, D, F) matmul, and tokens beyond an
    expert's capacity are dropped (their combine weight is zero; the
    caller's residual passes them through). Tokens are routed in GROUPS
    (the GShard "group" = the EP shard unit): capacity is per (group,
    expert), so `moe_ffn(groups=n)` — the EP path's dense twin, kept for
    the parity tests — and `moe_ffn_ep` on n devices compute IDENTICAL
    results. `moe_ffn_ep` runs under `shard_map` with groups sharded
    over the "expert" mesh axis and expert weights sharded on their
    leading E axis; tokens travel to their experts and back via
    `jax.lax.all_to_all` (XLA AllToAll over ICI).

Routing is computed in f32 regardless of compute dtype (router logits are
tiny and routing decisions must not flip with the activation dtype).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dnn_tpu.ops.nn import gelu
from dnn_tpu.parallel.mesh import EXPERT_AXIS


def moe_capacity(tokens_per_group: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Static per-(group, expert) slot count: the expected k*S/E load times
    the capacity factor, floored at 1."""
    return max(1, int(math.ceil(top_k * tokens_per_group * capacity_factor / n_experts)))


def _expert_stack(key, shape, dtype, scale):
    """One random expert stack, finished before the next is begun. Eager
    dispatch runs ahead of the device, and every pending product keeps its
    unscaled operand alive: at OLMoE's widths six 537 MB operands stood
    beside the tree at once, and that — not serving — was the daemon's
    HBM high-water mark (9.09 GB against 4.8 in use; my chip run, PR 27).
    Same values; a no-op on tracers."""
    return jax.block_until_ready(jax.random.normal(key, shape, dtype) * scale)


def init_moe(rng, n_embd: int, n_experts: int, d_ff: Optional[int] = None,
             dtype=jnp.float32):
    """Param pytree for one MoE FFN layer.

    Expert weights are EXPERT-MAJOR stacked arrays — (E, D, F) / (E, F, D) —
    so EP shards them with a plain P("expert") on the leading axis and the
    dense path consumes them as one batched matmul."""
    d_ff = 4 * n_embd if d_ff is None else d_ff
    kr, k1, k2 = jax.random.split(rng, 3)
    scale_in = 1.0 / math.sqrt(n_embd)
    scale_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": {"kernel": jax.random.normal(kr, (n_embd, n_experts), dtype) * scale_in},
        "wi": _expert_stack(k1, (n_experts, n_embd, d_ff), dtype, scale_in),
        "bi": jnp.zeros((n_experts, d_ff), dtype),
        "wo": _expert_stack(k2, (n_experts, d_ff, n_embd), dtype, scale_out),
        "bo": jnp.zeros((n_experts, n_embd), dtype),
    }


def route_topk(gate_logits, *, top_k: int, capacity: int, normalize: bool = True):
    """One group's routing: (S, E) f32 gate logits -> dispatch/combine.

    Returns:
      dispatch: (S, E, cap) 0/1 — token s occupies slot c of expert e;
      combine:  (S, E, cap) f32 — dispatch weighted by the (optionally
                renormalized) router probability;
      aux: dict with "load" (E,) fraction of tokens per expert and
           "importance" (E,) mean router prob — the load-balance loss
           ingredients (Shazeer et al.'s aux loss; see load_balance_loss).

    Selection is iterative argmax (k rounds); slot positions are the
    running per-expert count in token order, so results are deterministic
    and order-stable. Tokens whose slot index >= capacity are dropped from
    that expert (combine weight 0)."""
    s, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)  # (S, E)

    remaining = probs
    counts = jnp.zeros((e,), jnp.int32)
    dispatch = jnp.zeros((s, e, capacity), jnp.float32)
    weight_sum = jnp.zeros((s, 1), jnp.float32)
    picked = []
    for _ in range(top_k):
        sel = jax.nn.one_hot(jnp.argmax(remaining, axis=-1), e, dtype=jnp.float32)
        remaining = remaining * (1.0 - sel)
        # slot index: tokens before me this round + slots used by earlier rounds
        pos = (jnp.cumsum(sel, axis=0) - sel) + counts[None, :].astype(jnp.float32)
        keep = (pos < capacity) * sel  # (S, E)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
        dispatch = dispatch + keep[..., None] * slot
        w = (probs * keep).sum(axis=-1, keepdims=True)  # this round's weight
        weight_sum = weight_sum + w
        picked.append((keep, probs * keep))
        counts = counts + sel.sum(axis=0).astype(jnp.int32)

    combine = jnp.zeros_like(dispatch)
    denom = jnp.maximum(weight_sum, 1e-9) if normalize else 1.0
    for keep, w in picked:
        slot_w = (w / denom if normalize else w).sum(axis=-1)  # (S,)
        combine = combine + dispatch * (keep * slot_w[:, None])[..., None]

    aux = {
        # realized fraction of SELECTIONS per expert (normalized by k*S, so
        # it sums to <= 1 and uniform routing gives exactly 1/E per expert
        # for any top_k — the convention load_balance_loss assumes)
        "load": dispatch.sum(axis=(0, 2)) / (s * top_k),
        "importance": probs.mean(axis=0),               # mean router prob per expert
    }
    return dispatch, combine, aux


def load_balance_loss(aux) -> jax.Array:
    """Switch-Transformer load-balance term: E * <load, importance>, with
    `load` the per-expert fraction of selections (normalized by k — see
    route_topk's aux). Equals 1.0 under perfectly uniform routing for any
    top_k; add `alpha * (loss - 1.0)` (alpha ~1e-2) to the training
    objective to keep experts busy."""
    e = aux["load"].shape[-1]
    return e * jnp.sum(aux["load"] * aux["importance"], axis=-1).mean()


def init_moe_gated(rng, n_embd: int, n_experts: int, d_ff: int,
                   dtype=jnp.float32, *, n_held: Optional[int] = None):
    """Param pytree for a GATED (SwiGLU) MoE FFN layer — the Mixtral
    expert shape: per-expert gate/up/down projections, no biases.
    Expert-major stacking exactly as init_moe (EP shards the leading
    axis; the dense path batches over it). `n_held`: the stacks of that
    many experts only (one chip's share), under the whole layer's
    router."""
    kr, kg, ku, kd = jax.random.split(rng, 4)
    scale_in = 1.0 / math.sqrt(n_embd)
    scale_out = 1.0 / math.sqrt(d_ff)
    e = n_experts if n_held is None else n_held
    return {
        "router": {"kernel": jax.random.normal(
            kr, (n_embd, n_experts), dtype) * scale_in},
        "wg": _expert_stack(kg, (e, n_embd, d_ff), dtype, scale_in),
        "wu": _expert_stack(ku, (e, n_embd, d_ff), dtype, scale_in),
        "wd": _expert_stack(kd, (e, d_ff, n_embd), dtype, scale_out),
    }


def init_moe_plain(rng, n_embd: int, n_experts: int, d_ff: int,
                   dtype=jnp.float32, *, n_held: Optional[int] = None,
                   d_in: Optional[int] = None):
    """Param pytree for an UNGATED MoE FFN layer without biases — two
    matrices an expert, "wi" (E, d_in, F) and "wo" (E, F, d_in) — whose
    experts read and write rows `d_in` wide (None: the model's; narrower:
    a latent, models/llama_moe.py `moe_latent`) under a router over the
    model's width. `n_held` as `init_moe_gated`'s."""
    kr, k1, k2 = jax.random.split(rng, 3)
    d_in = n_embd if d_in is None else d_in
    e = n_experts if n_held is None else n_held
    return {
        "router": {"kernel": jax.random.normal(
            kr, (n_embd, n_experts), dtype) / math.sqrt(n_embd)},
        "wi": _expert_stack(k1, (e, d_in, d_ff), dtype, 1.0 / math.sqrt(d_in)),
        "wo": _expert_stack(k2, (e, d_ff, d_in), dtype, 1.0 / math.sqrt(d_ff)),
    }


def _expert_ffn_gated(params, expert_in, *, compute_dtype):
    """(E, cap, D) tokens through each expert's SwiGLU —
    silu(x@wg) * (x@wu) @ wd, one batched matmul triple (the Mixtral
    expert). Same dtype recipe as _expert_ffn: f32 accumulation,
    operands in compute_dtype.

    Accepts int8 weight-only-quantized stacks (quant.quantize_tree):
    per-(expert, out-channel) `*_scale` factors fold as exact epilogue
    multiplies on the f32 accumulators; the int8->compute convert fuses
    into the einsum operand read — 1 byte/weight of expert HBM traffic,
    the bandwidth win MoE decode exists for."""
    wg, wu, wd = params["wg"], params["wu"], params["wd"]
    sg, su, sd = (params.get(k) for k in ("wg_scale", "wu_scale",
                                          "wd_scale"))
    x = expert_in
    cd = compute_dtype if compute_dtype is not None else (
        jnp.float32 if wg.dtype == jnp.int8 else None)
    if cd is not None:
        x = x.astype(cd)
        wg, wu, wd = (w.astype(cd) for w in (wg, wu, wd))
    g = jnp.einsum("ecd,edf->ecf", x, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", x, wu,
                   preferred_element_type=jnp.float32)
    if sg is not None:
        g = g * sg  # (E, 1, F) broadcasts over capacity
    if su is not None:
        u = u * su
    h = jax.nn.silu(g) * u
    if cd is not None:
        h = h.astype(cd)
    out = jnp.einsum("ecf,efd->ecd", h, wd,
                     preferred_element_type=jnp.float32)  # f32
    if sd is not None:
        out = out * sd
    return out


def _expert_ffn(params, expert_in, *, activation, compute_dtype):
    """(E, cap, D) tokens through each expert's 2-layer FFN, one batched
    matmul pair — or, when the params carry the gated stack ("wg"), the
    SwiGLU expert (_expert_ffn_gated; `activation` is then unused).
    Accumulate in f32, ride operands in compute_dtype.

    Accepts int8 weight-only-quantized expert stacks (dnn_tpu/quant.py):
    `wi`/`wo` as int8 with per-(expert, out-channel) `wi_scale`/`wo_scale`
    (E, 1, out). Per-channel scales commute with the contraction, so the
    dequant is an exact epilogue on the f32 accumulator; the int8->
    compute_dtype convert fuses into the einsum's operand read, keeping
    the experts' HBM traffic at 1 byte/weight — MoE decode is the most
    weight-bandwidth-bound path in the framework (E experts' weights
    stream for one token's worth of FLOPs)."""
    if "wg" in params:
        return _expert_ffn_gated(params, expert_in,
                                 compute_dtype=compute_dtype)
    wi, bi, wo, bo = params["wi"], params["bi"], params["wo"], params["bo"]
    wi_scale, wo_scale = params.get("wi_scale"), params.get("wo_scale")
    x = expert_in
    if compute_dtype is not None:
        x, wi, wo = x.astype(compute_dtype), wi.astype(compute_dtype), wo.astype(compute_dtype)
    h = jnp.einsum("ecd,edf->ecf", x, wi,
                   preferred_element_type=jnp.float32)
    if wi_scale is not None:
        h = h * wi_scale  # (E, 1, ff) broadcasts over capacity
    h = activation(h + bi[:, None, :].astype(jnp.float32))
    if compute_dtype is not None:
        h = h.astype(compute_dtype)
    out = jnp.einsum("ecf,efd->ecd", h, wo,
                     preferred_element_type=jnp.float32)
    if wo_scale is not None:
        out = out * wo_scale
    return out + bo[:, None, :].astype(jnp.float32)  # f32


def route_rows(router_kernel, xs, *, top_k: int, normalize: bool = True,
               held=None, scoring: str = "softmax", select_bias=None,
               scale: float = 1.0):
    """Drop-free routing of (S, D) tokens: softmax over all experts (f32),
    `lax.top_k`, and the S*k (token, expert) assignments sorted by expert
    (stable: token order within an expert).

    `scoring="sigmoid"` (DeepSeek-V3's router): each expert's score is
    sigmoid(logit) on its own; the k are those of largest score +
    `select_bias` ((E,) float32, the load-balancing correction: it moves
    the PICK and never enters a weight); the weights are the picked
    experts' scores, over their sum when `normalize`, times `scale`.

    Returns (weights (S, k) f32 — the selected probabilities, renormalized
    over the k when `normalize`; `order` (S*k,) — sorted position ->
    flat assignment index t*k + j; `expert_of_row` (S*k,) — the expert of
    each sorted row; `group_sizes` (E,) int32 — rows per expert).

    `held` = (first, count): only experts [first, first + count) have
    their stacks here (one chip's share of an expert-parallel layer).
    Routing is unchanged — every token picks among ALL experts and its
    weights are those of the whole layer — but the rows are sorted by
    HELD expert, counted from `first`, with every pick of an expert held
    elsewhere behind them: `expert_of_row` is then the index into the
    held stacks (`count` marks a row not held) and `group_sizes` is
    (count,), summing to the rows held."""
    e = router_kernel.shape[-1]
    # "highest": on a TPU a float32 matmul at the default precision rounds
    # its operands to bfloat16, and the eighth and ninth of 64 experts are
    # often closer than that; the matmul is (S, D) x (D, E), next to nothing
    logits = jnp.dot(xs.astype(jnp.float32), router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if select_bias is None else (
            scores + select_bias.astype(jnp.float32))
        _, experts = jax.lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if normalize:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    elif scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)  # (S, k)
        if normalize:
            weights = weights / jnp.maximum(
                weights.sum(axis=-1, keepdims=True), 1e-9)
    else:
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                         f"{scoring!r}")
    if scale != 1.0:
        weights = weights * scale
    flat = experts.reshape(-1).astype(jnp.int32)
    if held is not None:
        first, e = held
        local = flat - first
        flat = jnp.where((local >= 0) & (local < e), local, e)
    # the sort hands back its keys: the sorted experts cost no gather, and
    # the sizes are a compare-and-sum over them (a scatter-add is an update
    # at a time on the chip: 72 us for a chunk's 8192 picks, PERF.md
    # section 6, PR 65); a pick held elsewhere matches no expert here
    expert_of_row, order = jax.lax.sort(
        (flat, jnp.arange(flat.shape[0], dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    group_sizes = (flat[None, :] == jnp.arange(e, dtype=jnp.int32)[:, None]
                   ).sum(axis=1, dtype=jnp.int32)
    return weights, order, expert_of_row, group_sizes


class LayerOf(NamedTuple):
    """Layer `layer` (an int32 scalar, traced in a layer loop) of a stack
    `(L, E, K, N)` of expert matrices, NOT cut out of it: what a layer
    loop that keeps its expert stacks whole hands `_experts_grouped` in a
    matrix's place (`models/llama.scan_form`)."""
    stack: jax.Array
    layer: jax.Array


# the leaves of an expert layer that are one matrix an expert
EXPERT_MATRICES = ("wg", "wu", "wd", "wi", "wo")


def _operand_dtype(params, compute_dtype):
    """What `_experts_grouped` casts rows and matrices to: the caller's
    `compute_dtype`; float32 for an int8 stack without one; None: as they
    come."""
    w = params["wg" if "wg" in params else "wi"]
    w_dtype = (w.stack if isinstance(w, LayerOf) else w).dtype
    return compute_dtype if compute_dtype is not None else (
        jnp.float32 if w_dtype == jnp.int8 else None)


def _reads_stacks_in_place(params, compute_dtype, interpret, *, rows_dtype):
    """Whether the experts of `params` go through the Pallas kernel that
    reads a layer loop's whole stacks in place (`_experts_grouped`'s
    "stack_kernel"): `LayerOf` matrices of a float stack held in the
    operands' dtype (`rows_dtype` where no cast is made), no `*_scale`
    factors, on a TPU (or `interpret`)."""
    names = ("wg", "wu", "wd") if "wg" in params else ("wi", "wo")
    w = params[names[0]]
    if not isinstance(w, LayerOf):
        return False
    cd = _operand_dtype(params, compute_dtype)
    x_dtype = cd if cd is not None else rows_dtype
    return (w.stack.dtype == x_dtype
            and jnp.issubdtype(w.stack.dtype, jnp.floating)
            and all(params.get(k + "_scale") is None for k in names)
            and bool(interpret or jax.default_backend() == "tpu"))


def _experts_grouped(params, rows, expert_of_row, group_sizes, *,
                     activation, compute_dtype, interpret=False, forms=None):
    """The expert FFN over rows sorted by expert, (R, D) -> (R, D) f32:
    `silu(x@wg) * (x@wu) @ wd` for the gated stack ("wg"), `act(x@wi + bi)
    @ wo + bo` for the plain one (the biases where the tree carries them),
    as grouped matmuls — row r meets only
    its own expert's weights. Same dtype recipe as `_expert_ffn`
    (operands in compute_dtype, f32 accumulation).

    What it is handed decides how the matmul meets its weights (and
    `forms`, a set, is told which):

      * `LayerOf(stack, layer)` matrices of a float stack held in the
        operands' dtype, on a TPU (or `interpret`): the Pallas kernel
        (ops/pallas/grouped_matmul.py) reads the active experts' tiles out
        of the stack in place — "stack_kernel";
      * anything else — an `(E, K, N)` matrix a scan or a caller has cut
        out already, a stack to be converted, an int8 stack with its
        per-(expert, out-channel) `*_scale` factors (gathered per row and
        applied to the f32 accumulators), a CPU run, training (its layer
        loop hands over slices; the kernel has no VJP): `jax.lax.
        ragged_dot`, whose custom call on the TPU makes a copy of each
        matrix it is fed from a stack — "ragged_dot"."""
    from dnn_tpu.ops.pallas.grouped_matmul import grouped_matmul

    gated = "wg" in params
    names = ("wg", "wu", "wd") if gated else ("wi", "wo")
    ws = [params[k] for k in names]
    scales = [params.get(k + "_scale") for k in names]
    stacked = isinstance(ws[0], LayerOf)
    cd = _operand_dtype(params, compute_dtype)
    x = rows if cd is None else rows.astype(cd)
    in_place = _reads_stacks_in_place(params, compute_dtype, interpret,
                                      rows_dtype=rows.dtype)
    if forms is not None:
        forms.add("stack_kernel" if in_place else "ragged_dot")
    if not in_place:
        ws = [w.stack[w.layer] if stacked else w for w in ws]
        if cd is not None:
            ws = [w.astype(cd) for w in ws]

    def gdot(a, w, scale):
        if in_place:
            return grouped_matmul(a, w.stack, w.layer, group_sizes,
                                  interpret=interpret)
        out = jax.lax.ragged_dot(a, w, group_sizes,
                                 preferred_element_type=jnp.float32)
        if scale is not None:  # (E, 1, out) -> this row's expert's (out,)
            out = out * scale[expert_of_row, 0]
        return out

    if gated:
        h = jax.nn.silu(gdot(x, ws[0], scales[0])) * gdot(x, ws[1], scales[1])
    elif "bi" in params:
        h = activation(gdot(x, ws[0], scales[0])
                       + params["bi"][expert_of_row].astype(jnp.float32))
    else:
        h = activation(gdot(x, ws[0], scales[0]))
    if cd is not None:
        h = h.astype(cd)
    out = gdot(h, ws[-1], scales[-1])
    if "bo" in params:
        out = out + params["bo"][expert_of_row].astype(jnp.float32)
    return out


#: what `moe_ffn_grouped(return_stats=True)` counts a layer call, in order
#: (the batcher's adapter sums them into the moe_* series): rows through
#: the experts, experts with at least one row, the fullest expert's rows,
#: rows the permutation moved in and out, rounds of it beyond the first
N_STATS = 5

# a round of the permutation under `held` covers this many times the rows
# an even routing gives the held experts, in whole row tiles of the
# grouped matmul
_EXTENT_ROOM = 1.5
# the rounds are a loop whose trips the DEVICE counts, and the chip charges
# such a loop 30-90 us a layer call whatever is in it (92 us of a Keye
# chunk's 388, 33 of a dots3 chunk's 2686, 87 of a 64-slot Keye step's 247:
# PERF.md section 6, PR 65) — what moving ~1400 rows in and out costs (65
# ns a row). A layer call that would save fewer rows than this keeps every
# pick's row in one pass: every decode step does
_MIN_ROWS_SAVED = 2048


def permutation_extent(n_rows: int, n_expert: int, n_held: int) -> int:
    """Rows ONE round of a share's permutation moves, of the `n_rows`
    picks of a layer call whose `n_held` of `n_expert` experts are here:
    `_EXTENT_ROOM` times the held experts' even share, rounded up to the
    row tile; all of them (one pass, no round) where that would save fewer
    than `_MIN_ROWS_SAVED` rows."""
    from dnn_tpu.ops.pallas.grouped_matmul import _ROW_TILE

    even = n_rows * n_held / n_expert
    tiles = -(-math.ceil(_EXTENT_ROOM * even) // _ROW_TILE)
    extent = max(tiles, 1) * _ROW_TILE
    return extent if n_rows - extent >= _MIN_ROWS_SAVED else n_rows


def moe_ffn_grouped(params, x, *, top_k: int = 2, normalize: bool = True,
                    activation=gelu, compute_dtype=None,
                    return_stats: bool = False, held=None,
                    scoring: str = "softmax", scale: float = 1.0,
                    interpret: bool = False, forms=None, rows=None):
    """Drop-free MoE FFN on one device: (..., D) -> (..., D), every token
    of `x` routed to its top_k experts and every routed row computed.
    Output does NOT include the residual; callers add it.

    `rows` (..., W): what the experts READ where that is not what the
    router scores — a token's latent, W the experts' in / out width
    (models/llama_moe.py `moe_latent`): `x` is scored, `rows` are permuted
    and computed, and the result is (..., W), the weighted sum in the
    experts' width. None: `x` itself.

    The three scopes name its parts on a device trace: `moe.route`
    (router matmul, softmax, top-k, the sort and the row gather),
    `moe.experts` (the grouped matmuls and the gate's product),
    `moe.combine` (each computed row, weighted, summed into its token).

    `return_stats` adds an int32 (`N_STATS`,): what this layer call cost,
    for the serving counters.

    `held` = (first, count) (`route_rows`): `params` carries the stacks
    of those experts only, beside the whole layer's router. The grouped
    matmuls cover the rows whose expert is held; a pick of an expert
    held elsewhere contributes ZERO, so the result is this chip's part
    of the layer's sum, and the shares of all chips add up to the whole
    layer's result. The statistics count the held experts' rows. None:
    every expert is held, and the program is what it was without the
    argument.

    The EXTENT of the permutation follows the rows held. The sort puts
    them first, and where the experts go through the kernel that reads
    their stacks in place (`_experts_grouped`: no VJP is asked of it) the
    dispatch, the experts and the combine work on `permutation_extent`
    rows a ROUND, for as many rounds as the live rows need
    (`cdiv(live, extent)`, traced: one at any routing near even, none
    when no pick is held here, S*k / extent when every pick is) — every
    array between the gather and the sum is `extent` rows long, no row is
    dropped at any routing, and a round's rows are added into their
    tokens by `ops/pallas/row_accumulate.py`. Everywhere else (every
    expert held; `ragged_dot`'s callers, training among them; a layer
    call that a round would save too few rows to pay its loop, every
    decode step among them: `permutation_extent`) the extent is S*k: one
    pass, the rows back at their picks by the inverse permutation, a pick
    not held here a flag where its row is summed.

    `scoring` / `scale` are `route_rows`'; the selection bias is read
    from `params["router"]["select_bias"]` where the tree carries one.

    The expert matrices may be `LayerOf(stack, layer)`: a layer loop's
    whole stacks, which the grouped-matmul kernel reads in place
    (`_experts_grouped` says when; `interpret` runs the kernels in
    interpreter mode, for CPU tests; `forms`, a set, is told at trace
    time which form the matmuls took)."""
    xs = x.reshape(-1, x.shape[-1])
    # what the permutation moves: the scored rows, or the caller's `rows`
    us = xs if rows is None else rows.reshape(-1, rows.shape[-1])
    shape, d = (x if rows is None else rows).shape, us.shape[-1]
    s = xs.shape[0]
    n_rows = s * top_k
    experts = functools.partial(
        _experts_grouped, params, activation=activation,
        compute_dtype=compute_dtype, interpret=interpret, forms=forms)
    with jax.named_scope("moe.route"):
        weights, order, expert_of_row, group_sizes = route_rows(
            params["router"]["kernel"], xs, top_k=top_k, normalize=normalize,
            held=held, scoring=scoring,
            select_bias=params["router"].get("select_bias"), scale=scale)
    extent = n_rows
    if held is not None and _reads_stacks_in_place(
            params, compute_dtype, interpret, rows_dtype=us.dtype):
        extent = permutation_extent(
            n_rows, params["router"]["kernel"].shape[-1], held[1])
    live = jnp.int32(n_rows) if held is None else group_sizes.sum()
    if extent == n_rows:
        with jax.named_scope("moe.route"):
            sorted_rows = us[order // top_k]  # (S*k, D), sorted by expert
        with jax.named_scope("moe.experts"):
            # rows behind the last group belong to no expert here: the
            # grouped matmul leaves them unspecified
            out = experts(sorted_rows, expert_of_row, group_sizes)
        with jax.named_scope("moe.combine"):
            # row t*k + j back at (t, j): the inverse of a permutation is
            # its argsort (8 us for 8192 where the scatter took 39)
            inverse = jnp.argsort(order)
            unsorted = out[inverse].reshape(s, top_k, d)
            if held is not None:
                unsorted = jnp.where(
                    (inverse < live).reshape(s, top_k, 1), unsorted, 0.0)
            y = (unsorted * weights[..., None]).sum(axis=1)
        rounds = jnp.int32(1)
    else:
        y, rounds = _rounds(experts, us, weights, order, expert_of_row,
                            group_sizes, live, extent, interpret=interpret)
    with jax.named_scope("moe.combine"):
        y = y.reshape(shape).astype(x.dtype)
    if not return_stats:
        return y
    stats = jnp.stack([live,
                       (group_sizes > 0).sum().astype(jnp.int32),
                       group_sizes.max(), rounds * extent,
                       jnp.maximum(rounds - 1, 0)])
    return y, stats


def _rounds(experts, xs, weights, order, expert_of_row, group_sizes, live,
            extent, *, interpret):
    """The compact form of `moe_ffn_grouped`: -> (y (S, D) float32, the
    rounds it took). Round i gathers the tokens of sorted rows [i * extent,
    (i + 1) * extent), runs the experts on them with each group's size cut
    to the window, and adds the live ones, weighted, into y."""
    from dnn_tpu.ops.pallas.row_accumulate import row_accumulate

    s, top_k = weights.shape
    n_rows, count = order.shape[0], group_sizes.shape[0]
    with jax.named_scope("moe.route"):
        rounds = -(-live // extent)
        pad = -n_rows % extent  # the last window's rows past S*k: held by none
        order = jnp.pad(order, (0, pad))
        expert_of_row = jnp.pad(expert_of_row, (0, pad),
                                constant_values=count)
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes
        flat_weights = weights.reshape(-1)

    def one(i, y):
        lo = i * extent
        with jax.named_scope("moe.route"):
            picks = jax.lax.dynamic_slice(order, (lo,), (extent,))
            tokens = picks // top_k
            rows = xs[tokens]  # (extent, D), sorted by expert
            sizes = (jnp.clip(ends, lo, lo + extent)
                     - jnp.clip(starts, lo, lo + extent))
        with jax.named_scope("moe.experts"):
            out = experts(
                rows, jax.lax.dynamic_slice(expert_of_row, (lo,), (extent,)),
                sizes)
        with jax.named_scope("moe.combine"):
            return row_accumulate(y, out, tokens, flat_weights[picks],
                                  live - lo, interpret=interpret)

    with jax.named_scope("moe.combine"):
        y = jnp.zeros((s, xs.shape[-1]), jnp.float32)
    # (the loop itself stands under no part's scope: what the chip charges
    # for a loop whose trips it counts is nobody's work)
    return jax.lax.fori_loop(0, rounds, one, y), rounds


def _group_dispatch(params, xg, *, top_k, capacity, normalize):
    """Routing for one (S, D) group -> dispatch/combine/aux (f32)."""
    logits = xg.astype(jnp.float32) @ params["router"]["kernel"].astype(jnp.float32)
    return route_topk(logits, top_k=top_k, capacity=capacity, normalize=normalize)


def moe_ffn(params, x, *, top_k: int = 2, capacity_factor: float = 1.25,
            groups: int = 1, activation=gelu, compute_dtype=None,
            return_aux: bool = False, normalize: bool = True):
    """The EP path's dense twin (single-program, static capacity): (B, T,
    D) -> (B, T, D). Serving does not come here — `moe_ffn_grouped` is the
    single-device path; this one exists so that the parity tests can
    compute on one device exactly what `moe_ffn_ep` computes on n.

    Tokens are routed in `groups` independent groups (B*T must divide by
    groups); with groups == n_devices this computes exactly what
    `moe_ffn_ep` computes on an n-device mesh — the parity contract.
    Output does NOT include the residual; callers add it (dropped tokens
    then degrade to identity, the standard MoE fallback)."""
    b, t, d = x.shape
    n_tok = b * t
    if n_tok % groups:
        raise ValueError(f"B*T={n_tok} not divisible by groups={groups}")
    s = n_tok // groups
    e = params["wg" if "wg" in params else "wi"].shape[0]
    capacity = moe_capacity(s, e, top_k, capacity_factor)

    xg = x.reshape(groups, s, d)
    # normalize=False (Qwen2-MoE norm_topk_prob) keeps the RAW softmax
    # probabilities as combine weights instead of renormalizing the
    # selected top-k (Mixtral's convention)
    dispatch, combine, aux = jax.vmap(
        lambda g: _group_dispatch(params, g, top_k=top_k, capacity=capacity,
                                  normalize=normalize)
    )(xg)

    expert_in = jnp.einsum("gsec,gsd->gecd", dispatch,
                           xg.astype(jnp.float32))  # (G, E, cap, D)
    out = jax.vmap(
        lambda ein: _expert_ffn(params, ein, activation=activation,
                                compute_dtype=compute_dtype)
    )(expert_in)  # (G, E, cap, D) f32
    y = jnp.einsum("gsec,gecd->gsd", combine, out).reshape(b, t, d).astype(x.dtype)
    if return_aux:
        return y, {k: v.mean(axis=0) for k, v in aux.items()}
    return y


def moe_ffn_local(params_local, xg, *, top_k, capacity, axis_name,
                  activation=gelu, compute_dtype=None,
                  normalize: bool = True):
    """Per-device EP body (call inside shard_map): this device's group
    (S, D) + its shard of the experts -> (S, D).

    The two `all_to_all`s are the expert dispatch fabric: tokens leave for
    the device that owns their expert and come back combined — XLA
    AllToAll over ICI, replacing the reference's per-hop gRPC sends."""
    dispatch, combine, _aux = _group_dispatch(
        # router weights are replicated; only expert weights are sharded
        params_local, xg, top_k=top_k, capacity=capacity,
        normalize=normalize,
    )
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, xg.astype(jnp.float32))
    if compute_dtype is not None:
        # round BEFORE the hop: _expert_ffn casts to compute_dtype anyway,
        # and rounding commutes with the permutation, so this halves the
        # dispatch collective's ICI bytes with bit-identical output vs the
        # dense path (which rounds the same values device-locally)
        expert_in = expert_in.astype(compute_dtype)
    # (E, cap, D) -> (E/n, n*cap, D): send each expert-block to its owner,
    # gather every device's tokens for my experts
    expert_in = jax.lax.all_to_all(
        expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True
    )
    out = _expert_ffn(params_local, expert_in, activation=activation,
                      compute_dtype=compute_dtype)
    # inverse exchange: (E/n, n*cap, D) -> (E, cap, D)
    out = jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=0, tiled=True
    )
    y = jnp.einsum("sec,ecd->sd", combine, out)
    return y.astype(xg.dtype)


def make_moe_ffn_ep(mesh: Mesh, *, top_k: int = 2, capacity_factor: float = 1.25,
                    axis_name: str = EXPERT_AXIS, activation=gelu,
                    compute_dtype=None):
    """Expert-parallel MoE FFN over `mesh`'s "expert" axis.

    apply(params, x): x (B, T, D) with B divisible by the axis size; the
    BATCH is sharded over the expert axis (each device's local batch is
    its routing group — dp and ep share the axis, the standard MoE mesh
    layout), expert weights shard P("expert") on their leading E axis,
    router/bias params replicate. Equals moe_ffn(groups=n) exactly."""
    n = mesh.shape[axis_name]

    def _param_specs(params):
        # every expert-stack leaf (wi/wo/biases and, when quantized, the
        # wi_scale/wo_scale factors) has leading dim E -> shard P(axis);
        # the router replicates (tokens route locally, pre-dispatch)
        return {
            k: ({"kernel": P()} if k == "router" else P(axis_name))
            for k in params
        }

    def apply(params, x):
        b, t, d = x.shape
        if b % n:
            raise ValueError(f"batch {b} not divisible by expert-axis size {n}")
        e = params["wg" if "wg" in params else "wi"].shape[0]
        if e % n:
            raise ValueError(f"{e} experts not divisible by expert-axis size {n}")
        s = (b // n) * t
        capacity = moe_capacity(s, e, top_k, capacity_factor)

        def local(params_local, x_local):
            bl = x_local.shape[0]
            xg = x_local.reshape(bl * t, d)
            y = moe_ffn_local(
                params_local, xg, top_k=top_k, capacity=capacity,
                axis_name=axis_name, activation=activation,
                compute_dtype=compute_dtype,
            )
            return y.reshape(bl, t, d)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(_param_specs(params), P(axis_name)),
            out_specs=P(axis_name),
            check_vma=False,
        )(params, x)

    return apply
