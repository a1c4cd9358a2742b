"""The layers that keep a STATE a slot — a matrix a head whatever the
length, and perhaps the last rows of a short convolution — behind ONE
adapter: what models/kda.py (the gated delta rule), models/retention.py
(power retention), models/mamba2.py (Mamba-2, BESIDE softmax attention or in
its place, by the config) and models/lightning.py (fixed-decay linear
attention) share.

A rule module keeps its mathematics and exports one `Rule`: the config
field that turns it on, the layer kind it serves, where its params sit in a
block, its `slot_leaves(cfg)` and its two forms under ONE signature, the
kind's leaves a mapping by name that comes in holding the incoming ones
and is left holding those after the last real position (`leaves[name]`,
`leaves.update(name=new, ...)`: a dict, a layer of the pool as
`LayerLeaves`, or a layer of a chunk's transient row as
`paged_kvcache.LayerRows`) —

    chunk(p, h, leaves, start_pos, n_real, *, cfg, compute_dtype, kernel)
        h (B, T, C) at [start_pos, start_pos + T), the first `n_real` real
    step(p, h, leaves, pos, *, cfg, compute_dtype, kernel, layer)
        h (B, 1, C) at per-slot positions `pos` (B,)
        -> the mixer's output

— a rule ignores what it does not use. `config_rule` / `layer_rule` are the
one lookup models/llama.py's params, dense forward and `family_rows` ask;
`StateKindRows` is the batcher's adapter for every one of them; the next
rule is a module with a `Rule` and a line in `_rules`.

A kind's `slot_leaves` (runtime/paged_kvcache.py's module docstring) is
name -> (the shape a slot a layer, dtype or None for the cache's): the ONE
place a state leaf's shape is said; the pool (`init_paged_cache(kinds=)`),
a family's transient row and a dense forward's empty state are all made
from it (`fresh`). `conv_chunk` / `conv_step` are the short causal depthwise
convolution that carries its last rows — the TAIL — from one call to the
next.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import llama


@dataclasses.dataclass(frozen=True)
class Rule:
    """What the adapter needs of a rule (module docstring)."""
    field: str             # the config field that holds its widths
    kind: str              # the layer kind it serves
    params: Optional[str]  # its params' key in a block; None: the block
    slot_leaves: Callable  # cfg -> the kind's slot leaves
    init: Callable         # (blk, key, cfg, dtype): its params into a block
    chunk: Callable
    step: Callable
    kernel: str            # the form with a Pallas kernel: "chunk" / "step"
    # the leaves the step's kernel takes WHOLE, (L, slots, ...), and updates
    # in place at `layer`; the plain form takes one layer's of every leaf
    whole: tuple = ()
    fits: Callable = lambda cfg: True  # the widths the kernel is built for
    # (attn_o, o, cfg) -> their sum, where the rule runs BESIDE softmax
    # attention on the same normed input; None: in its place
    beside: Optional[Callable] = None
    forms: tuple = ("prefill", "decode")  # its names in `attn_forms[kind]`
    # cfg -> the rule as THAT config runs it, where its kind and `beside`
    # follow the config (models/mamba2.py); None: as it stands
    resolve: Optional[Callable] = None

    def of(self, bp):
        return bp if self.params is None else bp[self.params]


@functools.cache
def _rules():
    # the rule modules import this one
    from dnn_tpu.models import kda, lightning, mamba2, retention

    return kda.RULE, retention.RULE, mamba2.RULE, lightning.RULE


def config_rule(cfg) -> Optional[Rule]:
    """The rule a config's state layers run; None: it has none."""
    rule = next((r for r in _rules()
                 if getattr(cfg, r.field, None) is not None), None)
    return rule if rule is None or rule.resolve is None else rule.resolve(cfg)


def layer_rule(cfg, kind) -> Optional[Rule]:
    """The rule a layer of `kind` runs: the config's, in every layer of a
    config without `layer_types` and in the layers of the rule's kind."""
    rule = config_rule(cfg)
    served = rule is not None and (
        kind == rule.kind or getattr(cfg, "layer_types", None) is None)
    return rule if served else None


def fresh(slot_leaves, batch, tail_dtype=None, layers=None):
    """Zeros of `slot_leaves` for `batch` slots — a leaf whose dtype is
    None in `tail_dtype` — with a leading layer axis where `layers` is
    given."""
    lead = (batch,) if layers is None else (layers, batch)
    return {name: jnp.zeros((*lead, *shape), dtype or tail_dtype)
            for name, (shape, dtype) in slot_leaves.items()}


def _rows(tail, pre):
    return jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)


def conv_chunk(tail, pre, taps, n_real):
    """The convolution over a chunk: `pre` (B, T, W) the un-convolved rows
    whose first `n_real` are real, `tail` (B, n - 1, W) the rows before
    them, `taps` (n, W) float32 or a function that makes them (y_t = sum_j
    taps_j x_{t - n + 1 + j}) -> (y (B, T, W) float32, the last n - 1 REAL
    rows: the next call's tail, in `pre`'s dtype)."""
    t = pre.shape[1]
    rows = _rows(tail, pre)
    if callable(taps):
        taps = taps()
    n = taps.shape[0]
    y = sum(taps[j] * rows[:, j:j + t].astype(jnp.float32) for j in range(n))
    # the last n - 1 real rows: rows [n_real, n_real + n - 1)
    return y, lax.dynamic_slice_in_dim(rows, n_real, n - 1, axis=1)


def conv_step(tail, pre, taps):
    """The convolution for one position a slot: `pre` (B, 1, W), `tail` (B,
    n - 1, W), `taps` as `conv_chunk`'s -> (y (B, 1, W) float32, the n rows
    it read: all but the first are the next call's tail)."""
    rows = _rows(tail, pre)
    if callable(taps):
        taps = taps()
    return (taps * rows.astype(jnp.float32)).sum(1, keepdims=True), rows


def dense_mixer(rule, attend, *, cfg, compute_dtype):
    """`llama.block_apply`'s mixer `fn(bp, h)` of a layer that runs `rule`
    over whole sequences h (B, T, C) from an empty state: the chunk form, T
    padded up to whole chunks — added to `attend(bp, h)` where the rule
    runs beside attention."""
    def fn(bp, h):
        b, t, _ = h.shape
        attn_o = None if rule.beside is None else attend(bp, h)
        pad = -t % getattr(cfg, rule.field).chunk
        o = rule.chunk(
            rule.of(bp), jnp.pad(h, ((0, 0), (0, pad), (0, 0))),
            fresh(rule.slot_leaves(cfg), b, h.dtype), 0, jnp.int32(t),
            cfg=cfg, compute_dtype=compute_dtype)[:, :t]
        return o if attn_o is None else rule.beside(attn_o, o, cfg)

    return fn


class LayerLeaves:
    """One layer's slot leaves of a pool `cache` that rides the layer loop
    whole, as the mapping a rule's step is handed: `leaves[name]` IS the
    read (scope `state_pool.read`) of layer `layer`'s slots and `update` the
    write (scope `state_pool.write`) — of nothing where the leaf is one the
    rule's kernel takes `whole` and hands back updated in place —, each
    made where the rule asks for it: the order of a step program's text is
    the order of its trace, and a rule that projects first reads after."""

    def __init__(self, cache, layer, whole=()):
        self.cache, self.layer, self.whole = cache, layer, whole

    def __getitem__(self, name):
        if name in self.whole:
            return self.cache[name]
        with jax.named_scope("state_pool.read"):
            return self.cache[name][self.layer]

    def update(self, **new):
        with jax.named_scope("state_pool.write"):
            self.cache = {**self.cache, **{
                name: leaf if name in self.whole
                else self.cache[name].at[self.layer].set(leaf)
                for name, leaf in new.items()}}


class StateKindRows(llama.LlamaKindRows):
    """`LlamaKindRows` for a config whose layers — all of them, or those of
    one kind of `layer_types` — run a state `Rule` (`config_rule`), in place
    of softmax attention or beside it; a block of kind `llama.EXPERTS`
    (`one_mixer`) runs its experts and keeps nothing.

    The rule's kind has `slot_leaves` (`cache_kinds[kind]["slot_leaves"]`):
    leaves (L_kind, slots, ...) with NO position axis, no blocks and no
    tables — a kind of their own (models/kda.py, models/lightning.py:
    "linear", beside a "full" kind of K and V in other layers; models/
    retention.py: "retention", the ONLY kind: nothing is paged, the batcher
    holds `init_cache`'s leaves as they are and admits by slots alone), or
    the slot leaves of the kind that pages K and V (models/mamba2.py:
    "full"). The pool carries them through the layer loop with the K and V
    leaves; a decode step reads and writes every slot's state IN PLACE at
    the layer's index among the kind's layers (`LayerLeaves`; on the chip
    the rule's kernel makes ONE pass over the whole leaf, `kernel_form`);
    the finish-and-install program writes the transient row's running state
    into the slot, which is also what resets a slot; the chunk program is
    told how many of its positions are real (`takes_n_real`: a recurrence
    has no mask to hide a padded tail behind). What assumes K and V alone —
    the prefix store, the KV tier, int8 / int4 pools, interleaved prefill,
    speculative verify — is refused by the batcher at construction, by the
    leaves' names."""

    takes_n_real = True

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        rule = self.rule = config_rule(cfg)
        types = getattr(cfg, "layer_types", None)
        self.requires_paged = bool(self.kinds)  # no K/V kind: nothing to page
        self.paged_ok = False  # a verifier would have no state to rewind
        if rule.beside is None:
            self.cache_kinds[rule.kind] = {
                "layers": sum(t == rule.kind for t in types or
                              (rule.kind,) * cfg.n_layer),
                "leaves": {}, "tables": None, "window": None}
            self.attn_forms[rule.kind] = {}
        self.cache_kinds[rule.kind]["slot_leaves"] = rule.slot_leaves(cfg)
        self.attn_forms[rule.kind].update(
            zip(rule.forms, ("chunked_jnp", "step_jnp")))

    def kernel_form(self, form):
        """Whether the rule's `form` ("chunk" / "step") runs in its Pallas
        kernel — False, True or "interpret" —: where it has one for these
        widths, on the chip unless the family's kernels are off,
        interpreted where a test asks."""
        if form != self.rule.kernel or not self.rule.fits(self.cfg):
            return False
        if self.attn_kernel == "interpret":
            return "interpret"
        return bool(self.attn_kernel) and jax.default_backend() == "tpu"

    def _runs_rule(self, kind):
        return layer_rule(self.cfg, kind) is not None

    def _chunk_block(self, bp, x, rows, start_pos, ffn, kind, **chunk_kw):
        if kind == llama.EXPERTS:  # no state, no K or V: nothing of `rows`
            return llama.experts_block(bp, x, ffn, cfg=self.cfg), rows
        return super()._chunk_block(bp, x, rows, start_pos, ffn, kind,
                                    **chunk_kw)

    def _block_rows(self, bp, x, layer_cache, pos, write, codec,
                    window=None, ffn=None, **kind):
        if kind.get("kind") == llama.EXPERTS:
            return (llama.experts_block(bp, x, ffn or self.ffn, cfg=self.cfg),
                    layer_cache)
        return super()._block_rows(bp, x, layer_cache, pos, write, codec,
                                   window=window, ffn=ffn, **kind)

    def _chunk_attn(self, bp, h, rows, start_pos, kind, n_real=None):
        """`LlamaKindRows._chunk_attn` where the layer runs the rule: its
        chunk form over the layer's transient `rows`, whose first `n_real`
        positions are real (all of them where the caller does not say)."""
        if not self._runs_rule(kind):
            return super()._chunk_attn(bp, h, rows, start_pos, kind)
        rule = self.rule
        if rule.beside is not None:
            attn_o, rows = super()._chunk_attn(bp, h, rows, start_pos, kind)
        kernel = self.kernel_form("chunk")
        self.attn_forms[rule.kind][rule.forms[0]] = (
            "chunked_kernel" if kernel else "chunked_jnp")
        o = rule.chunk(
            rule.of(bp), h, rows, start_pos,
            h.shape[1] if n_real is None else n_real, cfg=self.cfg,
            compute_dtype=self.compute_dtype, kernel=kernel)
        if rule.beside is not None:
            o = rule.beside(attn_o, o, self.cfg)
        return o, rows

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window,
                   kind="full"):
        if not self._runs_rule(kind):
            return super()._attn_rows(bp, x, layer_cache, pos, write, codec,
                                      window, kind)
        cfg, rule, c = self.cfg, self.rule, layer_cache
        if rule.beside is None:
            h = llama._pre_normed(bp, x, cfg)
        else:
            h, attn_o, c = super()._attn_rows(bp, x, c, pos, write, codec,
                                              window, kind)
        kernel = self.kernel_form("step")
        self.attn_forms[rule.kind][rule.forms[1]] = (
            "step_kernel" if kernel else "step_jnp")
        leaves = LayerLeaves(c, codec.layer, rule.whole if kernel else ())
        o = rule.step(rule.of(bp), h, leaves, pos, cfg=cfg,
                      compute_dtype=self.compute_dtype, kernel=kernel,
                      layer=codec.layer)
        if rule.beside is not None:
            o = rule.beside(attn_o, o, cfg)
        return h, o, leaves.cache
