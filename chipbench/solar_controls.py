#!/usr/bin/env python3
"""The controls behind `solar-open2-250b-ep8-1chip`'s `check` limits: what
`correct` reads when ONE thing is wrong, without a daemon.

    python3 chipbench/solar_controls.py --seed <n> [--context 4096] [--rows 1024]
        [--model solar-open2-250b-ep8-1chip]

One sequence of `--context` random ids; the plain reference
(`reference/solar.py`) at "highest" matmul precision is the judge, as in a
run's check. Each control is the same reference at the chip's DEFAULT
precision (what any bfloat16 computation reads) with one thing wrong: its
argmax over the last `--rows` positions plays the served tokens, and the
line gives the share of them that are the judge's argmax and their worst
and mean distance from the judge's largest logit — `argmax_share`,
`worst_margin`, `mean_margin` as `serve_dots.served_margins` computes them.
`sound` is the reference at default precision with nothing wrong: the
ceiling a sound bfloat16 program can read. Weights are drawn a layer at a
time (layer outer, control inner), as the check draws them.

Controls: every matmul weight the daemon holds in bfloat16 rounded to fp8
(e4m3), the nearest precision below; the state held in bfloat16 (rounded
after every position); beta not doubled; a decay a HEAD (the channels'
mean) instead of a channel; the convolution left out; pad positions let
into the state (the sequence with the pad ids of a 1024-position chunk
after its first `context - rows - 300` ids, which the softmax layer's other
rows do not see: what a chunk program that was not told its count of real
positions computes); the softmax layer rotated; its gate left out; the
shared expert left out; the selection bias left out. One JSON line a
control on stdout."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAD_TO = 1024  # the cell's prompt_pad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context", type=int, default=4096)
    ap.add_argument("--rows", type=int, default=1024)
    ap.add_argument("--model", default="solar-open2-250b-ep8-1chip")
    ap.add_argument("--pad_to", type=int, default=PAD_TO)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import solar as ref
    from dnn_tpu.ops.nn import matmul_operand
    from dnn_tpu.registry import get_model

    spec = get_model(args.model)
    cfg = spec.config
    parts = spec.init_parts(jax.random.PRNGKey(args.seed))
    ids = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, args.context).astype(np.int32)
    controls = {
        "judge": {}, "sound": {}, "fp8_weights": {},
        "state_bfloat16": {"state_dtype": "bfloat16"},
        "beta_not_doubled": {"beta_scale": 1.0},
        "decay_a_head": {"head_decay": True},
        "no_convolution": {"conv": False},
        "pad_into_state": {},
        "full_layer_rotated": {"rope": True},
        "no_gate": {"gate": False},
        "no_shared_expert": {"shared": False},
        "no_selection_bias": {"bias": False}}
    # the pad control's own sequence: a prompt that ends 300 short of the
    # scored rows, its chunk's pad ids, then the rest
    cut = args.context - args.rows - 300
    n_pad = -cut % args.pad_to
    padded = np.concatenate([ids[:cut], np.zeros(n_pad, np.int32), ids[cut:]])
    skip = np.zeros(len(padded), bool)
    skip[cut:cut + n_pad] = True

    def fp8(path, leaf):
        if matmul_operand(path) and jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype)
        return leaf

    def run(name, fn, *a, **kw):
        precision = "highest" if name == "judge" else "default"
        with jax.default_matmul_precision(precision):
            return fn(*a, **kw)

    x0 = np.asarray(ref.embed(parts["wte"], ids))
    xs = {name: x0 for name in controls}
    xs["pad_into_state"] = np.asarray(ref.embed(parts["wte"], padded))
    for i in range(cfg.n_layer):
        p = parts.pop(f"h_{i}")
        p8 = jax.tree_util.tree_map_with_path(fp8, p)
        for name, wrong in controls.items():
            kw = ref.layer_args(cfg, i, **wrong)
            if name == "pad_into_state":
                kw["skip"] = jnp.asarray(skip)
            xs[name] = np.asarray(run(
                name, ref.layer, p8 if name == "fp8_weights" else p,
                jnp.asarray(xs[name]), **kw))
        for leaf in jax.tree.leaves((p, p8)):
            if isinstance(leaf, jax.Array):
                leaf.delete()
    head = parts["lm_head"]["kernel"]
    logits = {}
    for name, x in xs.items():
        rows = np.arange(len(x) - args.rows, len(x))
        logits[name] = np.asarray(run(
            name, ref.head, parts["ln_f"],
            fp8(("lm_head", "kernel"), head) if name == "fp8_weights"
            else head, jnp.asarray(x[rows]), eps=float(cfg.rms_eps)))
    judge = logits.pop("judge")
    for name, got in logits.items():
        served = got.argmax(-1)
        margin = judge.max(-1) - judge[np.arange(args.rows), served]
        print(json.dumps({
            "control": name, "seed": args.seed, "context": args.context,
            "positions": int(args.rows),
            "argmax_share": float((margin == 0.0).mean()),
            "worst_margin": float(margin.max()),
            "mean_margin": float(margin.mean()),
            "logit_sigma": float(judge.std(-1).mean())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
