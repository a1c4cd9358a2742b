#!/usr/bin/env python3
"""The controls behind `brumby-14b-pp8-1chip`'s `check` limits: what
`correct` reads when ONE thing is wrong, without a daemon.

    python3 chipbench/brumby_controls.py --seed <n> [--context 2560] [--rows 512]
        [--model brumby-14b-pp8-1chip]

One sequence of `--context` random ids; the plain reference
(`reference/brumby.py`, the quadratic form) at "highest" matmul precision
is the judge, as in a run's check. Each control is the same reference at
the chip's DEFAULT precision (what any bfloat16 computation reads) with one
thing wrong: its argmax over the last `--rows` positions plays the served
tokens, and the line gives the share of them that are the judge's argmax
and their worst and mean distance from the judge's largest logit —
`argmax_share`, `worst_margin`, `mean_margin` as
`serve_dots.served_margins` computes them. `sound` is the reference at
default precision with nothing wrong: the ceiling a sound bfloat16 program
can read. Weights are drawn a layer at a time (layer outer, control inner),
as the check draws them.

Controls: every matmul weight the daemon holds in bfloat16 rounded to fp8
(e4m3), the nearest precision below; degree 1; the gate left out (g = 1);
the normaliser left out; the gate's heads averaged; the rotary embedding
left out; the q/k norm left out; the state reset at a chunk's edge (every
`--pad_to` positions: what a program that dropped the state between prefill
chunks computes). One JSON line a control on stdout."""

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
    ap.add_argument("--context", type=int, default=2560)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--model", default="brumby-14b-pp8-1chip")
    ap.add_argument("--pad_to", type=int, default=PAD_TO)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import brumby as ref
    from dnn_tpu.ops.nn import matmul_operand
    from dnn_tpu.registry import get_model

    spec = get_model(args.model)
    cfg = spec.config
    parts = spec.init_parts(jax.random.PRNGKey(args.seed))
    ids = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, args.context).astype(np.int32)
    controls = {
        "judge": {}, "sound": {}, "fp8_weights": {},
        "degree_1": {"degree": 1},
        "no_gate": {"gate": False},
        "no_normaliser": {"normaliser": False},
        "gate_heads_averaged": {"head_gate": False},
        "no_rotary_embedding": {"rope": False},
        "no_qk_norm": {"qk_norm": False},
        "state_reset_at_chunk_edge": {"reset": args.pad_to}}

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
    for i in range(cfg.n_layer):
        p = parts.pop(f"h_{i}")
        p8 = jax.tree_util.tree_map_with_path(fp8, p)
        for name, wrong in controls.items():
            xs[name] = np.asarray(run(
                name, ref.layer, p8 if name == "fp8_weights" else p,
                jnp.asarray(xs[name]), **ref.layer_args(cfg, i, **wrong)))
        for leaf in jax.tree.leaves((p, p8)):
            if isinstance(leaf, jax.Array):
                leaf.delete()
    head = parts["lm_head"]["kernel"]
    rows = np.arange(args.context - args.rows, args.context)
    logits = {}
    for name, x in xs.items():
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
