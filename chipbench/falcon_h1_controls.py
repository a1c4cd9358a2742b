#!/usr/bin/env python3
"""The controls behind `falcon-h1-34b-pp8-1chip`'s `check` limits: what
`correct` reads when ONE thing is wrong, without a daemon.

    python3 chipbench/falcon_h1_controls.py --seed <n> [--context 1536]
        [--rows 512] [--model falcon-h1-34b-pp8-1chip] [--pad_to 256]

One sequence of `--context` random ids; the plain reference
(`reference/falcon_h1.py`: the scan over positions, full softmax) at
"highest" matmul precision is the judge, as in a run's check. Each control is
the same reference at the chip's DEFAULT precision (what any bfloat16
computation reads) with one thing wrong: its argmax over the last `--rows`
positions plays the served tokens, and the line gives the share of them that
are the judge's argmax and their worst and mean distance from the judge's
largest logit — `argmax_share`, `worst_margin`, `mean_margin` as
`serve_fh1.served_margins` computes them. `sound` is the reference at default
precision with nothing wrong: the ceiling a sound bfloat16 program can read.
Weights are drawn a layer at a time (layer outer, control inner), as the
check draws them.

Controls: every matmul weight the daemon holds in bfloat16 rounded to fp8
(e4m3), the nearest precision below; the state-space branch left out; the
attention branch left out; the state reset at a chunk's edge (every
`--pad_to` positions); the decay left out (a = 1); dt left out of the input
term; B and C of group 0 used by every head; the gate applied AFTER the norm;
the norm over all channels, not by group; the convolution's tail dropped at a
chunk's edge; `ssm_multipliers` all 1; `key_multiplier` 1; the rotary
embedding left out. One JSON line a control on stdout."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAD_TO = 256  # the cell's prompt_pad

CONTROLS = {
    "judge": {}, "sound": {}, "fp8_weights": {},
    "no_ssm_branch": {"ssm": False},
    "no_attention_branch": {"attn": False},
    "state_reset_at_chunk_edge": {"reset": PAD_TO},
    "no_decay": {"decay": False},
    "dt_left_out_of_input": {"dt_in": False},
    "group0_for_every_head": {"group0": True},
    "gate_after_norm": {"gate_first": False},
    "norm_over_all_channels": {"grouped_norm": False},
    "conv_tail_dropped_at_chunk_edge": {"tail_reset": PAD_TO},
    "ssm_multipliers_all_1": {"ssm_mup": False},
    "key_multiplier_1": {"key_mup": False},
    "no_rotary_embedding": {"rope": False}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context", type=int, default=1536)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--model", default="falcon-h1-34b-pp8-1chip")
    ap.add_argument("--pad_to", type=int, default=PAD_TO)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import falcon_h1 as ref
    from dnn_tpu.ops.nn import matmul_operand
    from dnn_tpu.registry import get_model

    spec = get_model(args.model)
    cfg = spec.config
    parts = spec.init_parts(jax.random.PRNGKey(args.seed))
    ids = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, args.context).astype(np.int32)
    controls = {name: {k: args.pad_to if v == PAD_TO and k in (
        "reset", "tail_reset") else v for k, v in wrong.items()}
        for name, wrong in CONTROLS.items()}

    def fp8(path, leaf):
        if matmul_operand(path) and jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype)
        return leaf

    def run(name, fn, *a, **kw):
        precision = "highest" if name == "judge" else "default"
        with jax.default_matmul_precision(precision):
            return fn(*a, **kw)

    x0 = np.asarray(ref.embed(cfg, parts["wte"], ids))
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
            name, ref.head, cfg, parts["ln_f"],
            fp8(("lm_head", "kernel"), head) if name == "fp8_weights"
            else head, jnp.asarray(x[rows])))
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
