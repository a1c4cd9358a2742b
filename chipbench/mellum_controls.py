#!/usr/bin/env python3
"""The controls behind `mellum2-12b-a2.5b-pp4-1chip`'s `check` limits: what
`correct` reads when ONE thing is wrong, without a daemon.

    python3 chipbench/mellum_controls.py --seed <n> [--context 12288] [--rows 512]
        [--only a,b] [--model mellum2-12b-a2.5b-pp4-1chip]

One sequence of `--context` random ids (past YaRN's 8192 original positions
and twelve windows deep by default); the plain reference
(`reference/mellum.py`) at "highest" matmul precision is the judge, as in a
run's check. Each control is the same reference at the chip's DEFAULT
precision (what any bfloat16 computation reads) with one thing wrong: its
argmax over the last `--rows` positions plays the served tokens, and the line
gives the share of them that are the judge's argmax and their worst and mean
distance from the judge's largest logit — `argmax_share`, `worst_margin`,
`mean_margin` as `serve_dots.served_margins` computes them. `sound` is the
reference at default precision with nothing wrong: the ceiling a sound
bfloat16 program can read. Weights are drawn a layer at a time (layer outer,
control inner), as the check draws them.

Controls: every matmul weight the daemon holds in bfloat16 rounded to fp8
(e4m3), the nearest precision below; the window ignored in sliding layers;
the full layers on the sliding table (plain RoPE, no factor); the sliding
layers on the YaRN table; the attention factor left out; the weights not
renormalised; q/k norm left out. One JSON line a control on stdout."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = {
    "judge": {}, "sound": {}, "fp8_weights": {},
    "window_ignored": {"window": None},
    "full_on_sliding_table": {"full_table": "sliding"},
    "sliding_on_yarn_table": {"sliding_table": "full"},
    "no_attention_factor": {"attention_factor": 1.0},
    "not_renormalised": {"renorm": False},
    "no_qk_norm": {"qk_norm": False}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context", type=int, default=12288)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--only", default="")
    ap.add_argument("--model", default="mellum2-12b-a2.5b-pp4-1chip")
    ap.add_argument("--expert_out_scale", type=float, default=1.0,
                    help="every expert's down projection times this, for "
                         "measuring what the preset's seeded init should be")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import mellum as ref
    from dnn_tpu.ops.nn import matmul_operand
    from dnn_tpu.registry import get_model

    only = set(filter(None, args.only.split(",")))
    controls = {n: w for n, w in CONTROLS.items()
                if not only or n in only or n == "judge"}
    spec = get_model(args.model)
    cfg = spec.config
    parts = spec.init_parts(jax.random.PRNGKey(args.seed))
    ids = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, args.context).astype(np.int32)

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
        if args.expert_out_scale != 1.0:
            p["moe"]["wd"] = p["moe"]["wd"] * args.expert_out_scale
        p8 = jax.tree_util.tree_map_with_path(fp8, p) \
            if "fp8_weights" in controls else None
        for name, wrong in controls.items():
            xs[name] = np.asarray(run(
                name, ref.layer, p8 if name == "fp8_weights" else p,
                jnp.asarray(xs[name]), **ref.layer_args(cfg, i, **wrong)))
        for leaf in jax.tree.leaves((p, p8)):
            if isinstance(leaf, jax.Array):
                leaf.delete()
    rows = np.arange(args.context - args.rows, args.context)
    head = parts["lm_head"]["kernel"]
    logits = {name: np.asarray(run(
        name, ref.head, parts["ln_f"],
        fp8(("lm_head", "kernel"), head) if name == "fp8_weights" else head,
        jnp.asarray(x[rows]), eps=float(cfg.rms_eps)))
        for name, x in xs.items()}
    judge = logits.pop("judge")
    for name, got in logits.items():
        served = got.argmax(-1)
        margin = judge.max(-1) - judge[np.arange(len(rows)), served]
        print(json.dumps({
            "control": name, "seed": args.seed, "context": args.context,
            "positions": int(len(rows)),
            "argmax_share": float((margin == 0.0).mean()),
            "worst_margin": float(margin.max()),
            "mean_margin": float(margin.mean()),
            "logit_sigma": float(judge.std(-1).mean()),
            **({"expert_out_scale": args.expert_out_scale}
               if args.expert_out_scale != 1.0 else {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
