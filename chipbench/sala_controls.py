#!/usr/bin/env python3
"""The controls behind `minicpm-sala-pp8-1chip`'s `check` limits: what
`correct` reads when ONE thing is wrong, without a daemon.

    python3 chipbench/sala_controls.py --seed <n> [--context 12288]
        [--rows 512] [--model minicpm-sala-pp8-1chip]

One sequence of `--context` random ids; the plain reference
(`reference/minicpm_sala.py`: the scan over positions, full scores under the
mask of the equations) at "highest" matmul precision is the judge, as in a
run's check. Each control is the same reference at the chip's DEFAULT
precision (what any bfloat16 computation reads) with one thing wrong: its
argmax over the last `--rows` positions plays the served tokens, and the line
gives the share of them that are the judge's argmax and their worst and mean
distance from the judge's largest logit — `argmax_share`, `worst_margin`,
`mean_margin` as `serve_fh1.served_margins` computes them. `sound` is the
reference at default precision with nothing wrong: the ceiling a sound
bfloat16 program can read. Weights are drawn a layer at a time (layer outer,
control inner), as the check draws them.

Controls: a bfloat16 state (rounded after every position); K and V of the
softmax layer in fp8 (e4m3), the nearest precision below the cache's; no
decay; the slopes reversed; the linear kind unrotated; the softmax kind
rotated; the 64 blocks of SMALLEST score; the local window left out; the
initial block not forced; a pooled key seen 16 positions early; scores not
summed over the group (head 0's alone); either gate left out; the output norm
left out; r = 1; `scale_emb` 1; the head's input undivided. One JSON line a
control on stdout."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = {
    "judge": {}, "sound": {},
    "state_bfloat16": {"state_dtype": "bfloat16"},
    "kv_fp8": {"kv_dtype": "float8_e4m3fn"},
    "no_decay": {"decay": False},
    "slopes_reversed": {"slopes": "reversed"},
    "linear_kind_unrotated": {"lin_rope": False},
    "softmax_kind_rotated": {"full_rope": True},
    "smallest_64": {"pick": "smallest"},
    "no_local_window": {"local": False},
    "no_initial_block": {"init": False},
    "pooled_key_a_stride_early": {"early": True},
    "head0_scores_alone": {"group_sum": False},
    "no_gate": {"gate": False},
    "no_output_norm": {"out_norm": False},
    "r_1": {"r": 1.0},
    "scale_emb_1": {"scale_emb": 1.0},
    "head_input_undivided": {"head_div": 1.0}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context", type=int, default=12288)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--model", default="minicpm-sala-pp8-1chip")
    ap.add_argument("--only", default="",
                    help="comma-separated controls (judge is always run)")
    ap.add_argument("--qk_norm_init", type=float, default=None,
                    help="the preset's q/k norm gains' scale, overridden "
                         "(how `assumed`'s value was chosen)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import minicpm_sala as ref
    from dnn_tpu.registry import get_model

    spec = get_model(args.model)
    cfg = spec.config
    if args.qk_norm_init is None:
        parts = spec.init_parts(jax.random.PRNGKey(args.seed))
    else:
        import dataclasses

        from dnn_tpu.models import llama
        from dnn_tpu.registry import ParamParts

        cfg = dataclasses.replace(cfg, qk_norm_init=args.qk_norm_init)
        parts = ParamParts(llama.init_parts(
            jax.random.PRNGKey(args.seed), cfg))
    ids = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, args.context).astype(np.int32)
    only = {"judge", *filter(None, args.only.split(","))}
    controls = {n: w for n, w in CONTROLS.items()
                if not args.only or n in only}

    def run(name, fn, *a, **kw):
        precision = "highest" if name == "judge" else "default"
        with jax.default_matmul_precision(precision):
            return fn(*a, **kw)

    xs = {name: np.asarray(ref.embed(cfg, parts["wte"], ids,
                                     wrong.get("scale_emb")))
          for name, wrong in controls.items()}
    for i in range(cfg.n_layer):
        p = parts.pop(f"h_{i}")
        for name, wrong in controls.items():
            xs[name] = np.asarray(run(
                name, ref.layer, p, jnp.asarray(xs[name]),
                **ref.layer_args(cfg, i, **wrong)))
        for leaf in jax.tree.leaves(p):
            if isinstance(leaf, jax.Array):
                leaf.delete()
    head = parts["lm_head"]["kernel"]
    rows = np.arange(args.context - args.rows, args.context)
    logits = {}
    for name, x in xs.items():
        logits[name] = np.asarray(run(
            name, ref.head, cfg, parts["ln_f"], head, jnp.asarray(x[rows]),
            controls[name].get("head_div")))
    judge = logits.pop("judge")
    for name, got in logits.items():
        served = got.argmax(-1)
        margin = judge.max(-1) - judge[np.arange(args.rows), served]
        print(json.dumps({
            "control": name, "seed": args.seed, "context": args.context,
            "qk_norm_init": cfg.qk_norm_init,
            "positions": int(args.rows),
            "argmax_share": float((margin == 0.0).mean()),
            "worst_margin": float(margin.max()),
            "mean_margin": float(margin.mean()),
            "logit_sigma": float(judge.std(-1).mean())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
