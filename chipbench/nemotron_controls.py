#!/usr/bin/env python3
"""The controls behind `nemotron-3-super-120b-a12b-ep4-1chip`'s `check`
limits: what `correct` reads when ONE thing is wrong, without a daemon.

    python3 chipbench/nemotron_controls.py --seed <n> [--context 4096]
        [--rows 512] [--only a,b] [--expert_out_scale s]
        [--model nemotron-3-super-120b-a12b-ep4-1chip]

One sequence of `--context` random ids; the plain reference
(`reference/nemotron_h.py`) at "highest" matmul precision is the judge, as in
a run's check. Each control is the same reference at the chip's DEFAULT
precision (what any bfloat16 computation reads) with one thing wrong: its
argmax over the last `--rows` positions plays the served tokens, and the line
gives the share of them that are the judge's argmax and their worst and mean
distance from the judge's largest logit — `argmax_share`, `worst_margin`,
`mean_margin` as `serve_dots.served_margins` computes them. `sound` is the
reference at default precision with nothing wrong: the ceiling a sound
bfloat16 program can read. Weights are drawn a block at a time (block outer,
control inner), as the check draws them.

Controls: every matmul weight the daemon holds in bfloat16 rounded to fp8
(e4m3), the nearest precision below; relu in place of relu^2; the router fed
the latent; the shared expert left out; the scaling 5 left out; the norm
before the gate; a rotary embedding in the attention block; the state held in
bfloat16 between positions. `--expert_out_scale` multiplies every expert's
and shared expert's output projection, for measuring what the preset's seeded
`expert_out_init` should be. One JSON line a control on stdout."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = {
    "judge": {}, "sound": {}, "fp8_weights": {},
    "relu_for_relu2": {"act": "relu"},
    "router_fed_the_latent": {"route_latent": True},
    "no_shared_expert": {"shared": False},
    "no_scaling": {"scale": 1.0},
    "norm_before_gate": {"gate_first": False},
    "rope_in_attention": {"rope": True},
    "state_in_bfloat16": {"state_dtype": "bfloat16"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context", type=int, default=4096)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--only", default="")
    ap.add_argument("--model",
                    default="nemotron-3-super-120b-a12b-ep4-1chip")
    ap.add_argument("--expert_out_scale", type=float, default=1.0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import nemotron_h as ref
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
        if args.expert_out_scale != 1.0 and "moe" in p:
            m, s = p["moe"], args.expert_out_scale
            m["wo"] = m["wo"] * s
            m["shared"]["down"]["kernel"] = m["shared"]["down"]["kernel"] * s
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
