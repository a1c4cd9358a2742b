"""The driver of a serving configuration whose reference's embedding and
head are scaled by the CONFIGURATION's multipliers (Falcon-H1's muP
`embedding_multiplier` and `lm_head_multiplier`): `run` is `serve.run`;
`check_served` and `served_margins` are `serve_dots`' — served tokens
teacher-forced through the plain reference, LAYER OUTER, SEQUENCE INNER, the
weights drawn one layer at a time, the head on the served rows only — with
the configuration handed to the reference's `embed(cfg, wte, ids)` and
`head(cfg, ln_f, kernel, x)`, so that the two multipliers are the
reference's own and not the driver's. `serve_dots.served_margins` calls both
without a configuration, which is why this is a module of its own; the
padding and the rounding of rows are `serve_dots`'.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.serve import run  # noqa: F401 — the driver's `run`
from chipbench.serve_dots import PAD_TO, ROWS_TO

__all__ = ["run", "served_margins", "check_served"]


def served_margins(reference, cfg, parts, prompts, tokens, **wrong) -> dict:
    """`serve_dots.served_margins` with `cfg` handed to the reference's
    `embed` and `head`. `parts`: a tree whose layers are made when popped
    (`registry.ParamParts`; a plain dict of a whole tree does too);
    `wrong`: one of the reference's arguments set wrong (the controls)."""
    import importlib

    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"chipbench.reference.{reference}")
    seqs = [np.concatenate([p, np.asarray(t, np.int32)])
            for p, t in zip(prompts, tokens)]
    longest = max(len(s) for s in seqs)
    padded = -(-longest // PAD_TO) * PAD_TO if longest > PAD_TO else longest
    with jax.default_matmul_precision("highest"):
        xs = []
        for seq in seqs:
            ids = np.zeros((padded,), np.int32)
            ids[:len(seq)] = seq
            xs.append(np.asarray(ref.embed(cfg, parts["wte"], ids)))
        for i in range(cfg.n_layer):
            p = parts.pop(f"h_{i}")
            kw = ref.layer_args(cfg, i, **wrong)
            for s, x in enumerate(xs):
                xs[s] = np.asarray(ref.layer(p, jnp.asarray(x), **kw))
            for leaf in jax.tree.leaves(p):
                if isinstance(leaf, jax.Array):
                    leaf.delete()
        served, sig = [], []
        for p, t, seq, x in zip(prompts, tokens, seqs, xs):
            # the rows that predicted the served tokens, their count
            # rounded up (the last repeated) so that few shapes compile
            first, n = len(p) - 1, len(t)
            rows = np.minimum(first + np.arange(-(-n // ROWS_TO) * ROWS_TO),
                              first + n - 1)
            logits = ref.head(cfg, parts["ln_f"], parts["lm_head"]["kernel"],
                              jnp.asarray(x[rows]))[:n]
            chosen = jnp.take_along_axis(
                logits, jnp.asarray(seq[len(p):])[:, None], axis=-1)[:, 0]
            served.append(np.asarray(logits.max(-1) - chosen))
            sig.append(float(np.asarray(logits.std(-1)).mean()))
    served = np.concatenate(served)
    if not np.isfinite(served).all():
        raise RuntimeError("reference margins are not finite")
    return {"worst_margin": float(served.max()),
            "mean_margin": float(served.mean()),
            "argmax_share": float((served == 0.0).mean()),
            "positions": int(served.size),
            "longest_context": longest,
            "mean_logit_sigma": float(np.mean(sig))}


def check_served(facts, *, seed, emit) -> bool:
    """`serve_dots.check_served` over the margins above. Leaves each number
    compared, beside its limit, in `facts["compared"]`."""
    import jax

    from dnn_tpu.registry import get_model

    config = facts["config"]
    t = time.perf_counter()
    spec = get_model(config["run"]["model"])
    res = served_margins(
        config["reference"], spec.config,
        spec.init_parts(jax.random.PRNGKey(seed)),
        facts["check"]["prompts"], facts["check"]["tokens"])
    bound = config["check"]["margin_bound"]
    floor = config["check"]["argmax_floor"]
    emit(phase="check", **res,
         window_streams=facts["check"]["window_streams"], margin_bound=bound,
         argmax_floor=floor, init_s=0.0,
         reference_s=time.perf_counter() - t)
    facts["compared"] = {
        "worst_margin": {"value": res["worst_margin"], "limit": bound,
                         "passes": "at most"},
        "argmax_share": {"value": res["argmax_share"], "limit": floor,
                         "passes": "at least"}}
    return res["worst_margin"] <= bound and res["argmax_share"] >= floor
