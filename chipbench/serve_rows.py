"""The driver of a serving configuration whose reference logits do not fit
beside its weights all at once: `run` is `serve.run`; `check_served` is
`serve.check_served` computed one sequence at a time.

`check.served_margins` pads every checked sequence to the model's context
and takes the whole (n, context, vocab) float32 logits in one call. For a
context of 4096 and a vocabulary of 50304 that is 0.82 GB a sequence,
6.6 GB for eight, three copies live inside `std`, beside 5.9 GB of float32
weights: it cannot run on a 16 GB chip. Here each sequence goes through
the reference alone and leaves five numbers per served position; the
statistics and the two tests are `check.served_margins`' and
`serve.check_served`'s (`tests/test_olmoe_cells.py` holds them equal).
"""

from __future__ import annotations

import numpy as np

from chipbench import serve
from chipbench.serve import run  # noqa: F401 — the driver's `run`

__all__ = ["run", "served_margins", "check_served"]


def served_margins(reference, cfg, params, prompts, tokens) -> dict:
    """`check.served_margins`, one sequence at a time: worst and mean
    (reference max logit - reference logit of the served token) over every
    served position, the share of served tokens that ARE the reference
    argmax, and the mean logit sigma. Every sequence is padded to the
    model's full context, so the reference compiles for one shape."""
    import jax.numpy as jnp

    from chipbench import check

    served, sig, longest = [], [], 0
    for p, t in zip(prompts, tokens):
        seq = np.concatenate([p, np.asarray(t, np.int32)])
        longest = max(longest, len(seq))
        ids = np.zeros((1, cfg.block_size), np.int32)
        ids[0, :len(seq)] = seq
        # the rows that predicted the served tokens
        sl = slice(len(p) - 1, len(p) + len(t) - 1)
        logits = check._reference_logits(
            reference, cfg, params, jnp.asarray(ids))[0, sl]
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
    """`serve.check_served` with the margins taken a sequence at a time."""
    return serve.check_served(facts, seed=seed, emit=emit,
                              margins=served_margins)
