"""The driver of a serving configuration whose reference cannot afford the
output head on every position: `run` is `serve.run`; `check_served` is
`serve.check_served` with the margins taken one sequence at a time and the
head applied to the SERVED rows only.

`serve_rows.served_margins` pads every checked sequence to the model's
context and takes (context, vocab) float32 logits a sequence. Here the
context is 262 144 positions and the vocabulary 151 936 words: 16 k x
151 936 float32 is 10 GB. The reference module (`reference/keye.py`) hands
out `forward(cfg, params, ids, rows=...)`: the blocks run over the whole
sequence — a selection needs every position before it — and the final norm
and head over the rows that predicted a served token. Sequences are padded
to one length a run (the longest, rounded up to 512), so the reference
compiles once; what is padded lies after every served row and is causal
future to all of them. The statistics and the two tests are
`check.served_margins`' and `serve.check_served`'s (`tests/test_dsa.py`
and `tests/test_mla.py` hold the margins equal on models small enough for
both).
"""

from __future__ import annotations

import numpy as np

from chipbench import serve
from chipbench.serve import run  # noqa: F401 — the driver's `run`

__all__ = ["run", "served_margins", "check_served"]

PAD_TO, ROWS_TO = 512, 64


def served_margins(reference, cfg, params, prompts, tokens) -> dict:
    """Worst and mean (reference max logit - reference logit of the served
    token) over every served position, the share of served tokens that ARE
    the reference argmax, and the mean logit sigma."""
    import importlib

    import jax
    import jax.numpy as jnp

    module = importlib.import_module(f"chipbench.reference.{reference}")
    seqs = [np.concatenate([p, np.asarray(t, np.int32)])
            for p, t in zip(prompts, tokens)]
    longest = max(len(s) for s in seqs)
    padded = -(-longest // PAD_TO) * PAD_TO
    served, sig = [], []
    for p, t, seq in zip(prompts, tokens, seqs):
        ids = np.zeros((padded,), np.int32)
        ids[:len(seq)] = seq
        # the rows that predicted the served tokens, their count rounded
        # up (the last repeated) so that few shapes of the head compile
        first, n = len(p) - 1, len(t)
        rows = np.minimum(first + np.arange(-(-n // ROWS_TO) * ROWS_TO),
                          first + n - 1)
        with jax.default_matmul_precision("highest"):
            logits = module.forward(cfg, params, jnp.asarray(ids),
                                    rows=jnp.asarray(rows))[:n]
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
    """`serve.check_served` with the margins above."""
    return serve.check_served(facts, seed=seed, emit=emit,
                              margins=served_margins)
