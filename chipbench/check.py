"""The comparison that decides `correct`, against the plain reference.

Serving cells: the daemon streams tokens, not logits, so each served token
is teacher-forced through the reference (float32, "highest" matmul
precision) on the same weights, and must sit within the configuration's
`margin_bound` of that position's largest reference logit. Exact token
equality is not a sound test: at GPT-2's widths random-init logits are
near-tied and bfloat16 rounding moves the argmax.

Pipeline cell: the engine's logits for whole rows of a batch against the
reference's, as the largest absolute difference.

This module initializes a JAX backend: call it only in a process that may
hold the chip (after the daemon child has exited, or in the one process
of a pipeline cell).
"""

from __future__ import annotations

import numpy as np

__all__ = ["device_info", "init_params", "served_margins", "logits_diff"]


def device_info(need: int, *, rehearse: bool) -> dict:
    """The devices as JAX reports them. Without the rehearsal switch,
    anything but `need` TPU chips is an error."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse and (info["platform"] != "tpu" or len(devs) < need):
        raise RuntimeError(f"need {need} TPU chip(s); JAX found "
                           f"{len(devs)} x {info['platform']} ({info['kind']})")
    return info


def init_params(model: str, seed: int):
    """The program's own seeded random initialisation — the weights the
    daemon and the engine serve for `--seed`."""
    import jax

    from dnn_tpu.registry import get_model

    spec = get_model(model)
    return spec.config, spec.init(jax.random.PRNGKey(seed))


def _reference_logits(reference, cfg, params, ids):
    """`reference` is the configuration file's `"reference"`: the module
    under chipbench/reference/ whose `logits(cfg, params, ids)` is the
    plain forward of that model family."""
    import importlib

    import jax

    module = importlib.import_module(f"chipbench.reference.{reference}")
    with jax.default_matmul_precision("highest"):
        return module.logits(cfg, params, ids)


def served_margins(reference, cfg, params, prompts, tokens) -> dict:
    """Worst and mean (reference max logit - reference logit of the served
    token) over every served position, the share of served tokens that ARE
    the reference argmax, and the mean logit sigma to read them against.
    Sequences are padded to the model's full context, so every run of a
    configuration compiles the reference for one shape."""
    import jax.numpy as jnp

    seqs = [np.concatenate([p, np.asarray(t, np.int32)])
            for p, t in zip(prompts, tokens)]
    ids = np.zeros((len(seqs), cfg.block_size), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    logits = _reference_logits(reference, cfg, params,
                               jnp.asarray(ids))[:, :-1]
    chosen = jnp.take_along_axis(logits, jnp.asarray(ids)[:, 1:, None],
                                 axis=-1)[..., 0]
    margin = np.asarray(logits.max(-1) - chosen)
    sigma = np.asarray(logits.std(-1))
    served, sig = [], []
    for i, (p, t) in enumerate(zip(prompts, tokens)):
        sl = slice(len(p) - 1, len(p) + len(t) - 1)
        served.append(margin[i, sl])
        sig.append(float(sigma[i, sl].mean()))
    served = np.concatenate(served)
    if not np.isfinite(served).all():
        raise RuntimeError("reference margins are not finite")
    return {"worst_margin": float(served.max()),
            "mean_margin": float(served.mean()),
            "argmax_share": float((served == 0.0).mean()),
            "positions": int(served.size),
            "longest_context": max(len(s) for s in seqs),
            "mean_logit_sigma": float(np.mean(sig))}


def logits_diff(reference, cfg, params, ids, got) -> dict:
    """Largest and root-mean-square difference between `got` (rows of the
    engine's logits) and the reference's logits for `ids`."""
    import jax.numpy as jnp

    ref = _reference_logits(reference, cfg, params, jnp.asarray(ids))
    d = jnp.asarray(got, jnp.float32) - ref
    return {"max_abs_diff": float(jnp.abs(d).max()),
            "rms_diff": float(jnp.sqrt((d * d).mean())),
            "logit_sigma": float(ref.std(-1).mean())}
