"""The seam between a state rule and its ONE adapter (models/state_kind.py):
every `Rule` — the delta rule, power retention, Mamba-2, fixed-decay linear
attention — called through the one signature, `chunk(p, h, leaves,
start_pos, n_real, ...)` and `step(p, h, leaves, pos, ...)` over a mapping
of the kind's leaves by name, at its family's test size. A fifth rule
entered with another shape of call fails here before it reaches a batcher.
Everything float32 on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import llama, state_kind
from dnn_tpu.registry import get_model

PAD, N_REAL = 16, 11

#: the config field a rule answers to -> a test preset that sets it (the
#: linear-attention preset's heads widened to the 128 lanes its step kernel
#: is built for, `Rule.fits`)
PRESETS = {
    "kda": ("solar-open2-test", {}),
    "retention": ("brumby-test", {}),
    "mamba": ("falcon-h1-test", {}),
    "lightning": ("minicpm-sala-test", {"lightning": llama.LightningConfig(
        n_head=2, head_dim=128, chunk=8)}),
}


def test_the_table_holds_the_four_rules_and_the_lookup_finds_them():
    assert [r.field for r in state_kind._rules()] == list(PRESETS)
    assert state_kind.config_rule(get_model("gpt2-test").config) is None
    assert state_kind.config_rule(get_model("keye-test").config) is None
    sala = get_model("minicpm-sala-test").config
    assert state_kind.layer_rule(sala, "linear").field == "lightning"
    assert state_kind.layer_rule(sala, "full") is None
    # a config without `layer_types`: the rule in every layer
    falcon = get_model("falcon-h1-test").config
    assert state_kind.layer_rule(falcon, None).field == "mamba"


@pytest.mark.parametrize("field", list(PRESETS))
def test_a_rules_chunk_form_is_its_step_form_walked(field):
    """The chunk form over a padded prompt and the step form walked over
    its real positions leave the same leaves and give the same outputs;
    through a layer of a pool (`LayerLeaves`) the step moves that layer's
    slots alone; and the kernel form of the step, interpreted on the WHOLE
    leaves the record names, is the plain form."""
    preset, widths = PRESETS[field]
    cfg = dataclasses.replace(get_model(preset).config, **widths)
    rule = state_kind.config_rule(cfg)
    assert rule.field == field and state_kind.layer_rule(cfg, rule.kind)
    p = rule.of(llama.init_block(jax.random.PRNGKey(1), cfg,
                                 include_mlp=False, kind=rule.kind))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, PAD, cfg.n_embd))
    names = set(rule.slot_leaves(cfg))
    kw = dict(cfg=cfg, compute_dtype=None)

    chunked = state_kind.fresh(rule.slot_leaves(cfg), 1, jnp.float32)
    y = rule.chunk(p, h, chunked, 0, jnp.int32(N_REAL), **kw)
    assert y.shape == h.shape and set(chunked) == names

    walked = state_kind.fresh(rule.slot_leaves(cfg), 1, jnp.float32)
    pool = state_kind.fresh(rule.slot_leaves(cfg), 1, jnp.float32, layers=2)
    whole = dict(pool)
    ys = []
    for i in range(N_REAL):
        x, pos = h[:, i:i + 1], jnp.asarray([i], jnp.int32)
        ys.append(rule.step(p, x, walked, pos, **kw))
        layer = state_kind.LayerLeaves(pool, jnp.int32(1))
        through = rule.step(p, x, layer, pos, layer=jnp.int32(1), **kw)
        pool = layer.cache
        assert float(jnp.abs(through - ys[-1]).max()) < 1e-6
        if rule.kernel == "step":
            assert rule.fits(cfg) and set(rule.whole) <= names
            layer = state_kind.LayerLeaves(whole, jnp.int32(1), rule.whole)
            kernel = rule.step(p, x, layer, pos, kernel="interpret",
                               layer=jnp.int32(1), **kw)
            whole = layer.cache
            assert float(jnp.abs(kernel - ys[-1]).max()) < 1e-4
    assert set(walked) == names
    scale = float(jnp.abs(y).max())
    assert float(jnp.abs(jnp.concatenate(ys, 1) - y[:, :N_REAL]).max()
                 ) < 1e-4 * max(scale, 1.0)
    for name in names:
        tol = 1e-5 * max(float(jnp.abs(walked[name]).max()), 1.0)
        assert float(jnp.abs(chunked[name] - walked[name]).max()) < tol, name
        for got in (pool, whole) if rule.kernel == "step" else (pool,):
            assert float(jnp.abs(got[name][1] - walked[name]).max()) < (
                10 * tol), name
            assert not np.asarray(got[name][0]).any(), name
