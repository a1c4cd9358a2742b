"""The lowered text of the serving step programs at test size: what
`tests/test_mla.py` holds the GPT-2, OLMoE and Keye families to, so that
a change made for one family is seen to leave the others' programs as they
were. `python tests/step_program_texts.py` prints {preset: {program:
sha256 of its StableHLO text}} for the checkout it runs in (the recorded
file `tests/step_program_texts_parent.json` was made so on the commit
before PR 35; PR 36 re-recorded `olmoe-test`'s and `keye-test`'s chunk and
decode programs, whose layer loops keep the expert stacks whole since, and
left `gpt2-test`'s as they were; PR 38 re-recorded every chunk and finish
program — the head moved from the one into the other — and no decode
program; PR 43 added `joyai-test`'s and `dots3-test`'s, recorded on its
parent commit; PR 47 `k-exaone-test`'s, on its own)."""

import hashlib
import json
import os
import sys

PRESETS = ("gpt2-test", "olmoe-test", "keye-test", "joyai-test",
           "dots3-test", "k-exaone-test")
PROGRAMS = ("_prefill_chunk", "_prefill_finish", "_decode")


def texts(preset):
    """{program: lowered text} of one preset's batcher: the programs it
    dispatches, lowered from the arguments of their first real calls."""
    import jax

    from dnn_tpu.models.gpt import prepare_stacked
    from dnn_tpu.registry import get_model
    from dnn_tpu.runtime.serving import ContinuousBatcher
    from tests.test_chip_compile import first_calls

    spec = get_model(preset)
    cfg = spec.config
    prepared = prepare_stacked(dict(spec.init(jax.random.PRNGKey(0))), cfg)
    opts = dict(slots=3, max_len=64, prompt_pad=16, kv="paged", block_len=8)
    if "family_rows" in spec.extras:
        opts["family"] = spec.extras["family_rows"]()
    b = ContinuousBatcher(cfg, prepared, **opts)
    calls = first_calls([(b, PROGRAMS)], prompt_len=21)
    return {name: fn.lower(*args).as_text() for name, (fn, args)
            in calls.items()}


def hashes():
    return {p: {name: hashlib.sha256(t.encode()).hexdigest()
                for name, t in texts(p).items()} for p in PRESETS}


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.getcwd())
    print(json.dumps(hashes(), indent=1, sort_keys=True))
