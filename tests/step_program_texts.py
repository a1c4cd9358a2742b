"""The lowered text of the serving step programs at test size: what
`tests/test_step_programs.py` holds ten families' presets to, so that a
change made for one family is seen to leave the others' programs as they
were. `python tests/step_program_texts.py` prints {preset: {program: sha256
of its StableHLO text}} for the checkout it runs in — on the chip
(`JAX_PLATFORMS=tpu`) the text holds the Mosaic calls the cells run.

What `tests/step_program_texts_parent.json` records, and when: `gpt2-test`,
`olmoe-test` and `keye-test` on the commit before PR 35 (PR 36 re-recorded
the two MoE presets' chunk and decode programs, whose layer loops keep the
expert stacks whole since; PR 38 every chunk and finish program — the head
moved from the one into the other — and no decode program); `joyai-test`
and `dots3-test` on PR 43's parent; `k-exaone-test` on PR 47's own commit;
the four STATE families (`STATE_PRESETS`: Solar Open 2, Brumby, Falcon-H1,
MiniCPM-SALA) on PR 60's parent, before their four adapters became
`models/state_kind.py`'s one — in the plain form and, as
"<preset>@interpret", with the family's kernels interpreted (the
retention-step, ssm-step and block-list calls with their aliasing and
scalar prefetch are then in the text; `minicpm-sala-test`'s and
`solar-open2-test`'s heads are 16 wide, where the families keep
lin-step's and the chunked delta rule's plain forms: PR 61, whose kernel
for the whole chunked rule is built for heads of 128, re-recorded
`solar-open2-test@interpret`'s chunk program, which held PR 47's scan
kernel until then). PR 63 re-recorded the `_prefill_chunk` of all fourteen
cases and nothing else: the transient row is the chunk program's carry
since (`paged_kvcache.scan_rows`), written a chunk's positions at a time;
every `_prefill_finish` and `_decode` hash stayed letter for letter. PR 65
re-recorded the `_prefill_chunk` and `_decode` of the presets with an
expert layer and of nothing else (`olmoe-test`, `keye-test`, `joyai-test`,
`dots3-test`, `k-exaone-test`, `solar-open2-test` and its "@interpret"
case): `parallel/moe.route_rows` takes the sorted experts from the sort's
keys and the group sizes from a compare-and-sum, `moe_ffn_grouped` the
inverse permutation from an argsort, a share's mask is a flag where a row
is summed, and the stats a layer call hands back are five; `gpt2-test`,
`brumby-test`, `falcon-h1-test`, `minicpm-sala-test` and every
`_prefill_finish` stayed letter for letter."""

import base64
import functools
import hashlib
import json
import os
import re
import sys

PRESETS = ("gpt2-test", "olmoe-test", "keye-test", "joyai-test",
           "dots3-test", "k-exaone-test", "solar-open2-test", "brumby-test",
           "falcon-h1-test", "minicpm-sala-test")
#: the families whose layers keep a state (models/state_kind.py): recorded
#: in the plain form AND, under "<preset>@interpret", in the kernel form
STATE_PRESETS = PRESETS[6:]
PROGRAMS = ("_prefill_chunk", "_prefill_finish", "_decode")


@functools.cache
def _model(preset):
    """(spec, config, prepared params): one init a preset a process."""
    import jax

    from dnn_tpu.models.gpt import prepare_stacked
    from dnn_tpu.registry import get_model

    spec = get_model(preset)
    return spec, spec.config, prepare_stacked(
        dict(spec.init(jax.random.PRNGKey(0))), spec.config)


def without_kernel_locations(text):
    """A lowered text with every Mosaic kernel's serialized body (MLIR
    bytecode: on the chip, or lowered for a described one) replaced by the
    sha256 of its module printed WITHOUT debug locations: the bytecode
    carries the files and lines of the Python frames that called the
    kernel, which moving an adapter changes and the device never sees. A
    text without such a call (the CPU's, interpreted kernels') is itself."""
    def body(m):
        from jax._src.interpreters import mlir
        from jax._src.lib.mlir import ir

        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the kernel's own dialect
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(2))).operation \
                .get_asm(enable_debug_info=False)
        return m.group(1) + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)', body, text)


def texts(preset):
    """{program: lowered text} of one preset's batcher: the programs it
    dispatches, lowered from the arguments of their first real calls.
    "<preset>@<form>" sets the family's `attn_kernel` to <form>."""
    from dnn_tpu.runtime.serving import ContinuousBatcher
    from tests.test_chip_compile import first_calls

    preset, _, attn_kernel = preset.partition("@")
    spec, cfg, prepared = _model(preset)
    # "auto" pages whatever has a position axis (Brumby's cache has none)
    opts = dict(slots=3, max_len=64, prompt_pad=16, kv="auto", block_len=8)
    if "family_rows" in spec.extras:
        opts["family"] = spec.extras["family_rows"]()
    if attn_kernel:
        opts["family"].attn_kernel = attn_kernel
    b = ContinuousBatcher(cfg, prepared, **opts)
    calls = first_calls([(b, PROGRAMS)], prompt_len=21)
    return {name: without_kernel_locations(fn.lower(*args).as_text())
            for name, (fn, args) in calls.items()}


#: every recorded case: the presets, and the state presets' kernel form
CASES = (*PRESETS, *(f"{p}@interpret" for p in STATE_PRESETS))


def hashes(case):
    return {name: hashlib.sha256(t.encode()).hexdigest()
            for name, t in texts(case).items()}


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.getcwd())
    table = {}
    for case in CASES:
        try:
            table[case] = hashes(case)
        except Exception as e:  # a test preset's widths under a real kernel
            # (the chip's Mosaic refuses tiles the interpreter takes)
            table[case] = {"error": f"{type(e).__name__}: {e}"[:200]}
    print(json.dumps(table, indent=1, sort_keys=True))
