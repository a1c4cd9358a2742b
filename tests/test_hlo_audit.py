"""Static HLO bytes audit (utils/hlo_audit.py): parser pins for both
program-text formats, plus the decode-step regressions that answer the
long-context hypotheses of a dense cache on paper —

  (a) cache-sized TRANSPOSE: absent at the StableHLO level for every
      decode step (the program never demands a transposed cache copy);
  (b) cache-sized COPY: present in the backend-optimized unbucketed step
      (the scan/carry structure materializes cache-scale buffers), and
      ABSENT at allocation scale in the bucketed step — bucketing bounds
      every materialized buffer by the live bucket, not max_len."""

import jax
import jax.numpy as jnp

from dnn_tpu.models import gpt
from dnn_tpu.utils import hlo_audit as H

CFG = gpt.GPTConfig(block_size=256, vocab_size=128, n_layer=2, n_head=2,
                    n_embd=32)


def test_parser_stablehlo_format():
    text = """
    %3 = stablehlo.transpose %2, dims = [0, 1, 3, 2] : (tensor<8x12x256x64xf32>) -> tensor<8x12x64x256xf32>
    %4 = stablehlo.add %3, %3 : tensor<8x12x64x256xf32>
    %5 = stablehlo.constant dense<0.0> : tensor<f32>
    """
    rows = H.op_result_sizes(text)
    assert ("transpose", 8 * 12 * 256 * 64) in rows
    assert ("add", 8 * 12 * 256 * 64) in rows
    assert ("constant", 1) in rows
    assert H.count_cache_sized(text, 8 * 12 * 256 * 64) == {"transpose": 1}


def test_parser_hlo_format():
    text = """
    %copy.1 = f32[4,8,12,512,64]{4,3,2,1,0} copy(f32[4,8,12,512,64]{4,3,2,1,0} %p.1)
    %transpose.2 = bf16[8,12,64,512]{3,2,1,0} transpose(bf16[8,12,512,64]{3,2,1,0} %p.2), dimensions={0,1,3,2}
    %add.3 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
    """
    counts = H.count_cache_sized(text, 8 * 12 * 512 * 64)
    assert counts == {"copy": 1, "transpose": 1}
    assert H.count_cache_sized(text, 10 ** 12) == {}


def _steps():
    alloc = 256  # the serving allocation (max_len)
    bucket = 32  # a live bucket at position << alloc
    step_u, args_u, layer_alloc = H.gpt_decode_step(CFG, batch=2,
                                                    s_max=alloc)
    step_b, args_b, _ = H.gpt_decode_step(CFG, batch=2, s_max=bucket)
    return (step_u, args_u), (step_b, args_b), layer_alloc


def test_stablehlo_demands_no_cache_sized_transpose_or_copy():
    """Hypothesis (a) at the program level: the traced decode step never
    asks for a transposed/copied cache — for the unbucketed AND bucketed
    programs alike."""
    (step_u, args_u), (step_b, args_b), layer_alloc = _steps()
    assert H.audit_decode_step(step_u, args_u, layer_alloc)["total"] == 0
    assert H.audit_decode_step(step_b, args_b, layer_alloc)["total"] == 0


def test_optimized_unbucketed_step_carries_its_cache_in_place():
    """Hypothesis (b) on this host's backend, as it stands since ISSUE 63:
    the compiled unbucketed decode step carried cache-scale copies while
    the cache rode its layer loop as xs in and ys out (a scan cannot alias
    the two: the structural 2x+ traffic multiplier that bucketing bounded);
    the loop now CARRIES the cache (`paged_kvcache.scan_rows`) and the
    compiled step holds none. The CPU lowering's answer, a proxy for the
    chip's."""
    (step_u, args_u), _, layer_alloc = _steps()
    out = H.audit_decode_step(step_u, args_u, layer_alloc, optimize=True)
    assert out["counts"].get("transpose", 0) == 0  # (a) stays dead
    assert out["counts"].get("copy", 0) == 0       # (b) is gone


def test_optimized_bucketed_step_materializes_nothing_allocation_sized():
    """THE bucketing regression: at a live bucket << max_len, no buffer
    of allocation scale (one max_len cache layer or bigger) appears in
    the compiled step — every materialization is bounded by the bucket."""
    _, (step_b, args_b), layer_alloc = _steps()
    out = H.audit_decode_step(step_b, args_b, layer_alloc, optimize=True)
    assert out["total"] == 0, (
        f"bucketed decode step materialized allocation-sized buffers: "
        f"{out['counts']}")


def test_eval_shape_costs_no_memory():
    """The audit rides abstract shapes end-to-end: a 1B-scale config
    lowers without building weights (only the StableHLO level — no
    backend compile — so this stays fast in CI)."""
    big = gpt.GPTConfig(block_size=2048, vocab_size=50257, n_layer=24,
                        n_head=16, n_embd=1024)
    step, args, layer = H.gpt_decode_step(big, batch=8, s_max=2048,
                                          compute_dtype=jnp.bfloat16,
                                          kv_dtype=jnp.bfloat16)
    out = H.audit_decode_step(step, args, layer)
    assert out["total"] == 0
    assert out["backend"] == "none (StableHLO)"
