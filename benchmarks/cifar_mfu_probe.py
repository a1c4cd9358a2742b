"""CIFAR CNN MFU experiments — close (or explain) the gap to the roofline cap.

The measured forward sits well below the model's own roofline cap
(benchmarks/RESULTS.md row 1; BASELINE.md's arithmetic-intensity argument
puts the cap around 22% MFU at B=256 — the CNN streams too many
activation bytes per FLOP for the MXU to stay busy). This probe times
controlled variants to find which structural lever moves the number:

  1. batch scaling (256..4096): amortize fixed overheads, give XLA bigger
     GEMM tiles per conv, and raise arithmetic intensity (the weight
     stream amortizes over more images — the roofline cap itself grows
     with batch);
  2. an UNPADDED conv1 control (the model now zero-pads input channels
     3->8 on TPU by default — the lever this probe discovered; the
     control keeps the degenerate cin=3 contraction measurable);
  3. conv-segment-only timing, to locate the time between the conv pair
     and the fc pair.

Each exact variant asserts numerical parity with the baseline forward
before its number is accepted. A forward here is sub-millisecond, so rep
counts are large (the slope method's two points must be separated by >>
the host's sync jitter).

Usage: python benchmarks/cifar_mfu_probe.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

from dnn_tpu.models import cifar
from dnn_tpu.utils.flops import cifar_forward_flops, mfu
from dnn_tpu.utils.timing import device_time


def _emit(**row):
    print(json.dumps(row), flush=True)


def _ips(fn, *args, batch):
    dt = device_time(fn, *args, n1=100, n2=400, trials=5)
    return batch / dt


def main():
    params = cifar.init(jax.random.PRNGKey(0))
    base_fn = jax.jit(cifar.make_apply(compute_dtype=jnp.bfloat16))
    flops1 = cifar_forward_flops(1)

    # -- 1. batch scaling ---------------------------------------------------
    for batch in (256, 1024, 2048, 4096):
        x = cifar.example_input(batch_size=batch)
        ips = _ips(base_fn, params, x, batch=batch)
        _emit(variant=f"baseline_b{batch}", images_per_sec=round(ips, 1),
              mfu=round(mfu(flops1, ips) or 0, 4))

    batch = 1024
    x = cifar.example_input(batch_size=batch)
    ref = np.asarray(base_fn(params, x))

    # -- 2. UNPADDED control --------------------------------------------
    # cifar._seg_conv1 now pads cin 3->8 on TPU by default (the lever this
    # probe originally discovered: 19.7% -> 39.1% MFU at B=1024). The
    # baseline above therefore already runs padded; this control runs the
    # ORIGINAL unpadded conv1 so the lever stays measurable — expect the
    # control to be ~2x SLOWER than the baseline on a v5e.
    from dnn_tpu.ops.nn import conv2d, max_pool2d, relu

    @jax.jit
    def nopad_fn(p, xx):
        xx = xx.astype(jnp.bfloat16)
        h = max_pool2d(relu(conv2d(p["conv1"], xx,
                                   compute_dtype=jnp.bfloat16)))
        h = cifar._seg_conv2(p, h, compute_dtype=jnp.bfloat16)
        h = cifar._seg_fc1(p, h, compute_dtype=jnp.bfloat16)
        return cifar._seg_fc2(p, h, compute_dtype=jnp.bfloat16)

    np.testing.assert_allclose(np.asarray(nopad_fn(params, x)), ref,
                               atol=2e-2, rtol=2e-2)
    ips = _ips(nopad_fn, params, x, batch=batch)
    _emit(variant=f"cin_nopad_control_b{batch}",
          images_per_sec=round(ips, 1),
          mfu=round(mfu(flops1, ips) or 0, 4))

    # -- 3. segment split: convs only vs fcs only ---------------------------
    @jax.jit
    def convs_fn(p, xx):
        xx = xx.astype(jnp.bfloat16)
        h = cifar._seg_conv1(p, xx, compute_dtype=jnp.bfloat16)
        return cifar._seg_conv2(p, h, compute_dtype=jnp.bfloat16)

    flat = np.asarray(convs_fn(params, x))

    @jax.jit
    def fcs_fn(p, hh):
        h2 = cifar._seg_fc1(p, hh, compute_dtype=jnp.bfloat16)
        return cifar._seg_fc2(p, h2, compute_dtype=jnp.bfloat16)

    hh = jnp.asarray(flat)
    ips_c = _ips(convs_fn, params, x, batch=batch)
    ips_f = _ips(fcs_fn, params, hh, batch=batch)
    _emit(variant=f"convs_only_b{batch}", images_per_sec=round(ips_c, 1),
          share_of_forward_pct=round(100 * (batch / ips_c)
                                     / (batch / ips_c + batch / ips_f), 1))
    _emit(variant=f"fcs_only_b{batch}", images_per_sec=round(ips_f, 1),
          share_of_forward_pct=round(100 * (batch / ips_f)
                                     / (batch / ips_c + batch / ips_f), 1))


if __name__ == "__main__":
    main()
