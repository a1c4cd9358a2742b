"""CIFAR-10 CNN model family, TPU-native.

Re-authors the reference's `NeuralNetwork`
(/root/reference/cifar_model_parts.py:6-25):

    conv1 3->32 k3 s1 p1, relu, maxpool 2x2
    conv2 32->64 k3 s1 p1, relu, maxpool 2x2
    flatten -> fc1 4096->512, relu -> fc2 512->10 -> softmax(dim=1)

and its 2-way split (`ModelPart0_2Node` = convs + flatten,
`ModelPart1_2Node` = fcs + softmax — cifar_model_parts.py:29-58), but:

  * NHWC activations / HWIO kernels (TPU MXU layout) instead of NCHW;
  * pure functions over a param pytree instead of nn.Module aliasing;
  * partitioning generalized to any 1 <= num_parts <= 4 at layer
    boundaries (the reference hard-codes exactly 2 — node.py:246-248);
  * the flatten at the conv/fc boundary emits the reference's (C, H, W)
    order (see _seg_conv2), so the 2-way split's wire activation and the
    fc1 weight layout are interchangeable with a reference node's. NOTE:
    this fixes the native fc1 layout too — a native .npz saved by the
    earlier (H, W, C)-flatten revision would load without error but
    mispredict; no such artifact was ever shipped.

Param pytree layout (keys are the stage-sliceable unit, mirroring the
reference's per-layer state-dict keys conv1/conv2/fc1/fc2):

  {"conv1": {kernel, bias}, "conv2": {kernel, bias},
   "fc1": {kernel, bias}, "fc2": {kernel, bias}}
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from dnn_tpu.ops.nn import conv2d, linear, max_pool2d, relu, softmax
from dnn_tpu.registry import ModelSpec, StageSpec, register_model

NUM_CLASSES = 10
IMAGE_SHAPE = (32, 32, 3)  # HWC
FLAT_FEATURES = 8 * 8 * 64  # after two 2x2 pools: 32->16->8 spatial, 64 ch


def _kaiming_conv(key, kh, kw, cin, cout, dtype):
    # Matches torch's default Conv2d init scale (kaiming_uniform a=sqrt(5)).
    fan_in = kh * kw * cin
    bound = 1.0 / math.sqrt(fan_in)
    kkey, bkey = jax.random.split(key)
    kernel = jax.random.uniform(
        kkey, (kh, kw, cin, cout), dtype, minval=-math.sqrt(3.0) * bound, maxval=math.sqrt(3.0) * bound
    )
    bias = jax.random.uniform(bkey, (cout,), dtype, minval=-bound, maxval=bound)
    return {"kernel": kernel, "bias": bias}


def _torch_linear(key, cin, cout, dtype):
    bound = 1.0 / math.sqrt(cin)
    kkey, bkey = jax.random.split(key)
    kernel = jax.random.uniform(
        kkey, (cin, cout), dtype, minval=-math.sqrt(3.0) * bound, maxval=math.sqrt(3.0) * bound
    )
    bias = jax.random.uniform(bkey, (cout,), dtype, minval=-bound, maxval=bound)
    return {"kernel": kernel, "bias": bias}


def init(rng, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "conv1": _kaiming_conv(k1, 3, 3, 3, 32, dtype),
        "conv2": _kaiming_conv(k2, 3, 3, 32, 64, dtype),
        "fc1": _torch_linear(k3, FLAT_FEATURES, 512, dtype),
        "fc2": _torch_linear(k4, 512, NUM_CLASSES, dtype),
    }


# --- layer-granular segments: the partitionable unit ----------------------
# Reference forward order: pool(relu(conv1)) -> pool(relu(conv2)) -> flatten
# -> relu(fc1) -> softmax(fc2)  (cifar_model_parts.py:18-25).


def _seg_conv1(params, x, compute_dtype=None):
    # Input channels padded 3 -> 8 before the conv: XLA's TPU conv emitter
    # handles the degenerate cin=3 contraction poorly (no cell of the
    # chip benchmark runs this model: not measured this round). Zero
    # kernel rows contribute
    # exact zeros to the accumulation, so outputs are bit-identical in
    # every dtype; params keep the reference's (3, 32) kernel shape
    # (cifar_model_parts.py:9) so checkpoints are unaffected.
    kernel = params["conv1"]["kernel"]
    # TPU-only: other backends' conv emitters don't share the degenerate-
    # cin penalty, so they'd pay the extra MACs for nothing. Resolved at
    # trace time (jit traces per backend), so each backend compiles its
    # own consistent branch.
    pad = max(0, 8 - kernel.shape[2]) if jax.default_backend() == "tpu" else 0
    if pad:
        kernel = jnp.pad(kernel, ((0, 0), (0, 0), (0, pad), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
    padded = {"kernel": kernel, "bias": params["conv1"]["bias"]}
    return max_pool2d(relu(conv2d(padded, x, compute_dtype=compute_dtype)))


def _seg_conv2(params, x, compute_dtype=None):
    h = max_pool2d(relu(conv2d(params["conv2"], x, compute_dtype=compute_dtype)))
    # Flatten in the REFERENCE'S (C, H, W) order (`x.view(-1, 64*8*8)` on
    # NCHW, cifar_model_parts.py:41), not our activation-native (H, W, C):
    # this is the 2-way split's wire boundary, so matching the order makes
    # our stage-0 output byte-compatible with a reference part-1 node (and
    # vice versa) and lets fc1 weights carry over with no permutation. The
    # transpose is 4096 elements — noise next to the convs.
    return h.transpose(0, 3, 1, 2).reshape(h.shape[0], -1)


def _seg_fc1(params, x, compute_dtype=None):
    return relu(linear(params["fc1"], x, compute_dtype=compute_dtype))


def _seg_fc2(params, x, compute_dtype=None):
    # bf16 operands still accumulate + softmax in f32: probs stay f32 in
    # both modes (only matmul/conv operand traffic changes).
    h = linear(params["fc2"], x, compute_dtype=compute_dtype,
               accum_dtype=jnp.float32 if compute_dtype is not None else None)
    return softmax(h, axis=1)


_SEGMENTS = (
    ("conv1", _seg_conv1, ("conv1",)),
    ("conv2", _seg_conv2, ("conv2",)),
    ("fc1", _seg_fc1, ("fc1",)),
    ("fc2", _seg_fc2, ("fc2",)),
)

# Split points chosen so num_parts=2 reproduces the reference split exactly:
# part0 = convs + flatten, part1 = fcs + softmax (cifar_model_parts.py:29-58).
_PARTITIONS = {
    1: ((0, 1, 2, 3),),
    2: ((0, 1), (2, 3)),
    3: ((0,), (1,), (2, 3)),
    4: ((0,), (1,), (2,), (3,)),
}


def apply(params, x):
    """Full-model forward: (B, 32, 32, 3) NHWC -> (B, 10) class probs."""
    for _, fn, _ in _SEGMENTS:
        x = fn(params, x)
    return x


def make_apply(compute_dtype=None):
    """Forward with an explicit matmul/conv operand dtype (e.g. bf16 for
    the MXU); probs are always f32 (see _seg_fc2). `None` returns the
    default f32 `apply` used by the parity tests."""
    if compute_dtype is None:
        return apply

    def apply_cd(params, x):
        x = x.astype(compute_dtype)
        for _, fn, _ in _SEGMENTS:
            x = fn(params, x, compute_dtype=compute_dtype)
        return x

    return apply_cd


def partition(num_parts):
    if num_parts not in _PARTITIONS:
        raise ValueError(
            f"cifar_cnn supports num_parts in {sorted(_PARTITIONS)}, got {num_parts}"
        )
    stages = []
    for seg_ids in _PARTITIONS[num_parts]:
        segs = [_SEGMENTS[i] for i in seg_ids]
        param_keys = tuple(k for _, _, keys in segs for k in keys)

        def stage_fn(params, x, _segs=tuple(segs)):
            for _, fn, _ in _segs:
                x = fn(params, x)
            return x

        stages.append(
            StageSpec(
                name="+".join(s[0] for s in segs),
                apply=stage_fn,
                param_keys=param_keys,
            )
        )
    return stages


def example_input(batch_size=1, rng=None):
    """Dummy input mirroring the reference's torch.randn(1, 3, 32, 32)
    fallback (node.py:149-154), in NHWC."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    return jax.random.normal(rng, (batch_size, *IMAGE_SHAPE), jnp.float32)


def _convert_state_dict(sd):
    from dnn_tpu.io.checkpoint import cifar_params_from_torch_state_dict

    return cifar_params_from_torch_state_dict(sd)


register_model(
    ModelSpec(
        name="cifar_cnn",
        init=init,
        apply=apply,
        partition=partition,
        example_input=example_input,
        supported_parts=tuple(sorted(_PARTITIONS)),
        convert_state_dict=_convert_state_dict,
    )
)
