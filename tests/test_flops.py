"""FLOPs accounting / MFU tests (the bench harness's analytic side)."""

import jax
import pytest

from dnn_tpu.models import gpt
from dnn_tpu.utils import flops


def test_gpt_forward_flops_scales():
    cfg = gpt.PRESETS["gpt2"]
    base = flops.gpt_forward_flops(cfg, 1, 512)
    assert flops.gpt_forward_flops(cfg, 4, 512) == 4 * base
    # doubling seq more than doubles (attention T^2 term)
    assert flops.gpt_forward_flops(cfg, 1, 1024) > 2 * base
    # gpt2-small at T=512: ~0.25 GFLOP/token is the well-known ballpark
    per_token = base / 512
    assert 2e8 < per_token < 4e8, per_token


def test_gpt_train_flops_is_3x_forward():
    cfg = gpt.PRESETS["gpt2-test"]
    assert flops.gpt_train_step_flops(cfg, 2, 32) == \
        3 * flops.gpt_forward_flops(cfg, 2, 32)


def test_train_step_factor_goldens():
    # hand-computed factors: 3x forward plain, 4x under remat (the
    # backward replays the forward); microbatch accumulation leaves
    # the TOTAL unchanged (forward FLOPs are linear in batch)
    cfg = gpt.PRESETS["gpt2-test"]
    fwd = flops.gpt_forward_flops(cfg, 8, 32)
    assert flops.gpt_train_step_flops(cfg, 8, 32, remat=True) == 4 * fwd
    assert flops.gpt_train_step_flops(cfg, 8, 32, accum_steps=4) == \
        3 * fwd
    # the divisibility check mirrors make_train_step's own rejection
    with pytest.raises(ValueError):
        flops.gpt_train_step_flops(cfg, 8, 32, accum_steps=3)
    with pytest.raises(ValueError):
        flops.gpt_train_step_flops(cfg, 8, 32, accum_steps=0)

    from dnn_tpu.models import llama

    lcfg = llama.PRESETS["tinyllama-1.1b"]
    lfwd = flops.llama_forward_flops(lcfg, 2, 64)
    assert flops.llama_train_step_flops(lcfg, 2, 64) == 3 * lfwd
    assert flops.llama_train_step_flops(lcfg, 2, 64, remat=True) == \
        4 * lfwd


def test_goodput_train_step_flops_delegates_per_family():
    # one analytic walk: the serving-side helper must sniff the config
    # family and agree exactly with the utils/flops owners
    from dnn_tpu.models import llama
    from dnn_tpu.obs.goodput import train_step_flops

    gcfg = gpt.PRESETS["gpt2-test"]
    assert train_step_flops(gcfg, 4, 32) == \
        flops.gpt_train_step_flops(gcfg, 4, 32)
    lcfg = llama.PRESETS["tinyllama-1.1b"]
    assert train_step_flops(lcfg, 2, 64, remat=True) == \
        flops.llama_train_step_flops(lcfg, 2, 64, remat=True)


def test_device_peak_and_mfu_off_tpu():
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        pytest.skip("suite runs on the CPU mesh")
    assert flops.device_peak_flops(dev) is None
    assert flops.mfu(1e9, 1000.0, dev) is None


def test_peak_table_matching():
    class FakeDev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    assert flops.device_peak_flops(FakeDev("TPU v5 lite")) == 197e12
    assert flops.device_peak_flops(FakeDev("TPU v4")) == 275e12
    assert flops.device_peak_flops(FakeDev("TPU v5p")) == 459e12
    # a TPU the tables do not know is an error, never a silent None
    for lookup in (flops.device_peak_flops, flops.device_peak_hbm_bw):
        with pytest.raises(ValueError, match="weird-future"):
            lookup(FakeDev("TPU weird-future"))
    assert flops.device_peak_hbm_bw(FakeDev("TPU v5 lite")) == 819e9
    # mfu math: 100 items/s at 1e12 FLOPs/item on a 197e12 chip
    assert flops.mfu(1e12, 100.0, FakeDev("TPU v5e")) == pytest.approx(
        100e12 / 197e12
    )


def test_hbm_peak_and_mbu_off_tpu():
    from dnn_tpu.utils.flops import device_peak_hbm_bw, mbu

    # CPU host: no peak tables -> None, callers omit the fields
    assert device_peak_hbm_bw() is None
    assert mbu(1e6, 1e6) is None


def test_llama_flops_accounting():
    from dnn_tpu.models import llama
    from dnn_tpu.utils.flops import llama_forward_flops

    cfg = llama.PRESETS["tinyllama-1.1b"]
    # per-token cost ~ 2 * N_params + attention: TinyLlama has ~1.1B
    # params, so the linear part sits near 2.2 GFLOPs/token
    per_tok = llama_forward_flops(cfg, 1, 512) / 512
    assert 2.0e9 < per_tok < 3.5e9, per_tok
    # GQA narrows only the k/v projections: an MHA twin costs more
    import dataclasses

    mha = dataclasses.replace(cfg, n_kv_head=cfg.n_head)
    assert llama_forward_flops(mha, 1, 512) > llama_forward_flops(cfg, 1, 512)
