"""trainlens tests (ISSUE 19): the training-step observatory.

The acceptance contract this module pins: TrainClock's phase
arithmetic and stall attribution are exact on an injected clock, the
published MFU/tokens-per-sec agree with hand arithmetic a reviewer can
redo, the batched registry flush bills the train.* counters/histograms
and the weak dnn_tpu_train_* gauges, checkpoint freshness
(staleness/last-good-step) follows save/restore through both the clock
and the module-level note_* wires, the GradSentinel's three detectors
(loss_nan latch + incident bundle, grad_spike EMA, train_stall run)
fire exactly once per episode, the obs gate makes every producer a
no-op when off, /trainz serves JSON and Prometheus text, the
`python -m dnn_tpu.obs trainlens` CLI smoke passes — and one real
`train.fit` run on a tiny GPT (grad_stats leg live, periodic
checkpointing, chaos sleep/nan vectors) feeds every seam end to end."""

import json
import math
import os
import subprocess
import sys
import urllib.request

import pytest

from dnn_tpu import obs
from dnn_tpu.obs import flight
from dnn_tpu.obs import trainlens as tl
from dnn_tpu.obs.trainlens import (
    TRAIN_PHASES,
    GradSentinel,
    TrainClock,
    note_ckpt_restored,
    note_ckpt_saved,
)
from dnn_tpu.utils.metrics import Metrics


@pytest.fixture(autouse=True)
def _obs_on():
    """Producers self-gate; unit legs run with the gate ON and restore."""
    was = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)


def _steps(clk, t, n, *, data=0.020, dispatch=0.004, wait=0.016,
           tail=0.010):
    """Drive n deterministic iterations through the producer protocol
    on the injected clock `t` (seconds per phase as given; the tail
    lands in "obs")."""
    for _ in range(n):
        rec = clk.begin()
        assert rec is not None
        for phase, dt in (("data", data), ("dispatch", dispatch),
                          ("wait", wait)):
            t[0] += dt
            clk.mark(rec, phase)
        t[0] += tail
        clk.end(rec)


# ----------------------------------------------------------------------
# phase arithmetic + derived series (injected clock goldens)
# ----------------------------------------------------------------------

def test_phase_arithmetic_golden():
    t = [50.0]
    clk = TrainClock(capacity=16, registry=Metrics(), now=lambda: t[0])
    _steps(clk, t, 4)
    s = clk.summary()
    # per step: wall 50 ms = data 20 + dispatch 4 + wait 16 + obs 10
    assert s["steps_total"] == 4 and s["window_steps"] == 4
    assert s["window_wall_s"] == pytest.approx(4 * 0.050)
    assert s["phases"]["data"]["s"] == pytest.approx(4 * 0.020)
    assert s["phases"]["dispatch"]["mean_ms"] == pytest.approx(4.0)
    assert s["phases"]["wait"]["frac"] == pytest.approx(0.32)
    # the unmarked tail folds into "obs", never into dark time
    assert s["phases"]["obs"]["s"] == pytest.approx(4 * 0.010)
    assert s["phases"]["ckpt"]["s"] == 0.0
    assert s["data_stall_fraction"] == pytest.approx(0.4)
    assert sum(d["s"] for d in s["phases"].values()) == pytest.approx(
        s["window_wall_s"])
    recs = clk.records()
    assert [r["wall"] for r in recs] == pytest.approx([0.050] * 4)
    assert set(recs[0]["phases"]) == {"data", "dispatch", "wait", "obs"}


def test_rate_mfu_and_tokens_agree_with_hand_arithmetic():
    t = [200.0]
    clk = TrainClock(capacity=32, registry=Metrics(),
                     flops_per_step=2e6, tokens_per_step=128,
                     peak_flops=1e9, now=lambda: t[0])
    _steps(clk, t, 5)
    # ring spans first-begin -> now = 5 x 50 ms
    sps = 5 / 0.250
    s = clk.summary()
    assert s["steps_per_sec"] == pytest.approx(sps, rel=1e-3)
    assert s["tokens_per_sec"] == pytest.approx(128 * sps, rel=1e-3)
    assert s["tokens"] == 5 * 128
    assert s["mfu"] == pytest.approx(2e6 * sps / 1e9, abs=1e-6)
    assert clk.mfu() == pytest.approx(0.04, abs=1e-6)
    # explicit per-iteration tokens override the per-step default
    rec = clk.begin()
    t[0] += 0.05
    clk.end(rec, tokens=7)
    assert clk.records()[-1]["tokens"] == 7


def test_mfu_is_none_not_zero_when_unpriced():
    t = [0.0]
    clk = TrainClock(capacity=4, registry=Metrics(), peak_flops=1e12,
                     now=lambda: t[0])
    _steps(clk, t, 2)
    assert clk.mfu() is None            # no flops_per_step
    assert clk.summary()["mfu"] is None
    assert clk._mfu_read() == 0.0       # the gauge reads 0, not None


def test_data_stall_memoized_per_landed_step():
    t = [0.0]
    clk = TrainClock(capacity=16, registry=Metrics(), now=lambda: t[0])
    _steps(clk, t, 2)
    a = clk.data_stall_fraction()
    assert clk.data_stall_fraction() is a or \
        clk.data_stall_fraction() == a  # cached, same key
    _steps(clk, t, 2, data=0.040)       # heavier data phase shifts it
    assert clk.data_stall_fraction() > a


def test_registry_flush_bills_counters_hists_and_gauges():
    t = [0.0]
    reg = Metrics()
    clk = TrainClock(capacity=16, registry=reg, flops_per_step=1e6,
                     tokens_per_step=32, peak_flops=1e9,
                     now=lambda: t[0])
    _steps(clk, t, 3)
    clk.flush()
    snap = reg.snapshot()
    assert snap["counters"]["train.steps_total"] == 3
    assert snap["counters"]["train.tokens_total"] == 96
    assert 'train.phase_seconds{phase="data"}' in snap["histogram"]
    assert snap["histogram"]["train.wall_seconds"]["count"] == 3
    # the weak gauges landed as FULL prom family names (the fleet
    # rollup reads them off /metrics text verbatim)
    for fam in ("dnn_tpu_train_mfu", "dnn_tpu_train_data_stall",
                "dnn_tpu_train_tokens_per_sec",
                "dnn_tpu_ckpt_staleness_seconds"):
        assert fam in snap["gauges"], fam


def test_render_prom_and_chrome_trace():
    t = [10.0]
    clk = TrainClock(capacity=8, registry=Metrics(), flops_per_step=1e6,
                     peak_flops=1e9, now=lambda: t[0])
    _steps(clk, t, 3)
    prom = clk.render_prom()
    assert "dnn_tpu_train_steps_total 3" in prom
    assert 'dnn_tpu_train_phase_frac{phase="data"}' in prom
    ct = clk.chrome_trace()
    xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 3 * 3  # one slice per marked phase per step
    assert xs[0]["ts"] == 0.0  # rebased to the oldest record


def test_ring_capacity_bounds_the_window():
    t = [0.0]
    clk = TrainClock(capacity=4, registry=Metrics(), now=lambda: t[0])
    _steps(clk, t, 10)
    s = clk.summary()
    assert s["steps_total"] == 10 and s["window_steps"] == 4


def test_gate_off_records_nothing():
    obs.set_enabled(False)
    t = [0.0]
    clk = TrainClock(capacity=4, registry=Metrics(), now=lambda: t[0])
    assert clk.begin() is None
    assert clk.steps_total == 0 and clk.records() == []
    sen = GradSentinel()
    assert sen.observe(1, float("nan")) == []
    assert sen.events_fired == 0


# ----------------------------------------------------------------------
# checkpoint observability
# ----------------------------------------------------------------------

def test_ckpt_freshness_arithmetic():
    t = [1000.0]
    reg = Metrics()
    clk = TrainClock(capacity=4, registry=reg, now=lambda: t[0])
    # no save yet: "nothing to lose", not an alarm
    assert clk.ckpt_staleness_s() == 0.0
    clk.ckpt_saved(10, 0.5, 2e6)
    t[0] += 3.0
    assert clk.ckpt_staleness_s() == pytest.approx(3.0)
    assert clk.summary()["ckpt"]["last_good_step"] == 10
    # a restore is also a known-good point: staleness resets
    clk.ckpt_restored(7, 0.2, 2e6)
    assert clk.ckpt_staleness_s() == pytest.approx(0.0)
    assert clk.summary()["ckpt"]["last_good_step"] == 7
    snap = reg.snapshot()
    assert snap["counters"]["train.ckpt_saves"] == 1
    assert snap["counters"]["train.ckpt_restores"] == 1
    assert snap["histogram"]["train.ckpt_save_seconds"]["count"] == 1
    assert snap["histogram"]["train.ckpt_restore_bytes"]["count"] == 1


def test_note_ckpt_wires_flight_and_active_clock():
    t = [0.0]
    clk = TrainClock(capacity=4, registry=Metrics(),
                     now=lambda: t[0]).install()
    assert tl.active_trainlens() is clk
    before = len(flight.recorder().events(kind="ckpt_saved"))
    note_ckpt_saved(5, 0.125, 4096)
    evs = flight.recorder().events(kind="ckpt_saved")
    assert len(evs) == before + 1
    assert evs[-1]["step"] == 5 and evs[-1]["bytes"] == 4096
    assert clk.summary()["ckpt"]["last_good_step"] == 5
    note_ckpt_restored(5, 0.06, 4096)
    assert flight.recorder().events(kind="ckpt_restored")
    # gate off: the helpers are one boolean check, no event, no clock
    obs.set_enabled(False)
    note_ckpt_saved(9, 0.1, 1)
    obs.set_enabled(True)
    assert clk.summary()["ckpt"]["last_good_step"] == 5


# ----------------------------------------------------------------------
# gradient-health sentinels
# ----------------------------------------------------------------------

def test_sentinel_constructor_validation():
    with pytest.raises(ValueError):
        GradSentinel(spike_factor=1.0)
    with pytest.raises(ValueError):
        GradSentinel(ema_alpha=0.0)


def test_sentinel_nan_latches_once_per_episode():
    sen = GradSentinel(warmup=1)
    assert sen.observe(1, 1.0, [1.0, 0.01, 0]) == []
    assert sen.observe(2, float("nan")) == ["loss_nan"]
    assert sen.observe(3, float("nan")) == []        # latched
    assert sen.observe(4, 0.9) == []                 # recovers
    assert sen.observe(5, float("inf")) == ["loss_nan"]  # new episode
    # nonfinite GRADS alone (finite loss) also count as divergence
    sen2 = GradSentinel(warmup=1)
    assert sen2.observe(1, 0.5, [1.0, 0.01, 2]) == ["loss_nan"]
    assert sen.events_fired == 2 and sen2.events_fired == 1


def test_sentinel_spike_ema_and_warmup():
    sen = GradSentinel(warmup=3, spike_factor=4.0, ema_alpha=0.5)
    # a huge norm INSIDE warmup must not fire (it seeds the EMA)
    assert sen.observe(1, 1.0, [1.0, 0.01, 0]) == []
    assert sen.observe(2, 1.0, [100.0, 0.01, 0]) == []
    for i in range(3, 6):
        assert sen.observe(i, 1.0, [1.0, 0.01, 0]) == []
    ema = sen._ema
    assert sen.observe(6, 1.0, [ema * 5, 0.01, 0]) == ["grad_spike"]
    assert sen.observe(7, 1.0, [ema * 9, 0.01, 0]) == []  # latched
    assert sen.observe(8, 1.0, [1.0, 0.01, 0]) == []      # unlatch
    # a NaN norm must not poison the EMA baseline
    base = sen._ema
    sen.observe(9, 1.0, [float("nan"), 0.01, 0])
    assert sen._ema == base


def test_sentinel_stall_needs_consecutive_run():
    sen = GradSentinel(warmup=1, stall_ratio=1e-6, stall_steps=3)
    assert sen.observe(1, 1.0, [1.0, 0.0, 0]) == []
    assert sen.observe(2, 1.0, [1.0, 0.0, 0]) == []
    # movement resets the run
    assert sen.observe(3, 1.0, [1.0, 0.5, 0]) == []
    assert sen.observe(4, 1.0, [1.0, 0.0, 0]) == []
    assert sen.observe(5, 1.0, [1.0, 0.0, 0]) == []
    assert sen.observe(6, 1.0, [1.0, 0.0, 0]) == ["train_stall"]
    assert sen.observe(7, 1.0, [1.0, 0.0, 0]) == []  # latched


def test_sentinel_nan_writes_incident_bundle(tmp_path):
    bundle = tmp_path / "incident"
    clk = TrainClock(capacity=4, registry=Metrics(),
                     now=lambda: 0.0).install()
    sen = GradSentinel(warmup=1, bundle_dir=str(bundle), clock=clk)
    assert sen.observe(3, float("nan"), [1.0, 0.01, 1]) == ["loss_nan"]
    assert bundle.is_dir() and any(bundle.iterdir())
    evs = flight.recorder().events(kind="loss_nan")
    assert evs and evs[-1]["step"] == 3
    assert evs[-1]["nonfinite_grads"] == 1
    assert math.isnan(evs[-1]["loss"])


# ----------------------------------------------------------------------
# /trainz endpoint + CLI
# ----------------------------------------------------------------------

def test_trainz_endpoint_json_and_prom():
    t = [0.0]
    clk = TrainClock(capacity=8, registry=Metrics(), flops_per_step=1e6,
                     tokens_per_step=64, peak_flops=1e9,
                     now=lambda: t[0])
    _steps(clk, t, 4)
    srv = obs.serve_metrics(0, trainlens=clk)
    try:
        base = f"http://127.0.0.1:{srv.port}/trainz"
        z = json.loads(urllib.request.urlopen(
            base, timeout=10).read().decode())
        assert z["steps_total"] == 4
        assert set(z["phases"]) == set(TRAIN_PHASES)
        assert z["data_stall_fraction"] == pytest.approx(0.4)
        prom = urllib.request.urlopen(
            base + "?format=prom", timeout=10).read().decode()
        assert "dnn_tpu_train_mfu" in prom
        assert "dnn_tpu_ckpt_staleness_seconds" in prom
    finally:
        srv.close()


def test_cli_selftest_and_saved_dump(tmp_path):
    r = subprocess.run([sys.executable, "-m", "dnn_tpu.obs", "trainlens",
                        "--selftest"], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "trainlens selftest ok" in r.stdout
    # the offline render path: a saved `curl .../trainz` dump
    t = [0.0]
    clk = TrainClock(capacity=8, registry=Metrics(), now=lambda: t[0])
    _steps(clk, t, 2)
    path = tmp_path / "trainz.json"
    path.write_text(json.dumps(clk.summary()))
    r = subprocess.run([sys.executable, "-m", "dnn_tpu.obs", "trainlens",
                        str(path)], capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "data stall" in r.stdout


# ----------------------------------------------------------------------
# real fit() e2e: every seam fed by the actual training loop
# ----------------------------------------------------------------------

def _toy_linear():
    """A FLOAT toy model (the chaos nan vector poisons float leaves
    only — token batches are int on purpose) with the grad_stats leg."""
    import jax
    import jax.numpy as jnp
    import optax

    from dnn_tpu.train import make_train_step

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8,)),
              "b": jnp.zeros(())}
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    y = x @ jax.random.normal(jax.random.PRNGKey(2), (8,))

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    opt = optax.sgd(1e-2)
    raw = make_train_step(loss_fn, opt, grad_stats=True)

    def step_fn(state, batch):
        p, s = state
        p, s, loss, stats = raw(p, s, batch)
        return (p, s), loss, stats

    def batches():
        while True:
            yield {"x": x, "y": y}

    return step_fn, (params, opt.init(params)), batches


def test_fit_e2e_feeds_clock_ckpt_and_flight(tmp_path):
    from dnn_tpu.train import fit, resume_or_init

    step_fn, state, batches = _toy_linear()
    clk = TrainClock(capacity=32, flops_per_step=1e3, tokens_per_step=16,
                     peak_flops=1e12, registry=Metrics()).install()
    sen = GradSentinel(warmup=2)
    first_before = len(flight.recorder().events(kind="train_step"))
    out_state, loss = fit(step_fn, state, batches(), num_steps=6,
                          ckpt_dir=str(tmp_path), ckpt_every=3,
                          clock=clk, sentinel=sen)
    assert loss is not None and math.isfinite(float(loss))
    s = clk.summary()
    assert s["steps_total"] == 6 and s["window_steps"] == 6
    # every phase boundary was marked — including the ckpt/eval slots
    assert set(clk.records()[0]["phases"]) >= {"data", "dispatch",
                                               "wait", "ckpt", "eval"}
    # two periodic saves landed in the freshness gauges + flight ring
    assert s["ckpt"]["last_good_step"] == 6
    saves = [e for e in flight.recorder().events(kind="ckpt_saved")
             if e["step"] in (3, 6)]
    assert len(saves) == 2 and all(e["bytes"] > 0 for e in saves)
    steps_ev = flight.recorder().events(kind="train_step")
    assert len(steps_ev) > first_before  # first-step + checkpointed
    assert sen.events_fired == 0  # a healthy run fires nothing
    # the resume path: restore-latest-good notes ckpt_restored
    restored, start = resume_or_init(str(tmp_path), state)
    assert start == 6
    assert flight.recorder().events(kind="ckpt_restored")[-1]["step"] == 6


def test_fit_chaos_sleep_lands_in_data_stall():
    from dnn_tpu.chaos import inject as chaos
    from dnn_tpu.train import fit

    step_fn, state, batches = _toy_linear()
    clk = TrainClock(capacity=16, registry=Metrics()).install()
    chaos.install({"seed": 0, "faults": [
        {"kind": "train_fault", "target": "sleep", "at_n": 0,
         "count": 2, "delay_s": 0.05}]})
    try:
        fit(step_fn, state, batches(), num_steps=4, clock=clk)
    finally:
        chaos.uninstall()
    s = clk.summary()
    # the injected 2 x 50 ms sleeps are inside the data window
    assert s["phases"]["data"]["s"] >= 0.09
    assert s["data_stall_fraction"] >= 0.09 / s["window_wall_s"] * 0.9


def test_fit_chaos_nan_fires_sentinel_within_budget(tmp_path):
    from dnn_tpu.chaos import inject as chaos
    from dnn_tpu.train import fit

    step_fn, state, batches = _toy_linear()
    sen = GradSentinel(warmup=1, bundle_dir=str(tmp_path / "inc"))
    before = len(flight.recorder().events(kind="loss_nan"))
    # chaos counter n is 0-indexed: at_n=2 poisons fit step 3
    chaos.install({"seed": 0, "faults": [
        {"kind": "train_fault", "target": "nan", "at_n": 2,
         "count": 1}]})
    try:
        fit(step_fn, state, batches(), num_steps=5, sentinel=sen,
            clock=None)
    finally:
        chaos.uninstall()
    evs = flight.recorder().events(kind="loss_nan")[before:]
    assert evs, "sentinel never fired on the poisoned batch"
    assert evs[-1]["step"] - 3 <= 2  # the sentinel fires within two steps
    assert (tmp_path / "inc").is_dir()
