"""The chip-side differential of the expert layer's compact form (ISSUE 65):
ONE layer call of `parallel/moe.moe_ffn_grouped` under `held=` — the
permutation at the extent of the rows held, its rounds, the kernel that adds
a round's rows back into their tokens — against the WHOLE form (`whole_form`
below: the program every PR up to 63 served, every pick's row moved in and
out), on the same inputs and stacks, with the REAL kernels on a TPU.

Every other test of these paths interprets the kernels on a CPU, which is
how PR 64's fault reached the driver: the interpreter runs a kernel's body
as plain `jax.numpy`, so what the chip's compiler makes of a dynamic row of
a block (`ops/pallas/row_accumulate.py`) is never seen there.

Under pytest the cases skip off the TPU (tests/conftest.py holds the suite
to the CPU, so the tier-1 count never moves). A builder runs the file as a
script through the chip tool, ~4 minutes of one chip:

    chiprun -- python3 tests/test_chip_moe_parity.py          # every case
    chiprun -- python3 tests/test_chip_moe_parity.py dots3    # cases by name
    python3 tests/test_chip_moe_parity.py --tiny              # CPU rehearsal

It prints a line a case (`ok` / `FAIL`, the worst row's error against its
bound, the rounds the compact form took), writes
`chiprun_out/moe_parity.json` and exits 1 if any case failed. `--tiny`
rehearses the same routings at toy widths with the kernels interpreted.

A case is (widths, routing[, extent]). The widths are the five `held`
cells' chunks (S 1024) and their decode steps (S 16, 32, 64: there
`permutation_extent` keeps ONE pass, whose mask is then what is compared;
`PINNED` holds the rounds and the kernel at those sizes too); a routing
plants the router's logits in the tokens themselves (the router is the
identity on the first E columns), so that the picks are exact:

  near_even        the router's own picks of random tokens
  none_held        no pick is held here: no round, y is zeros
  every_held       every pick is held here: S*k / extent rounds
  at_extent        exactly `extent` live rows: one round, full
  extent_plus_1    one row over: a second round of ONE row
  twice_extent     two full rounds
  padded_424/1000  a prompt's last chunk: the last 424 / 1000 positions are
                   the same token, ALL of whose picks are held here: each of
                   its experts' groups spans windows (two at the cells'
                   extents; three and five where `extent` is pinned at 384
                   and 256)
  padded_424_3     the same, three of the pad token's picks held
  one_token        ONE token picks k held experts and nobody else any: k
                   consecutive live rows update one row of y

Each runs twice: as the program is, and with NaNs planted in every row the
grouped matmul leaves unspecified (behind `live`), which must never surface.
`ACCUMULATE` holds the kernel alone against a loop over rows, y in one to
four column tiles. `FUZZ` draws routings at random — a share of the picks
held here between an eighth and all, a run of equal tokens at the end (a
step's idle slots, a chunk's pad positions) — for tokens in BFLOAT16, as
the daemon's are, and compares to two of the result's own roundings."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dnn_tpu.ops.pallas import row_accumulate as ra  # noqa: E402
from dnn_tpu.parallel import moe  # noqa: E402

ON_CHIP = jax.default_backend() == "tpu"

#: tokens S, width D, expert width F, experts E, held here, scoring — the
#: five `held` cells' chunk and (Keye's, dots3's) decode steps
WIDTHS = {
    "keye": dict(S=1024, D=2048, F=768, E=128, held=16, scoring="softmax"),
    "joyai": dict(S=1024, D=2048, F=768, E=256, held=16, scoring="sigmoid"),
    "solar": dict(S=1024, D=4096, F=1280, E=320, held=40, scoring="sigmoid"),
    "dots3": dict(S=1024, D=5120, F=1536, E=256, held=32, scoring="sigmoid"),
    "kexaone": dict(S=1024, D=6144, F=2048, E=128, held=16,
                    scoring="sigmoid"),
    "keye_s16": dict(S=16, D=2048, F=768, E=128, held=16, scoring="softmax"),
    "dots3_s16": dict(S=16, D=5120, F=1536, E=256, held=32,
                      scoring="sigmoid"),
    "keye_s32": dict(S=32, D=2048, F=768, E=128, held=16, scoring="softmax"),
    "dots3_s32": dict(S=32, D=5120, F=1536, E=256, held=32,
                      scoring="sigmoid"),
    "keye_s64": dict(S=64, D=2048, F=768, E=128, held=16, scoring="softmax"),
    "dots3_s64": dict(S=64, D=5120, F=1536, E=256, held=32,
                      scoring="sigmoid"),
}
TINY_WIDTHS = {
    "keye": dict(S=64, D=256, F=128, E=32, held=4, scoring="softmax"),
    "dots3": dict(S=64, D=512, F=128, E=32, held=4, scoring="sigmoid"),
    "keye_s16": dict(S=16, D=256, F=128, E=32, held=4, scoring="softmax"),
}
TOP_K = 8
ROUTINGS = ("near_even", "none_held", "every_held", "at_extent",
            "extent_plus_1", "twice_extent", "padded_424", "padded_1000",
            "padded_424_3", "one_token")
#: (widths, extent pinned) -> routings: a group of 1000 rows over five
#: windows, of 424 over three; and the rounds at a decode step's sizes, where
#: `permutation_extent` keeps one pass (the rounds' loop would cost a step
#: more than it saves)
PINNED_ROUTINGS = ("padded_424", "padded_1000", "every_held", "near_even")
PINNED = {("keye", 256): PINNED_ROUTINGS, ("dots3", 256): PINNED_ROUTINGS,
          ("dots3", 384): PINNED_ROUTINGS, ("joyai", 384): PINNED_ROUTINGS,
          ("keye_s32", 128): ROUTINGS, ("dots3_s32", 128): ROUTINGS,
          ("keye_s64", 128): ROUTINGS, ("dots3_s64", 128): ROUTINGS}

#: the kernel alone: (tokens S, width D, rows R, live, bytes a column tile
#: of y may hold — None the module's: one tile at 2048, two at 4096, four at
#: 5120, three at 6144)
ACCUMULATE = {
    "keye_one_tile": (1024, 2048, 1536, 1100, None),
    "solar_two_tiles": (1024, 4096, 1536, 1100, None),
    "dots3_four_tiles": (1024, 5120, 1536, 1100, None),
    "kexaone_three_tiles": (1024, 6144, 1536, 1100, None),
    "dots3_every_row": (1024, 5120, 1536, 1536, None),
    "dots3_one_row": (1024, 5120, 1536, 1, None),
    "dots3_no_row": (1024, 5120, 1536, 0, None),
    "keye_four_tiles": (1024, 2048, 1536, 1100, 1024 * 512 * 4),
    "keye_step": (64, 2048, 128, 70, None),
}


#: widths -> routings drawn at random (a decode step is a dots3 daemon's
#: 32 slots, Keye's 64)
FUZZ = {"dots3_s32": 150, "dots3_s32@128": 150, "keye_s64": 100,
        "keye_s64@128": 100, "dots3_s16": 100, "dots3": 40, "keye": 40,
        "joyai": 40, "kexaone": 20, "solar": 20}


def _random_logits(s, e, held, k, rng):
    """(S, E) logits of a routing drawn at random: each pick held here with
    one probability a trial; with even odds the last rows are ONE token."""
    p_held = rng.choice([held / e, 0.25, 0.5, 0.9, 1.0])
    base = 0.1 * rng.standard_normal((s, e)).astype(np.float32)
    outside = np.arange(held, e)

    def picks(n_held):
        return np.concatenate([rng.choice(held, n_held, replace=False),
                               rng.choice(outside, k - n_held, replace=False)]
                              ).astype(int)

    for t in range(s):
        base[t, picks(rng.binomial(k, p_held))] += 6.0
    n_same = int(rng.integers(0, s + 1)) if rng.random() < 0.5 else 0
    if n_same:
        row = 0.1 * rng.standard_normal((e,)).astype(np.float32)
        row[picks(int(rng.integers(0, k + 1)))] += 6.0
        base[s - n_same:] = row
    return base, n_same


def run_fuzz(name, trials, *, tiny=False):
    """`trials` routings drawn at random at one width, tokens in bfloat16:
    the compact form's bfloat16 result within two roundings of the whole
    form's, row by row."""
    label = name
    name, _, pinned = name.partition("@")
    widths = (TINY_WIDTHS if tiny else WIDTHS)[name]
    top_k = 4 if tiny else TOP_K
    s, d, e, held = (widths[n] for n in ("S", "D", "E", "held"))
    kw = dict(top_k=top_k, held=(0, held), scoring=widths["scoring"],
              interpret=tiny)
    whole = _jitted((name, "whole16"), lambda p, v: whole_form(
        p, v, **kw).astype(v.dtype))
    compact = _jitted((label, "fuzz"), lambda p, v: moe.moe_ffn_grouped(
        p, v, normalize=True, activation=jax.nn.silu,
        compute_dtype=jnp.bfloat16, return_stats=True, **kw))
    params = _stacks(name, widths, 65)
    real_extent = moe.permutation_extent
    if pinned:
        moe.permutation_extent = lambda *a: int(pinned)
    try:
        return _fuzz_trials(label, trials, whole, compact, params,
                            (s, d, e, held, top_k))
    finally:
        moe.permutation_extent = real_extent


def _fuzz_trials(label, trials, whole, compact, params, sizes):
    s, d, e, held, top_k = sizes
    failures, rounds_seen = [], {}
    for trial in range(trials):
        rng = np.random.default_rng(1000 + trial)
        x = rng.standard_normal((s, d)).astype(np.float32)
        logits, n_same = _random_logits(s, e, held, top_k, rng)
        if n_same:
            x[s - n_same:] = x[-1]
        x[:, :e] = logits
        x = jnp.asarray(x).astype(jnp.bfloat16)
        want = np.asarray(whole(params, x).astype(jnp.float32))
        got, stats = compact(params, x)
        got = np.asarray(got.astype(jnp.float32))
        err = np.abs(got - want).max(axis=1)
        err = np.where(np.isfinite(err), err, np.inf)
        bound = 2.0 ** -7 * np.maximum(np.abs(want).max(axis=1), 1e-2)
        bad = np.flatnonzero(~(err <= bound))
        extra = int(stats[4])
        rounds_seen[extra] = rounds_seen.get(extra, 0) + 1
        if bad.size:
            failures.append({"trial": trial, "rows_wrong": int(bad.size),
                             "first_wrong": bad[:8].tolist(),
                             "err": float(err[bad[0]]),
                             "bound": float(bound[bad[0]]),
                             "stats": stats.tolist(), "n_same": n_same})
    return {"case": f"fuzz/{label}", "ok": not failures, "trials": trials,
            "extra_rounds_seen": rounds_seen, "failures": failures[:6]}


def _logits(routing, s, e, held, extent, rng, tiny):
    """(S, E) router logits of a routing, experts [0, held) held here; the
    k picks of a token lead the rest by 6."""
    k = TOP_K if not tiny else 4
    base = 0.1 * rng.standard_normal((s, e)).astype(np.float32)
    if routing == "near_even":
        return rng.standard_normal((s, e)).astype(np.float32)
    outside = np.arange(held, e)

    def pick(t, n_held):
        mine = rng.choice(held, n_held, replace=False)
        others = rng.choice(outside, k - n_held, replace=False)
        base[t, np.concatenate([mine, others]).astype(int)] += 6.0

    n_pad = {"padded_424": 424, "padded_1000": 1000, "padded_424_3": 424}
    if routing in n_pad:
        n = n_pad[routing] if s >= 1024 else (
            s * n_pad[routing] // 1024 if s > 16 else s // 2)
        out = rng.standard_normal((s, e)).astype(np.float32)
        row = 0.1 * rng.standard_normal((e,)).astype(np.float32)
        n_held = min(k, 3) if routing.endswith("_3") else k
        row[np.concatenate([rng.choice(held, n_held, replace=False),
                            rng.choice(outside, k - n_held, replace=False)]
                           ).astype(int)] += 6.0
        out[s - n:] = row
        return out
    all_held = {"none_held": 0, "every_held": s,
                "at_extent": min(extent // k, s),
                "extent_plus_1": min(extent // k, s - 1),
                "twice_extent": min(2 * extent // k, s), "one_token": 0}[routing]
    for t in range(s):
        if routing == "one_token":
            pick(t, k if t == min(5, s - 1) else 0)
        elif t < all_held:
            pick(t, k)
        elif routing == "extent_plus_1" and t == all_held:
            pick(t, 1)
        else:
            pick(t, 0)
    return base


_STACKS, _JITS = {}, {}


def _stacks(name, widths, seed):
    """The held experts' matrices as layer 1 of two-layer `LayerOf` stacks
    (layer 0 is NaN) under an identity router; made once a width."""
    if name not in _STACKS:
        d, f, e, held = (widths[n] for n in ("D", "F", "E", "held"))
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        params = {"router": {"kernel": jnp.eye(d, e, dtype=jnp.float32)}}
        for key, (w_name, rows, cols) in zip(
                ks, (("wg", d, f), ("wu", d, f), ("wd", f, d))):
            w = (jax.random.normal(key, (held, rows, cols), jnp.float32)
                 / np.sqrt(rows)).astype(jnp.bfloat16)
            params[w_name] = moe.LayerOf(
                jnp.stack([jnp.full_like(w, jnp.nan), w]), jnp.int32(1))
        _STACKS.clear()  # one width's stacks on the device at a time
        _STACKS[name] = params
    return _STACKS[name]


def _inputs(name, widths, routing, extent, tiny, seed=65):
    """(the width's params, tokens (S, D) float32 whose first E columns are
    the routing's logits)."""
    s, d, e, held = (widths[n] for n in ("S", "D", "E", "held"))
    rng = np.random.default_rng(seed)
    x = np.array(jax.random.normal(jax.random.PRNGKey(seed + 1), (s, d),
                                   jnp.float32))
    logits = _logits(routing, s, e, held, extent, rng, tiny)
    if routing.startswith("padded"):
        n = int((logits == logits[-1]).all(axis=1).sum())
        x[s - n:] = x[-1]  # a pad position IS the same row
    x[:, :e] = logits
    return _stacks(name, widths, seed), jnp.asarray(x)


def _jitted(key, fn):
    """One `jax.jit` a (width, extent, form): a routing changes values, not
    shapes, so its cases share the compiled program."""
    if key not in _JITS:
        _JITS[key] = jax.jit(fn)
    return _JITS[key]


def whole_form(params, x, *, top_k, held, scoring, interpret):
    """The program every PR up to 63 served: every pick's row gathered, the
    experts over all S*k rows (the kernel visits the held ones), the rows
    not held zeroed, every row back at its pick, the sum over k."""
    s, d = x.shape
    weights, order, expert_of_row, group_sizes = moe.route_rows(
        params["router"]["kernel"], x, top_k=top_k, held=held,
        scoring=scoring)
    rows = x[order // top_k]
    out = moe._experts_grouped(
        params, rows, expert_of_row, group_sizes, activation=jax.nn.silu,
        compute_dtype=jnp.bfloat16, interpret=interpret)
    out = jnp.where((expert_of_row < held[1])[:, None], out, 0.0)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    unsorted = out[inverse].reshape(s, top_k, d)
    return (unsorted * weights[..., None]).sum(axis=1)


def _planted(experts):
    """`_experts_grouped` with NaN in every row behind its last group."""
    def run(params, rows, expert_of_row, group_sizes, **kw):
        out = experts(params, rows, expert_of_row, group_sizes, **kw)
        behind = (jnp.arange(out.shape[0]) >= group_sizes.sum())[:, None]
        return jnp.where(behind, jnp.nan, out)
    return run


def run_case(name, routing, extent=None, *, tiny=False):
    """One case -> dict(ok, worst (row, error, bound), stats of the compact
    form plain and planted)."""
    widths = (TINY_WIDTHS if tiny else WIDTHS)[name]
    top_k = 4 if tiny else TOP_K
    held = (0, widths["held"])
    natural = moe.permutation_extent(widths["S"] * top_k, widths["E"],
                                     widths["held"])
    params, x = _inputs(name, widths, routing, extent or natural, tiny)
    kw = dict(top_k=top_k, held=held, scoring=widths["scoring"],
              interpret=tiny)
    want = np.asarray(_jitted((name, "whole"), lambda p, v: whole_form(
        p, v, **kw))(params, x))
    result = {"case": f"{name}/{routing}" + (f"@{extent}" if extent else ""),
              "extent": extent or natural, "ok": True}
    real_extent, real_experts = moe.permutation_extent, moe._experts_grouped
    try:
        if extent:
            moe.permutation_extent = lambda *a: extent
        for tag in ("plain", "planted"):
            if tag == "planted":
                moe._experts_grouped = _planted(real_experts)
            got, stats = _jitted(
                (name, extent, tag), lambda p, v: moe.moe_ffn_grouped(
                    p, v, normalize=True, activation=jax.nn.silu,
                    compute_dtype=jnp.bfloat16, return_stats=True, **kw))(
                        params, x)
            got = np.asarray(got)
            err = np.abs(got - want).max(axis=1)
            err = np.where(np.isfinite(err), err, np.inf)
            bound = 1e-5 * np.maximum(1.0, np.abs(want).max(axis=1))
            bad = np.flatnonzero(~(err <= bound))
            worst = int(np.argmax(err / bound))
            result[tag] = {
                "rows_wrong": int(bad.size), "first_wrong": bad[:8].tolist(),
                "worst_row": worst, "err": float(err[worst]),
                "bound": float(bound[worst]), "stats": stats.tolist()}
            result["ok"] &= bad.size == 0
    finally:
        moe.permutation_extent, moe._experts_grouped = (
            real_extent, real_experts)
    result["scale"] = float(np.abs(want).max())
    return result


def run_accumulate(case, *, tiny=False):
    """`row_accumulate` alone against a loop over rows: NaN rows behind
    `live`, runs of consecutive rows of ONE token, y holding something."""
    s, d, r, live, y_bytes = ACCUMULATE[case]
    if tiny:
        s, d, r, live = 24, 512, 300, min(live, 200)
        y_bytes = y_bytes and 24 * 128 * 4
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    y = jax.random.normal(ks[0], (s, d))
    rows = jax.random.normal(ks[1], (r, d)).at[min(live, r):].set(jnp.nan)
    tokens = np.array(jax.random.randint(ks[2], (r,), 0, s))
    tokens[10:18] = 3  # eight consecutive rows of one token
    tokens[126:131] = 7  # and a run across a row tile's edge
    weights = jax.random.uniform(ks[3], (r,))
    real = ra._Y_BLOCK_BYTES
    try:
        if y_bytes is not None:
            ra._Y_BLOCK_BYTES = y_bytes
        got = np.asarray(jax.jit(lambda *a: ra.row_accumulate(
            *a, interpret=tiny))(y, rows, jnp.asarray(tokens), weights,
                                 jnp.int32(live)))
    finally:
        ra._Y_BLOCK_BYTES = real
    want = np.array(y)
    w, rr = np.asarray(weights), np.asarray(rows)
    for i in range(min(live, r)):
        want[tokens[i]] += w[i] * rr[i]
    err = np.abs(got - want).max(axis=1)
    err = np.where(np.isfinite(err), err, np.inf)
    bad = np.flatnonzero(~(err <= 1e-5 * np.maximum(1.0, np.abs(want).max(1))))
    return {"case": f"row_accumulate/{case}", "ok": bad.size == 0,
            "rows_wrong": int(bad.size), "first_wrong": bad[:8].tolist(),
            "err": float(err.max())}


def cases(tiny=False):
    """Every (kind, args) of the file, in running order."""
    out = [("accumulate", (c,)) for c in ACCUMULATE]
    for name in (TINY_WIDTHS if tiny else WIDTHS):
        out += [("layer", (name, r, None)) for r in ROUTINGS]
    pinned = {("keye", 32): PINNED_ROUTINGS, ("dots3", 16): PINNED_ROUTINGS,
              ("keye_s16", 16): ROUTINGS} if tiny else PINNED
    out += [("layer", (name, r, extent)) for (name, extent), routings
            in pinned.items() for r in routings]
    fuzz = {"keye": 6, "dots3@32": 6, "keye_s16@16": 6} if tiny else FUZZ
    out += [("fuzz", (name, trials)) for name, trials in fuzz.items()]
    return out


_RUNNERS = {"accumulate": run_accumulate, "layer": run_case,
            "fuzz": run_fuzz}


def _id(case):
    kind, args = case
    return "-".join(str(a) for a in (kind,) + args if a is not None)


@pytest.mark.skipif(not ON_CHIP, reason="the real kernels need the TPU: run "
                    "this file as a script through the chip tool")
@pytest.mark.parametrize("case", cases(), ids=_id)
def test_the_compact_form_is_the_whole_form_on_the_chip(case):
    kind, args = case
    result = _RUNNERS[kind](*args)
    assert result["ok"], result


def _brief(res):
    """A result in a line: the worst row's error and the rows wrong of each
    run, the compact form's stats (rows, experts, fullest, moved, extra
    rounds); everything of a failure."""
    if "plain" not in res:
        return json.dumps({k: v for k, v in res.items()
                           if k not in ("case", "ok")})
    runs = " ".join(
        f"{tag}: err {res[tag]['err']:.2e} wrong {res[tag]['rows_wrong']}"
        + ("" if res["ok"] else f" first {res[tag]['first_wrong']}")
        for tag in ("plain", "planted"))
    return f"extent {res['extent']} stats {res['plain']['stats']} {runs}"


def main(argv):
    tiny = "--tiny" in argv
    if tiny:  # toy sizes take the rounds too
        moe._MIN_ROWS_SAVED = 0
    wanted = [a for a in argv if not a.startswith("--")]
    results, failed = [], 0
    for kind, args in cases(tiny):
        label = _id((kind, args))
        if wanted and not any(w in label for w in wanted):
            continue
        try:
            res = _RUNNERS[kind](*args, tiny=tiny)
        except Exception as e:  # noqa: BLE001 - a case that cannot run FAILS
            res = {"case": label, "ok": False, "error": repr(e)[:400]}
        results.append(res)
        failed += not res["ok"]
        print("ok  " if res["ok"] else "FAIL", res["case"], _brief(res),
              flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "moe_parity.json"), "w") as f:
        json.dump({"device": str(jax.devices()[0]), "tiny": tiny,
                   "failed": failed, "results": results}, f, indent=1)
    print(json.dumps({"cases": len(results), "failed": failed,
                      "device": str(jax.devices()[0])}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
