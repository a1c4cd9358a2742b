"""The one general traffic generator: a traffic file's parameters plus
`--seed` give the requests (or batches) of a run.

A traffic file (`traffic/<name>.json`) has a `kind`:

  * `backlog`    an ordered list of requests replayed by one submitter
                 that keeps `outstanding` of them in flight; the window is
                 anchored to the first token of request `anchor_index`;
  * `open_loop`  requests due on a schedule (`poisson` or `bursty`) at a
                 rate fixed in the file, `warm_s` of schedule before the
                 window;
  * `batch_forward`  (batch, seq) token-id batches through the engine's
                 forward, back to back.

What the seed may and may not change. The SIZES of a run (prompt and
output lengths, arrival gaps) are drawn from the file's `layout_seed`, not
from `--seed`: every seed therefore offers the same work, which is what
lets runs with different seeds be compared. Lengths are stratified in
blocks of `strata`: block b holds one draw from each `strata`-th of the
length distribution, so any stretch of the list carries the same token
mass. `--seed` decides the order inside each block (prompts and outputs
permuted separately, so the pairing changes too), the order of the gaps
inside each block of the schedule, and every token id. A file with
`"order": "layout"` takes the orders from the `layout_seed` as well: every
run then replays ONE trace (lengths, pairing and due times), and `--seed`
draws only the token ids and the weights. That is for a metric the order
itself moves, such as a tail of gaps: which prefill lands beside which
live requests.

Randomness is `arrivals.uniform` (a blake2s hash of seed, name, counter):
the same on any host and Python build, for any whole-number seed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

from chipbench.arrivals import bursty_arrivals, uniform

__all__ = ["Request", "quantile", "block_lengths", "block_order",
           "order_seed", "request_lengths", "prompt_ids", "make_requests", "arrival_times",
           "make_batches"]


@dataclasses.dataclass
class Request:
    index: int
    prompt_len: int
    max_new: int
    due_s: Optional[float] = None  # open loop: offset from schedule start
    prompt: Optional[np.ndarray] = None


def quantile(dist: dict, u: float) -> float:
    """Inverse CDF of a piecewise distribution given by `knots`
    [[u0, x0], [u1, x1], ...] (u ascending from 0 to 1), interpolated
    linearly in x (`scale: "linear"`) or in log x (`scale: "log"`, a
    log-uniform piece between neighbouring knots)."""
    knots = dist["knots"]
    log = dist.get("scale", "linear") == "log"
    if not 0.0 <= u <= 1.0:
        raise ValueError(f"u must be in [0, 1], got {u}")
    for (u0, x0), (u1, x1) in zip(knots, knots[1:]):
        if u <= u1:
            w = (u - u0) / (u1 - u0)
            if log:
                return math.exp(math.log(x0) + w * (math.log(x1) - math.log(x0)))
            return x0 + w * (x1 - x0)
    return float(knots[-1][1])


def block_lengths(dist: dict, name: str, block: int, strata: int,
                  layout_seed: int) -> List[int]:
    """The `strata` lengths of block `block`, stratum order: stratum j is
    the quantile at (j + jitter) / strata, the jitter a pure function of
    (layout_seed, name, block, j)."""
    out = []
    for j in range(strata):
        jit = uniform(layout_seed, f"len:{name}", block * strata + j)
        out.append(int(round(quantile(dist, (j + jit) / strata))))
    return out


def block_order(seed: int, name: str, block: int, n: int,
                group: int) -> List[int]:
    """The order in which a block's `n` strata are used: a pure function
    of (seed, name, block). `group` g divides n: every g consecutive
    places hold one stratum from each g-th of the distribution — stratum j
    belongs to part j // (n // g), each part deals its strata over the
    n // g runs in a seeded order, and each run is shuffled. (g == n deals
    whole blocks: any permutation of range(n).)"""
    def shuffled(items, tag):
        keyed = [(uniform(seed, f"ord:{name}:{tag}", block * n + k), x)
                 for k, x in enumerate(items)]
        return [x for _, x in sorted(keyed)]

    if group < 1 or n % group:
        raise ValueError(f"group {group} does not divide strata {n}")
    per = n // group  # strata in a part == runs in the block
    dealt = [shuffled(range(c * per, (c + 1) * per), f"part{c}")
             for c in range(group)]
    out = []
    for r in range(per):
        out += shuffled([dealt[c][r] for c in range(group)], f"run{r}")
    return out


def order_seed(traffic: dict, seed: int) -> int:
    """The seed that orders a block: `--seed`, or the file's layout seed
    where the file says `"order": "layout"`."""
    order = traffic.get("order", "seed")
    if order not in ("seed", "layout"):
        raise ValueError(f"order is 'seed' or 'layout', not {order!r}")
    return int(traffic["layout_seed"]) if order == "layout" else seed


def request_lengths(traffic: dict, seed: int, n: int):
    """[(prompt_len, max_new)] for the first n requests."""
    strata = int(traffic["strata"])
    group = int(traffic["group"])
    layout = int(traffic["layout_seed"])
    max_total = int(traffic["max_total"])
    seed = order_seed(traffic, seed)
    out = []
    for b in range(-(-n // strata)):
        p = block_lengths(traffic["prompt_len"], "prompt", b, strata, layout)
        o = block_lengths(traffic["output_len"], "output", b, strata, layout)
        po = block_order(seed, "prompt", b, strata, group)
        oo = block_order(seed, "output", b, strata, group)
        for k in range(strata):
            pl = p[po[k]]
            out.append((pl, max(1, min(o[oo[k]], max_total - pl))))
    return out[:n]


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> List[float]:
    """Due offsets (seconds, ascending) covering [0, horizon_s).

    `poisson`: exponential gaps at `rate_hz` drawn from the layout seed,
    permuted by `--seed` (or, with `"order": "layout"`, by the layout
    seed) inside blocks of `strata` gaps — every seed sees the same gaps,
    and every block the same total. `bursty`: the layout
    seed's thinned schedule as it is (its gaps depend on the time of day,
    so they cannot be reordered)."""
    arr = traffic["arrivals"]
    layout = int(traffic["layout_seed"])
    rate = float(arr["rate_hz"])
    if arr["process"] == "bursty":
        return bursty_arrivals(
            rate, horizon_s, seed=layout,
            burst_factor=float(arr.get("burst_factor", 4.0)),
            period_s=float(arr.get("period_s", 20.0)))
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    strata, group = int(traffic["strata"]), int(traffic["group"])
    seed = order_seed(traffic, seed)
    out, t, b = [], 0.0, 0
    while True:
        gaps = [-math.log(1.0 - uniform(layout, "gap", b * strata + j)) / rate
                for j in range(strata)]
        for j in block_order(seed, "gap", b, strata, group):
            t += gaps[j]
            if t >= horizon_s:
                return out
            out.append(t)
        b += 1


def prompt_ids(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index])
    return rng.integers(1, vocab, size=n).astype(np.int32)


def make_requests(traffic: dict, seed: int, vocab: int, *,
                  horizon_s: Optional[float] = None) -> List[Request]:
    """The run's request list. `backlog`: `requests` of them, in order.
    `open_loop`: one per arrival before `horizon_s`."""
    kind = traffic["kind"]
    if kind == "backlog":
        due = [None] * int(traffic["requests"])
    elif kind == "open_loop":
        due = arrival_times(traffic, seed, float(horizon_s))
    else:
        raise ValueError(f"traffic kind {kind!r} has no request list")
    lens = request_lengths(traffic, seed, len(due))
    return [Request(i, pl, mn, due[i], prompt_ids(seed, i, pl, vocab))
            for i, (pl, mn) in enumerate(lens)]


def make_batches(traffic: dict, seed: int, vocab: int) -> List[np.ndarray]:
    """`distinct_batches` (batch, seq) int32 id arrays from the seed."""
    shape = (int(traffic["batch"]), int(traffic["seq"]))
    return [prompt_ids(seed, i, shape[0] * shape[1], vocab).reshape(shape)
            for i in range(int(traffic["distinct_batches"]))]
