"""Static analyzer: trace/shard safety, concurrency hazards, protocol
state machines.

Three static passes over the framework (and over user model code, via
CLI paths), plus one runtime companion:

  * AST lint (analysis/lint.py) — rules TPU001..TPU006 over source text:
    traced-value Python branching, implicit host transfers, PRNG key
    reuse, use-after-donation, loop-scalar recompile hazards, and
    divergent collectives across SPMD branches. No jax import needed.
  * concurrency pass (analysis/concurrency.py) — rules CON001..CON006,
    merged into the same lint walk: blocking primitives reachable from
    async bodies, unguarded Future settles, acquire-without-finally-
    release over registered resource pairs, lock-order cycles,
    cross-context unlocked writes, notify/thread-lifecycle misuse.
  * protocol pass (analysis/protocol.py) — rules PRO001..PRO004: the
    serving stack's state machines (circuit breaker, drain, supervisor,
    relay accept window) declared as transition tables, model-checked
    (reachability, no absorbing non-terminal state) and cross-checked
    against their code transition sites in both directions.
  * program pass (analysis/program.py) — rules PRG001..PRG004 over the
    REAL entrypoints' jaxprs/lowerings: mesh-axis-aware collective-
    sequence consistency across pipeline stage programs, allocation-
    sized baked constants, cache-donation coverage, and a recompile
    census (bucketed decode ladder bound; pipeline/transport pinned at
    one program). Device-free (eval_shape avals), CPU-only.
  * sharding pass (analysis/shardcheck.py) — rules SHD001..SHD009:
    SHD001-006 are AST rules merged into the lint walk (hard-coded
    device-count arithmetic, mesh-axis-name drift, sharded-in/
    replicated-out shard_maps, host materialization reachable from spmd
    bodies, per-host RNG divergence, donation/output sharding
    mismatch); SHD007-009 fire from a compiled audit of the REAL
    sharded programs (zero1 train step, llama dp x tp, stacked
    pipeline, moe EP): allocation-sized collectives, the per-shard
    memory bill, and conformance to sharding contracts declared next
    to the code with `shardcheck.contract`.
  * loop-lag sanitizer (analysis/sanitize.py) — the RUNTIME companion
    for blocking calls no per-module AST pass can see through an
    indirection: an env-gated event-loop self-timer emitting bounded
    flight events a run can read back from /debugz.

Gate: `python -m dnn_tpu.analysis` — exits nonzero on any finding not in
analysis/baseline.json; baselined findings are enumerated (never hidden)
and each carries a one-line justification. `--diff REV` lints only the
package files changed since REV; `--format sarif` emits SARIF 2.1.0 for
CI annotation. See README "Static analysis".
"""

from dnn_tpu.analysis.findings import (  # noqa: F401
    Finding,
    RULES,
    diff_against_baseline,
    load_baseline,
    render_finding,
)
from dnn_tpu.analysis.lint import lint_paths, lint_source  # noqa: F401
from dnn_tpu.analysis.shardcheck import contract  # noqa: F401

__all__ = ["Finding", "RULES", "lint_paths", "lint_source",
           "load_baseline", "diff_against_baseline", "render_finding",
           "contract"]
