"""dnn_tpu.chaos: fault injection + the recovery machinery it forces.

The obs arc (PRs 3-5) made every failure mode *visible* — watchdog
wedges, SLO burn, flight-recorder timelines — but nothing *reacted*: a
dead stage failed every in-flight request permanently and a wedged
device 503'd until a human restarted the process (ROADMAP item 5).
This package is the other half:

  * `plan.FaultPlan` — a deterministic, seeded schedule of faults
    (stage kill/hang, injected device wedge, RPC/relay drop-delay-
    corrupt, KV-pool exhaustion, checkpoint corruption), loadable from
    JSON / a file / the `--chaos` CLI flag. In-process faults trigger
    on CALL COUNTERS through a seeded hash — never wall-clock
    randomness in traced or hot-path code — so the same plan + seed
    reproduces the same injection sequence bit-for-bit.
  * `inject.Injector` — the process-local seam driver. The comm
    client/service, the relay assembler, the LM batcher worker and the
    watchdog's probe path each consult it with a single is-None check
    when chaos is off. Every injection lands in the flight recorder as
    a `chaos_inject` event, so each induced incident is reconstructable
    from `/debugz`.
  * `supervisor.Supervisor` — restarts a dead or wedged serving child
    with exponential backoff and crash-loop detection, optionally
    restoring from the latest GOOD checkpoint
    (`restore_latest_good`) and re-warming before declaring recovery
    (`supervisor_restart` flight events pair with the injections).
"""

from dnn_tpu.chaos.inject import (  # noqa: F401
    Injector,
    active,
    corrupt_file,
    install,
    uninstall,
)
from dnn_tpu.chaos.plan import Fault, FaultPlan  # noqa: F401

__all__ = ["Fault", "FaultPlan", "Injector", "install", "uninstall",
           "active", "corrupt_file"]
