"""The whole decode step's share of its roofline, for every serving cell
through ONE entry (`srv_decode_step_roofline_pct`; `layers/<metric>.json`
names it as `"step_roofline:decode_step_roofline_pct"`).

What a step must move and compute depends on the model: which parameters it
streams, what it keeps a cached position or a slot, what its counters say
of the window. That arithmetic stays in the module the family brought
(`reducers.py` for GPT-2, `keye_roofline.py`, `mla_roofline.py`,
`dots3_roofline.py`, ...), and the CONFIGURATION names the module:

    "trace": {"roofline": "falcon_h1_roofline", "known_scopes": [...]}

the name of a module under chipbench/ whose
`decode_step_roofline_pct(facts, *, program)` counts the step's bytes and
operations and returns 100 x least time / mean device time of `program`.
So the next configuration reports the whole step's share by adding files
only: its module, the key in its own file, its cell's name in the entry's
`workloads`. No fallback and no default: a configuration that names no
module reads None, and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import cells

__all__ = ["roofline_module", "decode_step_roofline_pct"]


def roofline_module(facts) -> Optional[str]:
    """The module the cell's configuration names (`trace.roofline` of its
    file); None where it names none."""
    return facts["config"].get("trace", {}).get("roofline")


def decode_step_roofline_pct(facts, **args) -> Optional[float]:
    module = roofline_module(facts)
    if not module:
        return None
    return cells.named(f"{module}:decode_step_roofline_pct",
                       "reducers")(facts, **args)
