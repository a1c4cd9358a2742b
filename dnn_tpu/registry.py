"""Model zoo and stage registry.

The reference hard-codes a registry `MODEL_PARTS_CLASSES = {0: ModelPart0_2Node,
1: ModelPart1_2Node}` (/root/reference/node.py:29-32) that must be hand-edited
to swap model families (its readme.md:100-108 says exactly that). Here the
registry is a first-class, config-selected model zoo: each `ModelSpec` knows
how to init params, run the full model, and partition itself into
`StageSpec`s for any supported number of pipeline parts.

A StageSpec is the rebuild of the reference's ModelPart* classes
(cifar_model_parts.py:29-58, partitions/gpt_model_parts.py:6-50): a pure
function over the slice of the param pytree named by `param_keys` — the
functional analog of `load_state_dict(strict=False)` keeping only your
layers (node.py:306).
"""

from __future__ import annotations

import dataclasses
from collections.abc import MutableMapping
from typing import Any, Callable, Dict, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage: a pure function plus the param keys it owns."""

    name: str
    apply: Callable[[Any, Any], Any]  # (params_slice, activation) -> activation
    param_keys: Tuple[str, ...]

    def slice_params(self, full_params):
        """Keep only this stage's entries of the full param pytree — the
        functional equivalent of the reference's strict=False per-part
        state-dict load (node.py:294-317)."""
        return {k: full_params[k] for k in self.param_keys}


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    init: Callable[..., Any]  # (rng, **kw) -> params
    apply: Callable[[Any, Any], Any]  # (params, x) -> y; full model forward
    partition: Callable[[int], Sequence[StageSpec]]
    example_input: Callable[..., Any]
    supported_parts: Tuple[int, ...] = (1, 2)
    # Convert a foreign flat state dict (torch/HF names+layouts) into this
    # family's param pytree — the torch->TPU half of the reference's
    # torch.load path (node.py:296).
    convert_state_dict: Optional[Callable[[Dict[str, Any]], Any]] = None
    # Optional extras (model-family specific):
    config: Optional[Any] = None  # e.g. GPTConfig for transformer families
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


    def init_parts(self, rng) -> "ParamParts":
        """`init(rng)` as a tree whose top-level entries are drawn when
        first read: the family's own `extras["init_parts"]` (an entry a
        layer, from that layer's keys), or the whole `init` on the spot,
        handed out entry by entry."""
        make = self.extras.get("init_parts")
        if make is not None:
            return ParamParts(make(rng))
        return ParamParts.of_whole(self.init(rng))


class ParamParts(MutableMapping):
    """A param tree {name: subtree} whose entries are made on first read
    (`makers`: name -> a function that makes it). `parts[name]` makes and
    keeps; `parts.pop(name)` makes and hands over without keeping — what
    lets a process that holds the tree in another dtype draw a layer,
    cast it and free the draw before the next exists
    (`node._stack_and_release`). Otherwise a dict."""

    def __init__(self, makers):
        self._makers = dict(makers)
        self._made = {}

    @classmethod
    def of_whole(cls, tree):
        """The entries of a tree that is made whole, here and now (a family
        with no `init_parts` of its own): each is handed over, and let go
        of, as a made one is."""
        parts = cls({})
        parts.update(tree)
        return parts

    def __getitem__(self, name):
        if name not in self._made:
            self._made[name] = self._makers[name]()
        return self._made[name]

    def __setitem__(self, name, value):
        self._makers.setdefault(name, None)
        self._made[name] = value

    def __delitem__(self, name):
        del self._makers[name]
        self._made.pop(name, None)

    def pop(self, name, *default):
        if name not in self._makers:
            if default:
                return default[0]
            raise KeyError(name)
        made = self._made.pop(name, None)
        make = self._makers.pop(name)
        return made if made is not None else make()

    def __iter__(self):
        return iter(self._makers)

    def __len__(self):
        return len(self._makers)


_REGISTRY: Dict[str, ModelSpec] = {}


def register_model(spec: ModelSpec) -> ModelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_model(name: str) -> ModelSpec:
    # Import built-in families lazily so `import dnn_tpu` stays cheap but
    # get_model("cifar_cnn") always works.
    if name not in _REGISTRY:
        import dnn_tpu.models  # noqa: F401  (registers built-ins)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"Unknown model '{name}'. Available: {sorted(_REGISTRY)}"
        ) from None


def available_models():
    import dnn_tpu.models  # noqa: F401

    return sorted(_REGISTRY)
