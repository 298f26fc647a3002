"""Built-in model zoo: every BASELINE config's model family, JAX-native.

A family is one module of this package with a ``CONFIGS`` dict (name →
its config dataclass) and ``model_def(name, **overrides)``. ``FAMILIES``
below is the one list of them: the factory table, the server's and the
engine's lookup (``family_of``) and the train loop's (``config_of``) all
read it, so a new family is its file and its name in that tuple. A
decoder whose layers follow a static plan (lfm2, nemotron_h, qwen3_next,
smallthinker, kimi_k2, exaone_moe) is its config, draw, mixers and expert
block and one table for ``models/plan.py``, which holds the walks and the engine's
surfaces they share.
Factories accept config overrides (e.g. ``seq_len``/``remat``) from the
JAXJob runtime section.
"""

from __future__ import annotations

import functools
from typing import Callable

from polyaxon_tpu.models import (bert, exaone_moe, kimi_k2, lfm2, llama,
                                 mnist, moe, nemotron_h, qwen3_next, resnet,
                                 smallthinker, t5, vit)
from polyaxon_tpu.models.common import ModelDef

# Decoders first: `serving/server.py` lists the servable names in this
# order.
FAMILIES = (llama, moe, lfm2, nemotron_h, qwen3_next, smallthinker, kimi_k2,
            exaone_moe, t5, vit, bert, resnet, mnist)

_FACTORIES: dict[str, Callable[..., ModelDef]] = {}

for _mod in FAMILIES:
    for _name in _mod.CONFIGS:
        _FACTORIES[_name] = functools.partial(_mod.model_def, _name)


def get_model(name: str, **overrides) -> ModelDef:
    if name not in _FACTORIES:
        raise ValueError(f"Unknown model `{name}`. Available: {sorted(_FACTORIES)}")
    return _FACTORIES[name](**overrides)


def available_models() -> list[str]:
    return sorted(_FACTORIES)


def family_of(name: str):
    """The family module whose ``CONFIGS`` holds ``name`` now. It is
    read on every call: a configuration may be written into a family's
    ``CONFIGS`` (and ``_FACTORIES``) after import, which is how the
    benchmark registers a published model's file under its own name."""
    for mod in FAMILIES:
        if name in mod.CONFIGS:
            return mod
    raise ValueError(f"Unknown model `{name}`. Available: {sorted(_FACTORIES)}")


def config_of(name: str):
    """``name``'s config dataclass instance, as its family holds it now."""
    return family_of(name).CONFIGS[name]
