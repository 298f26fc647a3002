"""Built-in model zoo: every BASELINE config's model family, JAX-native.

Registry maps runtime spec names → ``ModelDef`` factories. Factories
accept config overrides (e.g. ``seq_len``/``remat``) from the JAXJob
runtime section.
"""

from __future__ import annotations

from typing import Callable

from polyaxon_tpu.models import (bert, lfm2, llama, mnist, moe, resnet, t5,
                                 vit)
from polyaxon_tpu.models.common import ModelDef

_FACTORIES: dict[str, Callable[..., ModelDef]] = {}

for _name in llama.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: llama.model_def(n, **kw))(_name)
for _name in moe.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: moe.model_def(n, **kw))(_name)
for _name in lfm2.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: lfm2.model_def(n, **kw))(_name)
for _name in vit.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: vit.model_def(n, **kw))(_name)
for _name in bert.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: bert.model_def(n, **kw))(_name)
for _name in resnet.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: resnet.model_def(n, **kw))(_name)
for _name in mnist.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: mnist.model_def(n, **kw))(_name)
for _name in t5.CONFIGS:
    _FACTORIES[_name] = (lambda n: lambda **kw: t5.model_def(n, **kw))(_name)


def get_model(name: str, **overrides) -> ModelDef:
    if name not in _FACTORIES:
        raise ValueError(f"Unknown model `{name}`. Available: {sorted(_FACTORIES)}")
    return _FACTORIES[name](**overrides)


def available_models() -> list[str]:
    return sorted(_FACTORIES)
