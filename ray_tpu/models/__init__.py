"""Model families the serving path can build, by `llm_config["family"]`.

A family is a config class, a model class and, where tensor parallelism is
built for it, the rules that shard its parameters over a mesh. Classes are
named here and imported when asked for: importing this package loads no JAX.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class Family:
    config: str
    model: str
    sharding: Optional[str] = None   # None: one device only

    def load(self, attr: str) -> Any:
        module, _, name = getattr(self, attr).partition(":")
        return getattr(importlib.import_module(module), name)


FAMILIES = {
    "llama": Family("ray_tpu.models.llama:LlamaConfig",
                    "ray_tpu.models.llama:LlamaModel",
                    "ray_tpu.models.llama:LLAMA_SHARDING"),
    "olmo_hybrid": Family("ray_tpu.models.olmo_hybrid:OlmoHybridConfig",
                          "ray_tpu.models.olmo_hybrid:OlmoHybridModel"),
}


def family(name: str) -> Family:
    if name not in FAMILIES:
        raise ValueError(
            f"unknown model family {name!r} (has {sorted(FAMILIES)})")
    return FAMILIES[name]


def sharding_rules(model) -> Any:
    """The parameter sharding rules of `model`'s family, or None where the
    family runs on one device only."""
    for fam in FAMILIES.values():
        if fam.sharding and type(model) is fam.load("model"):
            return fam.load("sharding")
    return None
