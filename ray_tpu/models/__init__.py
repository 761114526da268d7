"""Model families the serving path can build, by `llm_config["family"]`.

A family is a config class, a model class and, where tensor parallelism is
built for it, the rules that shard its parameters over a mesh. Classes are
named here and imported when asked for: importing this package loads no JAX.
`serving_params` is how a serving replica holds any family's parameters.
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
    "sdar_moe": Family("ray_tpu.models.sdar_moe:SdarMoeConfig",
                       "ray_tpu.models.sdar_moe:SdarMoeModel"),
    "jamba": Family("ray_tpu.models.jamba:JambaConfig",
                    "ray_tpu.models.jamba:JambaModel"),
    "granite_hybrid": Family(
        "ray_tpu.models.granite_hybrid:GraniteHybridConfig",
        "ray_tpu.models.granite_hybrid:GraniteHybridModel"),
    "mellum": Family("ray_tpu.models.mellum:MellumConfig",
                     "ray_tpu.models.mellum:MellumModel"),
    "sarvam_mla": Family("ray_tpu.models.sarvam_mla:SarvamMlaConfig",
                         "ray_tpu.models.sarvam_mla:SarvamMlaModel"),
    "minicpm_sala": Family("ray_tpu.models.minicpm_sala:MiniCPMSalaConfig",
                           "ray_tpu.models.minicpm_sala:MiniCPMSalaModel"),
    "nemotron_h": Family("ray_tpu.models.nemotron_h:NemotronHConfig",
                         "ray_tpu.models.nemotron_h:NemotronHModel"),
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


def _only_cast_to(jaxpr, var, dtype) -> bool:
    """Whether `var` is used in `jaxpr`, and by nothing but conversions to
    `dtype`. A call that hands its operands one for one to a single inner
    program (jit, remat, scan) is followed into it; anything less plain
    counts as another use."""
    from jax.extend import core

    used = False
    for eqn in jaxpr.eqns:
        at = [i for i, v in enumerate(eqn.invars) if v is var]
        if not at:
            continue
        used = True
        if eqn.primitive.name == "convert_element_type":
            if eqn.params["new_dtype"] != dtype:
                return False
            continue
        inner = [getattr(p, "jaxpr", p) for p in eqn.params.values()
                 if isinstance(p, (core.Jaxpr, core.ClosedJaxpr))]
        if len(inner) != 1 or len(inner[0].invars) != len(eqn.invars):
            return False
        if not all(_only_cast_to(inner[0], inner[0].invars[i], dtype)
                   for i in at):
            return False
    return used and not any(v is var for v in jaxpr.outvars)


def serving_params(model, params):
    """`params` as a serving replica holds them: a leaf that `model.apply`
    does nothing with but convert it to the narrower `model.cfg.dtype` (in
    models/llama.py every `kernel` and the `embedding`) is rounded to it
    here, once, and not by every program that reads it; every other leaf (a
    norm's float32 `scale`, a router computed in float32, a tree that is
    `cfg.dtype` already) is returned as the object it was. Which leaves
    those are is read off the model's traced forward, not off their names.
    Works on arrays of the host or the device and on tracers (inside the
    program that makes the tree, so the wide tree is never whole in HBM):
    the values the programs multiply by are the same either way."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(model.cfg.dtype)
    leaves, treedef = jax.tree.flatten(params)
    wide = [i for i, x in enumerate(leaves)
            if jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.dtype(x.dtype).itemsize > dtype.itemsize]
    if not wide:
        return params
    shapes = treedef.unflatten(
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves])
    ids = jnp.zeros((1, 8), jnp.int32)
    forward = jax.make_jaxpr(
        lambda p: model.apply({"params": p}, ids))(shapes).jaxpr
    for i in wide:
        if _only_cast_to(forward, forward.invars[i], dtype):
            leaves[i] = leaves[i].astype(dtype)
    return treedef.unflatten(leaves)
