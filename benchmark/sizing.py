"""Whole programs of the cells, lowered from shapes for a described chip.

Nothing here runs on a device: each function returns a `jax.stages.Lowered`
built from `jax.ShapeDtypeStruct`s placed on the sharding it is given, so a
test (or a builder settling a depth) can `.compile().memory_analysis()` for
a v5e that is described and not attached. The engine's own jitted functions
are used, reached without building an engine (which would allocate)."""

from __future__ import annotations

from typing import Any, Dict, Tuple

GIB = float(2 ** 30)
# What one v5e chip offers a program (libtpu reports 15.75 GiB of the 16 GB).
USABLE_GIB = 15.75


def _sds(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def model_of(model_config: Dict[str, Any]):
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    return LlamaModel(LlamaConfig(**model_config))


def param_shapes(model, sharding):
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    return _sds(shapes, sharding)


def _bare_engine(model, engine_config: Dict[str, Any]):
    """An LLMEngine with only what its jitted-function builders read."""
    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine

    eng = LLMEngine.__new__(LLMEngine)
    eng.model = model
    eng.cfg = EngineConfig(**engine_config)
    eng.param_transform = None
    eng._decode_fns = {}
    eng._prefill_fns = {}
    return eng


def cache_shapes(model, engine_config: Dict[str, Any], sharding):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm._internal.engine import EngineConfig

    cfg, ec = model.cfg, EngineConfig(**engine_config)
    shape = (cfg.num_kv_heads, ec.resolved_num_pages() + 1, ec.page_size,
             cfg.head_dim)
    one = jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=sharding)
    return [(one, one) for _ in range(cfg.num_layers)]


def kv_pool_bytes(model_config: Dict[str, Any],
                  engine_config: Dict[str, Any]) -> int:
    import math

    import jax.numpy as jnp

    model = model_of(model_config)
    shapes = cache_shapes(model, engine_config, None)
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for pair in shapes for s in pair)


def lower_decode(model_config, engine_config, sharding):
    """The greedy decode program (`decode_steps` tokens for every slot)."""
    import jax
    import jax.numpy as jnp

    model = model_of(model_config)
    eng = _bare_engine(model, engine_config)
    b, mp = eng.cfg.max_seqs, eng.cfg.max_pages_per_seq
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    args = (param_shapes(model, sharding),
            cache_shapes(model, engine_config, sharding),
            s((b,), jnp.int32), s((b, mp), jnp.int32), s((b,), jnp.int32),
            s((b,), jnp.bool_), s((b,), jnp.float32), s((b,), jnp.float32),
            s((b,), jnp.int32), s((b, 2), jnp.uint32), None,
            s((b,), jnp.int32))
    return eng._decode_fn(False, False).lower(*args)


def lower_prefill(model_config, engine_config, bucket: int, nb: int,
                  sharding):
    """The greedy prefill program for `nb` prompts of one bucket."""
    import jax
    import jax.numpy as jnp

    model = model_of(model_config)
    eng = _bare_engine(model, engine_config)
    b, mp = eng.cfg.max_seqs, eng.cfg.max_pages_per_seq
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    args = (param_shapes(model, sharding),
            cache_shapes(model, engine_config, sharding),
            s((nb, bucket), jnp.int32), s((nb, mp), jnp.int32),
            s((nb,), jnp.int32), s((nb,), jnp.int32), s((nb,), jnp.float32),
            s((nb,), jnp.float32), s((nb,), jnp.int32),
            s((b, 2), jnp.uint32), s((nb,), jnp.int32), None,
            s((nb,), jnp.int32))
    return eng._prefill_fn(bucket, nb, False, False).lower(*args)


def lower_train_step(model_config, batch: int, seq: int,
                     learning_rate: float, sharding):
    """train/step.py's step with adafactor, as the train cell runs it."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.train.step import init_train_state, make_train_step

    model = model_of(model_config)
    opt = optax.adafactor(learning_rate)
    state = jax.eval_shape(
        lambda rng: init_train_state(model, opt,
                                     jnp.zeros((1, 8), jnp.int32), rng=rng),
        jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sharding)
    step = make_train_step(model, opt)
    return step.lower(_sds(state, sharding), ids, ids)


def peak_gib(compiled) -> Tuple[float, Dict[str, float]]:
    """Device bytes a compiled program needs while it runs: arguments,
    outputs that are not donated arguments, and temporaries."""
    m = compiled.memory_analysis()
    parts = {"args": m.argument_size_in_bytes / GIB,
             "out": m.output_size_in_bytes / GIB,
             "alias": m.alias_size_in_bytes / GIB,
             "temp": m.temp_size_in_bytes / GIB}
    return (parts["args"] + parts["out"] - parts["alias"]
            + parts["temp"]), parts
