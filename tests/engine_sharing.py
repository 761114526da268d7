"""What the model families' engine tests (`test_sdar_moe.py`,
`test_olmo_hybrid.py`, `test_jamba.py`) compile once a module and not once a
test: an engine's decode programs and the plain reference's forward. And the
four families' tiny models, for the tests of the engine's scheduler, which
run the same schedule through each (`test_engine_window.py`,
`test_engine_rechain.py`). And the engine's two programs with the arguments
the engine calls them with, as shapes, for the tests that lower or trace a
cell's program without building an engine (`test_tpu_compile.py`,
`test_mellum.py`)."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

# key -> (the model, kept alive so that its id is nobody else's; programs)
_DECODE_FNS = {}


def share_decode_programs(eng):
    """Hand `eng` the table of decode programs of the first engine that was
    built from the same arguments, params apart. Everything else of `eng`
    (pools, pages, slots, keys, prefill programs, compile record) stays its
    own.

    THE INVARIANT THIS RESTS ON: `LLMEngine._decode_fn` and `_block_decode`
    close over nothing but what the constructor was given besides `params`:
    the model (under a mesh a clone, so such engines share nothing), the
    config and the mesh. All three are the key. Params,
    pools, page tables, LoRA banks and PRNG keys are arguments of the jitted
    program, so an engine's own reach it. A decode program that came to
    close over anything else of its engine would make these tests run
    another engine's program without a word: put that thing in the key, or
    stop sharing."""
    key = (id(eng.model), dataclasses.astuple(eng.cfg), eng.mesh)
    _, eng._decode_fns = _DECODE_FNS.setdefault(
        key, (eng.model, eng._decode_fns))
    return eng


@functools.lru_cache(maxsize=None)
def _compiled_logprobs(reference, kw_items):
    kw = dict(kw_items)
    return jax.jit(lambda params, ids: reference.logprobs(params, ids, kw))


def reference_logprobs(reference, params, kw, ids, multiple):
    """The plain reference's rows for `ids`, from one compiled program for
    each (reference, kw, padded length): `ids` are padded with zeros at the
    end to a multiple of `multiple`, and the rows of the padding cut off.
    The caller says why no row before the padding sees it."""
    padded = jnp.asarray(list(ids) + [0] * (-len(ids) % multiple), jnp.int32)
    fn = _compiled_logprobs(reference, tuple(sorted(kw.items())))
    return np.asarray(fn(params, padded))[:len(ids)]


@functools.lru_cache(maxsize=None)
def tiny_family(name):
    """(model, params) of a family's tiny configuration, float32 on the CPU:
    `llama`, `olmo_hybrid`, `jamba` (a token a forward) or `sdar_moe` (a
    block of four). One of each a process, so that `share_decode_programs`
    finds the same model again."""
    if name == "llama":
        from ray_tpu.models.llama import LlamaConfig, LlamaModel

        model = LlamaModel(LlamaConfig.tiny())
        return model, model.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    if name == "olmo_hybrid":
        from ray_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                OlmoHybridModel)

        model = OlmoHybridModel(OlmoHybridConfig.tiny())
    elif name == "jamba":
        from ray_tpu.models.jamba import JambaConfig, JambaModel

        model = JambaModel(JambaConfig.tiny())
    else:
        from ray_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeModel

        assert name == "sdar_moe", name
        model = SdarMoeModel(SdarMoeConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(1))


def prompt_ids(n, seed=2):
    """`n` seeded token ids under 500 (never SDAR's MASK id, 511)."""
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 500)]


def cell_model(cell, layers=None):
    """(a benchmark cell's model at its first `layers` layers, or all; its
    traffic file)."""
    from benchmark.manifest import Manifest

    manifest = Manifest(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    made = manifest.cell(cell)
    cfg = manifest.config(made["config"])
    family = manifest.family(cfg["family"])
    kw = family.model_kwargs(cfg)
    if layers is not None and "layer_types" in kw:
        kw["layer_types"] = kw["layer_types"][:layers]
    elif layers is not None:
        kw["num_layers"] = layers
    return family.model(kw), manifest.traffic(made["traffic"])


def cell_at_depth(cell, layers=None):
    """(a serving cell's model as `cell_model` cuts it, its engine shapes)."""
    model, traffic = cell_model(cell, layers)
    return model, traffic["engine_config"]


def decode_call(model, ec, sharding=None):
    """(the jitted decode program, its arguments as shapes) as the engine
    calls it, where `sizing.lower_decode` (the benchmark's) describes one
    last token a row and a window of a static `decode_steps`: a window's
    token steps are a traced argument, the loop's trip count; for a model
    that generates by blocks the count stays static and what is carried
    between windows is two blocks' ids [rows, 2 * block_length] (the one
    awaiting its commit and the one a row is on)."""
    from benchmark import sizing

    eng = sizing._bare_engine(model, ec)
    b, block = eng.cfg.max_seqs, getattr(model, "block_length", 1)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    steps = (s((), jnp.int32),) if block == 1 else ()
    return eng._decode_fn(False, False), (
        sizing.param_shapes(model, sharding),
        sizing.cache_shapes(model, ec, sharding),
        s((b, 2 * block) if block > 1 else (b,), jnp.int32),
        s((b, eng.cfg.max_pages_per_seq), jnp.int32), s((b,), jnp.int32),
        s((b,), jnp.bool_), s((b,), jnp.float32), s((b,), jnp.float32),
        s((b,), jnp.int32), s((b, 2), jnp.uint32), None, s((b,), jnp.int32),
        *steps)


def prefill_call(model, ec, bucket, nb, sharding=None):
    """(the jitted prefill program, its arguments as shapes) as the engine
    calls it, where `sizing.lower_prefill` describes the arguments it had
    before a decode window was chained behind it: every slot's last token
    and length ride through it [max_seqs] and come back with the wave's rows
    scattered in, as the key table does. Block generation samples nothing
    in its prefill and passes none."""
    from benchmark import sizing

    eng = sizing._bare_engine(model, ec)
    b, mp = eng.cfg.max_seqs, eng.cfg.max_pages_per_seq
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    carry = ((None, None) if getattr(model, "block_length", 1) > 1
             else (s((b,), jnp.int32), s((b,), jnp.int32)))
    return eng._prefill_fn(bucket, nb, False, False), (
        sizing.param_shapes(model, sharding),
        sizing.cache_shapes(model, ec, sharding),
        s((nb, bucket), jnp.int32), s((nb, mp), jnp.int32),
        s((nb,), jnp.int32), s((nb,), jnp.int32), s((nb,), jnp.float32),
        s((nb,), jnp.float32), s((nb,), jnp.int32), s((b, 2), jnp.uint32),
        s((nb,), jnp.int32), None, s((nb,), jnp.int32), *carry)
