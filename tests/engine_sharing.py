"""What the model families' engine tests (`test_sdar_moe.py`,
`test_olmo_hybrid.py`, `test_jamba.py`) compile once a module and not once a
test: an engine's decode programs and the plain reference's forward."""

import dataclasses
import functools

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
    config, the mesh and `param_transform`. All four are the key. Params,
    pools, page tables, LoRA banks and PRNG keys are arguments of the jitted
    program, so an engine's own reach it. A decode program that came to
    close over anything else of its engine would make these tests run
    another engine's program without a word: put that thing in the key, or
    stop sharing."""
    key = (id(eng.model), dataclasses.astuple(eng.cfg), eng.mesh,
           eng.param_transform)
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
