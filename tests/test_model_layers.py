"""The seam between a family's file and `models/layers.py`, for all seven
families at their tests' tiny configurations: a seed's parameter tree is the
one the family's own initializer drew before the initializers became one
(digests recorded at that commit; the seventh family's at the commit that
brought it), every model answers what the engine reads off it, and the cache
is one entry a layer."""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu import models  # noqa: E402
from ray_tpu.llm._internal.paged import PagedCacheConfig  # noqa: E402
from ray_tpu.models.layers import Decoder  # noqa: E402

FAMILIES = sorted(models.FAMILIES)

# family -> sha256 of (the leaves' paths, dtypes and shapes; the leaves'
# bytes), in path order, of the tree of PRNGKey(0): `init_params` where the
# family has one, `model.init` on [1, 8] ids for Llama. Taken at the parent
# of the PR that wrote `models/layers.py` (b2bb6a7), on the CPU.
DIGESTS = {
    "llama": (
        "f7cf47d416c9cd152179ace08c568061e43ab12985daa7d6a278daa3e24400da",
        "5826f9780fdb2385c3896c9eec5bbbd1c4cb857a42626621aa15e99b110aa6a1"),
    "olmo_hybrid": (
        "2777164553e200bb9b89c89a99234196ad5ebe0cb59ef410cb45c9b80228b4d5",
        "a658af50f35a78e4ebfee678d1a7ae73a797c64a1abc16c509fa46399ccb3e3a"),
    "sdar_moe": (
        "f521f4903083900670613218a7e849ea755abb0899e3c46acdddedab0dd8f7fd",
        "35ba0dfa96110f7f050fe06708b0ba9397cb0c56c32fd116a1f661df2ea9c8f0"),
    "jamba": (
        "be80c8405c1b6490180aaebb566877340802b33c0d2a536b00ad74e2ab27dabb",
        "c4186dd509dbe8b63b0fcfae9b9fae62cac859fe01ebe56404b57e9731b45255"),
    "granite_hybrid": (
        "3160dcacc5459ceb49f4be88b409b46c16d765f07c8b1d094372dfdb1234dffc",
        "1821135f4dfb02762916fa89835c5e018454b0862d6d5026f0c2641775db34f5"),
    "mellum": (
        "df9aedbafd47af6cef419e18385640e01eefbc35bfe0b783436681e2be82706a",
        "e220cce234348517df00df37a378bf33a8b5418813e0b947538ba4852932dee5"),
    # (written on `models/layers.py` from the start: taken at its own commit)
    "sarvam_mla": (
        "13957818c63bcebd0f2da94fe35ddfceeeb28821c4a7b0088cb358186ebcc91e",
        "bc958b5ff90e0df234ce1bd05416ab9bae4ad98928c0ff007dcb90b349ec5adb"),
    # (PR 51: taken at its own commit)
    "minicpm_sala": (
        "6c7369cfb4d351b98d50e06e7d49ad0229da8eb733853a625dbc703bc989f74e",
        "8e3cdcff8e8ab57f47ef5845ca9049f689e41c9e721e4cb55507c53a6ffd42ac"),
    # (PR 56: taken at its own commit)
    "nemotron_h": (
        "0c82c4ba40fdbb4f957ea444ee802d9e314edeaa757384333c320c5d5c98bab9",
        "60a1b911386f56ee67296ccf88509468d9d09bd62de31667d7fe26adfe8c1f25"),
}

# What the engine reads off a model: the value of a family that does not say
# otherwise, then what each tiny configuration says.
READ = {"state_layer_ids": (), "ring_layer_ids": (), "expert_layer_ids": (),
        "cacheless_layer_ids": (),
        "block_length": 1, "num_logits_to_keep": 0, "sliding_window": 0,
        "latent_layer_ids": (), "latent_width": 0, "index_layer_ids": (),
        "index_segments": 4}
SAYS = {
    "llama": {},
    "olmo_hybrid": {"state_layer_ids": (0, 1, 2, 4, 5, 6)},
    "sdar_moe": {"block_length": 4},
    "jamba": {"state_layer_ids": (0, 1, 3), "num_logits_to_keep": 1},
    "granite_hybrid": {"state_layer_ids": (0, 2),
                       "expert_layer_ids": (0, 1, 2),
                       "num_logits_to_keep": 1},
    "mellum": {"ring_layer_ids": (0, 1, 2), "expert_layer_ids": (0, 1, 2, 3),
               "num_logits_to_keep": 1, "sliding_window": 8},
    "sarvam_mla": {"latent_layer_ids": (0, 1, 2),
                   "expert_layer_ids": (1, 2), "num_logits_to_keep": 1,
                   "latent_width": 40},
    "minicpm_sala": {"state_layer_ids": (0, 2, 3), "index_layer_ids": (1,),
                     "num_logits_to_keep": 1},
    "nemotron_h": {"state_layer_ids": (0, 4), "expert_layer_ids": (1, 3),
                   "cacheless_layer_ids": (1, 3), "num_logits_to_keep": 1},
}


def _tiny(name):
    fam = models.family(name)
    return fam.load("model")(fam.load("config").tiny())


def _digests(params):
    shapes, values = hashlib.sha256(), hashlib.sha256()
    for path, leaf in sorted(
            (jax.tree_util.keystr(p), x)
            for p, x in jax.tree_util.tree_leaves_with_path(params)):
        shapes.update(f"{path} {leaf.dtype} {leaf.shape}\n".encode())
        values.update(np.asarray(leaf).tobytes())
    return shapes.hexdigest(), values.hexdigest()


@pytest.mark.parametrize("name", FAMILIES)
def test_a_seeds_tree_is_bit_for_bit_what_the_family_drew_before(name):
    model = _tiny(name)
    rng = jax.random.PRNGKey(0)
    if name == "llama":
        # (the loader's one-program path: Llama has no `init_params`)
        assert not hasattr(model, "init_params")
        params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        params = model.init_params(rng)
        # and it is the tree the whole model's own `init` declares
        whole = jax.eval_shape(
            lambda: model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"])
        assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == \
            jax.tree.map(lambda x: (x.shape, x.dtype), whole)
    shapes, values = _digests(params)
    assert shapes == DIGESTS[name][0]      # paths, dtypes and shapes
    assert values == DIGESTS[name][1]      # every leaf's bytes


@pytest.mark.parametrize("name", FAMILIES)
def test_a_model_answers_what_the_engine_reads_and_caches_a_layer_an_entry(
        name):
    model = _tiny(name)
    assert isinstance(model, Decoder) and set(SAYS[name]) <= set(READ)
    for attr, default in READ.items():
        got = getattr(model, attr)
        assert type(got) is type(default), (attr, got)
        assert got == SAYS[name].get(attr, default), attr
        assert getattr(Decoder, attr) == default
    layers = model.cfg.num_layers
    assert all(0 <= i < layers and type(i) is int for attr in READ
               if attr.endswith("_ids") for i in getattr(model, attr))
    # (a page of a family that selects pages is its selection block)
    cache_cfg = PagedCacheConfig(
        num_pages=9, page_size=getattr(model.cfg, "block_size", 8),
        max_seqs=2, max_pages_per_seq=4)
    caches = model.init_cache(cache_cfg)
    assert len(caches) == layers
    for i, entry in enumerate(caches):
        if i in model.cacheless_layer_ids:
            assert entry == ()      # a block that keeps nothing
            continue
        if i in model.latent_layer_ids:
            # one pool of the allocator's pages, 40 values on whole lanes
            assert entry.shape == (cache_cfg.num_pages, cache_cfg.page_size,
                                   128)
            continue
        if i in model.state_layer_ids and not isinstance(entry, tuple):
            # a state without a convolution's tail beside it
            assert entry.shape[0] == cache_cfg.max_seqs
            assert entry.dtype == jnp.float32
            continue
        first, second, *index = entry
        if i in model.state_layer_ids:
            assert first.shape[0] == second.shape[0] == cache_cfg.max_seqs
            assert second.dtype == jnp.float32
        else:
            # beside the K/V of a layer that selects pages, a page's
            # segment means, float32
            assert [x.shape for x in index] == (
                [(cache_cfg.num_pages, model.index_segments, first.shape[2])]
                if i in model.index_layer_ids else [])
            assert all(x.dtype == jnp.float32 for x in index)
            # K/V pages: the allocator's pool, or `max_seqs` rings of two
            pages = (2 * 2 if i in model.ring_layer_ids
                     else cache_cfg.num_pages)
            assert first.shape == second.shape and first.shape[:2] == (
                pages, cache_cfg.page_size)
    if models.sharding_rules(model) is None:
        # the one refusal, before anything of the arguments is read
        with pytest.raises(NotImplementedError,
                           match=f"^{type(model).__name__}: neither its "
                           "parameters nor its layers' caches have a "
                           "sharding under a mesh"):
            model.init_cache(None, mesh=object())
    else:
        from ray_tpu.parallel.mesh import create_mesh

        mesh = create_mesh({"tensor": 2}, devices=jax.devices()[:2])
        sharded = model.clone(mesh=mesh).init_cache(cache_cfg, mesh)
        assert len(sharded) == layers
        assert sharded[0][0].sharding.mesh.shape["tensor"] == 2


def test_the_seven_without_banks_refuse_lora_in_one_wording():
    for name in FAMILIES:
        model = _tiny(name)
        if name == "llama":
            continue
        with pytest.raises(NotImplementedError,
                           match=f"^{type(model).__name__} has no LoRA "
                           "banks"):
            model.apply({"params": {}}, jnp.zeros((1, 8), jnp.int32),
                        lora={})
