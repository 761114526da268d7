"""ops/paged_attention.py is the one place the K/V pool's layout lives: both
families' `init_cache` make their pages with its initialiser, and its one
call per attention layer is the three primitives in turn."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm._internal.paged import PagedCacheConfig  # noqa: E402
from ray_tpu.ops.paged_attention import (  # noqa: E402
    init_kv_pages,
    paged_attention,
    paged_write,
    paged_write_attend,
)

CACHE_CFG = PagedCacheConfig(num_pages=33, page_size=8, max_seqs=2,
                             max_pages_per_seq=16)


def _llama():
    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.parallel.mesh import create_mesh

    # two KV heads over a tensor axis of two: the pages are split
    return (LlamaModel(LlamaConfig.tiny()),
            create_mesh({"tensor": 2}, devices=jax.devices()[:2]))


def _olmo_hybrid():
    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig, OlmoHybridModel

    return OlmoHybridModel(OlmoHybridConfig.tiny()), None


@pytest.mark.parametrize("family", [_llama, _olmo_hybrid])
def test_init_cache_makes_kv_pages_with_the_ops_initialiser(family):
    model, mesh = family()
    cfg = model.cfg
    caches = model.init_cache(CACHE_CFG, mesh)
    want = init_kv_pages(CACHE_CFG, cfg.num_kv_heads, cfg.head_dim,
                         cfg.dtype, mesh=mesh)
    kv_layers = [i for i in range(cfg.num_layers)
                 if i not in model.state_layer_ids]
    assert len(caches) == cfg.num_layers and kv_layers
    for i in kv_layers:
        assert len(caches[i]) == 2
        for got, page in zip(caches[i], want):
            assert (got.shape, got.dtype) == (page.shape, page.dtype)
            assert got.sharding.is_equivalent_to(page.sharding, got.ndim)
    if mesh is not None:
        assert not want[0].sharding.is_fully_replicated


@pytest.mark.parametrize("s", [6, 1], ids=["prefill", "decode"])
def test_write_attend_is_write_k_write_v_attend(s):
    rng = np.random.default_rng(s)
    b, h, hk, d, ps, mp, p = 3, 4, 2, 16, 4, 4, 13
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = arr(b, s, h, d), arr(b, s, hk, d), arr(b, s, hk, d)
    pages = (arr(hk, p, ps, d), arr(hk, p, ps, d))
    page_table = jnp.asarray(
        rng.permutation(p - 1)[:b * mp].reshape(b, mp), jnp.int32)
    starts = jnp.asarray([0, 3, 9], jnp.int32)
    positions = starts[:, None] + jnp.arange(s)[None, :]
    # the last row writes nothing; the second only its first token
    true_lens = jnp.asarray([s, 1, 0], jnp.int32)
    write_mask = jnp.arange(s)[None, :] < true_lens[:, None]
    seq_lens = starts + true_lens

    out, (k_pages, v_pages) = paged_write_attend(
        q, k, v, pages, page_table, positions, write_mask, seq_lens)

    want_k = paged_write(pages[0], k, page_table, positions, write_mask)
    want_v = paged_write(pages[1], v, page_table, positions, write_mask)
    want = paged_attention(q, want_k, want_v, page_table, positions,
                           seq_lens)
    np.testing.assert_array_equal(np.asarray(k_pages), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(v_pages), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert not np.array_equal(np.asarray(k_pages), np.asarray(pages[0]))
