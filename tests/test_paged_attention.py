"""ops/paged_attention.py is the one place the K/V pool's layout lives: both
families' `init_cache` make their pages with its initialiser, and its one
call per attention layer is the three primitives in turn. The layout is
token major, [P, ps, HK*D]: what `paged_write` scatters, `paged_gather`
reads back in order and the decode kernel walks page by page are held to one
another and to a dense softmax here."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm._internal.paged import PagedCacheConfig  # noqa: E402
from ray_tpu.ops.paged_attention import (  # noqa: E402
    init_kv_pages,
    paged_attention,
    paged_attention_decode_kernel,
    paged_gather,
    paged_write,
    paged_write_attend,
    pages_spec,
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
        # the lanes are split between the two devices, a whole KV head each
        assert not want[0].sharding.is_fully_replicated
        assert {sh.data.shape for sh in want[0].addressable_shards} == {
            (CACHE_CFG.num_pages, CACHE_CFG.page_size,
             cfg.num_kv_heads // 2 * cfg.head_dim)}


@pytest.mark.parametrize("kv_heads,tensor,split", [
    (8, 4, True), (30, 2, True),
    (2, 4, False),    # 2 * D lanes divide by 4, the heads do not: replicated
    (30, 4, False),
])
def test_pages_spec_splits_whole_kv_heads_or_nothing(kv_heads, tensor, split):
    from jax.sharding import PartitionSpec

    from ray_tpu.parallel.mesh import create_mesh

    mesh = create_mesh({"tensor": tensor}, devices=jax.devices()[:tensor])
    assert pages_spec(kv_heads, mesh) == PartitionSpec(
        None, None, "tensor" if split else None)


@pytest.mark.parametrize("s", [6, 1], ids=["prefill", "decode"])
def test_write_attend_is_write_k_write_v_attend(s):
    rng = np.random.default_rng(s)
    b, h, hk, d, mp = 3, 4, 2, 16, 4
    cache_cfg = PagedCacheConfig(num_pages=13, page_size=4, max_seqs=b,
                                 max_pages_per_seq=mp)
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = arr(b, s, h, d), arr(b, s, hk, d), arr(b, s, hk, d)
    pages = tuple(arr(*zeros.shape) for zeros in
                  init_kv_pages(cache_cfg, hk, d, jnp.float32))
    page_table = jnp.asarray(
        rng.permutation(12)[:b * mp].reshape(b, mp), jnp.int32)
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
    # the row whose mask is all false left its own pages as they were
    for got, was in ((k_pages, pages[0]), (v_pages, pages[1])):
        np.testing.assert_array_equal(np.asarray(got)[page_table[2]],
                                      np.asarray(was)[page_table[2]])


def _written_pool(rng, hk, d, ps, mp, seq_lens, dtype=jnp.float32):
    """A pool in `init_kv_pages`' shapes that holds, for row i, `seq_lens[i]`
    known tokens (written by `paged_write`, a prefill then single tokens)
    and noise on every other slot. Returns the tokens [B, mp*ps, HK, D] too
    (noise beyond each row's length)."""
    b = len(seq_lens)
    cache_cfg = PagedCacheConfig(num_pages=b * mp + 2, page_size=ps,
                                 max_seqs=b, max_pages_per_seq=mp)
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    page_table = jnp.asarray(
        rng.permutation(b * mp + 1)[:b * mp].reshape(b, mp), jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    pools, tokens = [], []
    for zeros in init_kv_pages(cache_cfg, hk, d, dtype):
        noise = arr(*zeros.shape)
        kv = arr(b, mp * ps, hk, d)
        # all but each row's last token in one masked call, the last alone
        positions = jnp.broadcast_to(jnp.arange(mp * ps), (b, mp * ps))
        pool = paged_write(noise, kv, page_table, positions,
                           positions < lens[:, None] - 1)
        last = jnp.maximum(lens - 1, 0)[:, None]
        pool = paged_write(
            pool, jnp.take_along_axis(kv, last[:, :, None, None], axis=1),
            page_table, last, lens[:, None] > 0)
        # a row of length 0 wrote nothing at all
        for i in np.flatnonzero(np.asarray(seq_lens) == 0):
            np.testing.assert_array_equal(
                np.asarray(pool)[page_table[i]],
                np.asarray(noise)[page_table[i]])
        pools.append(pool)
        tokens.append(kv)
    return pools, tokens, page_table, lens


@pytest.mark.parametrize("hk,d", [(2, 16), (1, 128), (3, 32)])
def test_write_then_gather_returns_the_tokens_in_order(hk, d):
    ps, mp = 4, 3
    seq_lens = [0, 1, ps, ps + 1, mp * ps]
    (pool, _), (tokens, _), page_table, lens = _written_pool(
        np.random.default_rng(hk), hk, d, ps, mp, seq_lens)
    got = np.asarray(paged_gather(pool, page_table))
    assert got.shape == (len(seq_lens), mp * ps, hk * d)
    got = got.reshape(len(seq_lens), mp * ps, hk, d)
    for i, n in enumerate(seq_lens):
        np.testing.assert_array_equal(got[i, :n], np.asarray(tokens)[i, :n])
        if n < mp * ps:  # and nothing else was written to the row's pages
            assert not np.array_equal(got[i, n:], np.asarray(tokens)[i, n:])


def _dense_softmax_attention(q, k, v, seq_lens):
    """float64 attention of q [B,1,H,D] over the first seq_lens[i] of
    k, v [B,ctx,HK,D]: no pages, no kernel."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, _, h, d = q.shape
    rep = h // k.shape[2]
    out = np.zeros((b, 1, h, d))
    for i, n in enumerate(seq_lens):
        for j in range(h if n else 0):
            logits = k[i, :n, j // rep] @ q[i, 0, j] / np.sqrt(d)
            w = np.exp(logits - logits.max())
            out[i, 0, j] = (w / w.sum()) @ v[i, :n, j // rep]
    return out


@pytest.mark.parametrize("chunk", [2, 3, None],
                         ids=["chunk_divides", "chunk_does_not", "derived"])
@pytest.mark.parametrize("d", [128, 16])
@pytest.mark.parametrize("hg", [4, 1])
def test_decode_kernel_matches_gather_path_and_dense_softmax(hg, d, chunk):
    """The Pallas kernel (interpret mode) over the token-major pool, with
    query heads grouped four to a KV head (Llama) and one to one (the
    hybrid's full layers), rows of length 0, 1, a page, a page plus one and
    the table's capacity, and a chunk of pages that does and does not divide
    the table's four."""
    hk, ps, mp = 2, 8, 4
    seq_lens = [0, 1, ps, ps + 1, mp * ps]
    rng = np.random.default_rng(7 * hg + d)
    (k_pages, v_pages), (k, v), page_table, lens = _written_pool(
        rng, hk, d, ps, mp, seq_lens)
    q = jnp.asarray(rng.standard_normal((len(seq_lens), 1, hk * hg, d)),
                    jnp.float32)

    out = np.asarray(paged_attention_decode_kernel(
        q, k_pages, v_pages, page_table, lens, pages_per_chunk=chunk,
        interpret=True))
    gathered = np.asarray(paged_attention(
        q, k_pages, v_pages, page_table, (lens - 1)[:, None], lens,
        use_kernel=False))
    dense = _dense_softmax_attention(q, k, v, seq_lens)

    np.testing.assert_array_equal(out[0], 0.0)    # nothing to attend to
    np.testing.assert_allclose(out[1:], gathered[1:], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out, dense, atol=2e-5, rtol=2e-5)
    # one token attended alone is its value, for every head of the group
    np.testing.assert_allclose(
        out[1, 0], np.repeat(np.asarray(v)[1, 0], hg, axis=0), atol=1e-6)


def test_decode_kernel_never_reads_past_the_page_table():
    """A free slot's length keeps counting while decode windows chain on
    the device: past the table's capacity the kernel attends over the
    table's pages and nothing else."""
    hk, hg, d, ps, mp = 2, 2, 16, 8, 4
    rng = np.random.default_rng(3)
    (k_pages, v_pages), _, page_table, lens = _written_pool(
        rng, hk, d, ps, mp, [mp * ps, mp * ps])
    q = jnp.asarray(rng.standard_normal((2, 1, hk * hg, d)), jnp.float32)
    run = lambda lens, chunk: np.asarray(paged_attention_decode_kernel(
        q, k_pages, v_pages, page_table, lens, pages_per_chunk=chunk,
        interpret=True))
    for chunk in (3, None):
        np.testing.assert_array_equal(
            run(lens + jnp.asarray([5, 3 * ps + 1], jnp.int32), chunk),
            run(lens, chunk))


def test_decode_kernel_under_a_tensor_mesh_attends_whole_kv_heads():
    """The shard_map wrapper splits the pool's lanes and the query heads at
    the same KV-head boundary: each device attends its own heads, and the
    answer is the one-device answer."""
    from ray_tpu.parallel.mesh import create_mesh

    hk, hg, d, ps, mp = 4, 2, 16, 8, 3
    seq_lens = [0, 5, mp * ps]
    rng = np.random.default_rng(11)
    (k_pages, v_pages), _, page_table, lens = _written_pool(
        rng, hk, d, ps, mp, seq_lens)
    q = jnp.asarray(rng.standard_normal((len(seq_lens), 1, hk * hg, d)),
                    jnp.float32)
    mesh = create_mesh({"tensor": 2}, devices=jax.devices()[:2])
    alone = paged_attention_decode_kernel(
        q, k_pages, v_pages, page_table, lens, interpret=True)
    split = paged_attention_decode_kernel(
        q, k_pages, v_pages, page_table, lens, interpret=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(split), np.asarray(alone),
                               atol=1e-6, rtol=1e-6)
