"""LLM engine tests: paged KV + continuous batching vs a no-cache oracle
(reference strategy: llm/tests with mocked engines — here the engine is
real and the oracle is the same model run cacheless)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, LlamaModel  # noqa: E402


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(vocab_size=128)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def oracle_greedy(model, params, prompt, n):
    """Greedy continuation by full recompute (no cache) — the gold answer."""
    ids = list(prompt)
    out = []
    for _ in range(n):
        logits = model.apply({"params": params},
                             jnp.asarray([ids], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        ids.append(tok)
    return out


def drain(engine, want_done=None):
    got = {}
    steps = 0
    while engine.has_work() and steps < 500:
        for so in engine.step():
            got.setdefault(so.request_id, []).append(so.token)
        steps += 1
        if want_done is not None and set(want_done) <= set(
                k for k in got if True):
            pass
    return got


def test_single_request_matches_oracle(tiny_model):
    model, params = tiny_model
    prompt = [5, 17, 42, 7]
    expect = oracle_greedy(model, params, prompt, 8)
    eng = LLMEngine(model, params, EngineConfig(max_seqs=2, page_size=4,
                                                max_pages_per_seq=16))
    eng.add_request(Request("r1", prompt, max_tokens=8))
    got = drain(eng)
    assert got["r1"] == expect


def test_continuous_batching_matches_per_request_oracle(tiny_model):
    model, params = tiny_model
    prompts = {
        "a": [1, 2, 3],
        "b": [9, 8, 7, 6, 5],
        "c": [100, 3],
        "d": [11, 22, 33, 44],
    }
    expect = {k: oracle_greedy(model, params, p, 6)
              for k, p in prompts.items()}
    eng = LLMEngine(model, params, EngineConfig(max_seqs=2, page_size=4,
                                                max_pages_per_seq=16))
    # Only 2 slots for 4 requests: admission interleaves with decode.
    for k, p in prompts.items():
        eng.add_request(Request(k, p, max_tokens=6))
    got = drain(eng)
    assert got == expect


def test_page_reuse_across_many_requests(tiny_model):
    model, params = tiny_model
    cfg = EngineConfig(max_seqs=2, page_size=4, max_pages_per_seq=4,
                       num_pages=8)  # deliberately tiny page pool
    eng = LLMEngine(model, params, cfg)
    for i in range(6):
        eng.add_request(Request(f"r{i}", [i + 1, i + 2], max_tokens=5))
    got = drain(eng)
    assert len(got) == 6
    assert all(len(v) == 5 for v in got.values())
    assert eng.allocator.num_free == eng.cache_cfg.num_pages  # all freed


def test_stop_token_and_temperature_paths(tiny_model):
    model, params = tiny_model
    eng = LLMEngine(model, params, EngineConfig(max_seqs=2, page_size=4,
                                                max_pages_per_seq=8))
    expect = oracle_greedy(model, params, [3, 4], 12)
    # Stop on the first token value that hasn't appeared before it, so the
    # engine must generate exactly k+1 tokens.
    k = next((i for i in range(1, 12) if expect[i] not in expect[:i]), None)
    if k is not None:
        stop = expect[k]
        eng.add_request(Request("s", [3, 4], max_tokens=12,
                                stop_token=stop))
    eng.add_request(Request("t", [5, 6], max_tokens=4, temperature=0.8))
    got = drain(eng)
    if k is not None:
        assert got["s"] == expect[:k + 1]
    assert len(got["t"]) == 4


@pytest.mark.parametrize("heads,kv_heads,seq_lens", [
    (8, 2, [5, 17, 31]),
    # no grouping (H == HK, a [1, D] query tile): the full-attention layers
    # of models/olmo_hybrid.py. Same kernel, same call as with a group.
    (4, 4, [1, 18, 32]),
], ids=["grouped", "one_query_head_per_kv_head"])
def test_paged_decode_kernel_matches_jnp(heads, kv_heads, seq_lens):
    """Pallas decode kernel (interpret mode on CPU) vs the jnp gather path,
    over pools in the shapes `init_kv_pages` makes."""
    from ray_tpu.llm._internal.paged import PagedCacheConfig
    from ray_tpu.ops.paged_attention import (
        init_kv_pages,
        paged_attention,
        paged_attention_decode_kernel,
    )

    rng = np.random.default_rng(heads)
    B, D, MP = 3, 64, 4
    cache_cfg = PagedCacheConfig(num_pages=16, page_size=8, max_seqs=B,
                                 max_pages_per_seq=MP)
    q = jnp.asarray(rng.standard_normal((B, 1, heads, D)), jnp.float32)
    k_pages, v_pages = (
        jnp.asarray(rng.standard_normal(zeros.shape), jnp.float32)
        for zeros in init_kv_pages(cache_cfg, kv_heads, D, jnp.float32))
    page_table = jnp.asarray(
        rng.permutation(15)[: B * MP].reshape(B, MP), jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)

    ref = paged_attention(q, k_pages, v_pages, page_table,
                          (seq_lens - 1)[:, None], seq_lens,
                          use_kernel=False)
    out = paged_attention_decode_kernel(q, k_pages, v_pages, page_table,
                                        seq_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
