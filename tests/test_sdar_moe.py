"""SDAR-MoE at a tiny size on the CPU (hidden 64, 4 / 2 heads of 32, 16
experts of 32 routed top-8, two layers, float32, seeded): the block mask in
`ops/paged_attention.py`, and the engine's block generation (cache-fill
prefill, denoising passes through pages of which a block's first commits the
block before it, the prompt's remainder)
against the plain reference `benchmark/references/sdar_moe.py` (no cache,
every expert over every token). Logprobs and not tokens: with seeded weights
the largest logit changes on rounding."""

import dataclasses
import functools
import math
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.manifest import Manifest  # noqa: E402
from engine_sharing import reference_logprobs, share_decode_programs  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.llm._internal.paged import PagedCacheConfig  # noqa: E402
from ray_tpu.models.layers import apply_rope  # noqa: E402
from ray_tpu.models.sdar_moe import (  # noqa: E402
    SdarMoeConfig,
    SdarMoeModel,
    block_attention,
)
from ray_tpu.ops import paged_attention as pa  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on the CPU through two layers, the engine's sums through pages and
# the sorted experts in another order than the reference's (seen: 2.2e-6)
TOL = 5e-5
B = 4


def _family(remasking="sequential"):
    cfg = SdarMoeConfig.tiny(remasking=remasking)
    model = SdarMoeModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    params = model.init_params(jax.random.PRNGKey(1))
    kw = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return model, params, kw, Manifest(REPO).reference("sdar_moe")


@pytest.fixture(scope="module")
def tiny():
    return _family()


def _ids(n, seed=2):
    # never the MASK id (511)
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 500)]


def _engine(model, params, **kw):
    """A new engine, whose decode programs are compiled once for each
    (model, config) of the module (`engine_sharing`)."""
    cfg = dict(max_seqs=2, page_size=8, max_pages_per_seq=8,
               prefill_buckets=(16, 32), decode_steps=8, max_logprobs=3)
    cfg.update(kw)
    return share_decode_programs(
        LLMEngine(model, params, EngineConfig(**cfg)))


def _run(eng, *requests):
    """Step the engine until idle; {request id: [StepOutput]}."""
    for r in requests:
        eng.add_request(r)
    got = {}
    for _ in range(500):
        if not eng.has_work():
            break
        for so in eng.step():
            got.setdefault(so.request_id, []).append(so)
    assert not eng.has_work()
    return got


def _gap(reference, params, kw, prompt, outs):
    """Largest logprob gap between an engine request's reported top tokens
    and the reference's rows for prompt + tokens: row r is position r+1
    under MASK from r+1 to its block's end, what the pass that revealed
    position r+1 left to right computed."""
    toks = [o.token for o in outs]
    ids = list(prompt) + toks[:-1]
    # padded to 64 at the end, which no row before the padding sees (a row
    # reads the blocks before its own and its own block's head)
    ref = reference_logprobs(reference, params, kw, ids, 64)[len(prompt) - 1:]
    return max(abs(float(ref[i, t]) - lp)
               for i, o in enumerate(outs) for t, lp in o.top_logprobs)


def _dispatches(run):
    """`run()`'s result and the arguments of the `dispatch_decode` spans it
    left in the flight recorder."""
    from ray_tpu._private import flight_recorder as fr

    before = len(fr.dump_events())
    got = run()
    return got, [e["args"] for e in fr.dump_events()[before:]
                 if e.get("kind") == "span"
                 and e["name"] == "ray_tpu.engine.dispatch_decode"]


@functools.lru_cache(maxsize=None)
def _whole_sequence_kv(model):
    """The model's whole-sequence form on ids [S], keeping what `k_norm`
    and `v_proj` return; compiled once a length, not run op by op a call."""
    return jax.jit(lambda params, ids: model.apply(
        {"params": params}, ids[None],
        capture_intermediates=lambda m, _: m.name in ("k_norm", "v_proj"),
        mutable=["intermediates"])[1])


def _pool_gap(eng, params, slot, ids):
    """Largest gap, over every layer's K and V, between what the pool holds
    at positions 0 .. len(ids)-1 of `slot`'s pages and what the model's
    whole-sequence form (no cache, block mask) computes on `ids`: the
    outputs of `k_norm` (then rotated) and of `v_proj`."""
    n, ps, cfg = len(ids), eng.cfg.page_size, eng.model.cfg
    state = _whole_sequence_kv(eng.model)(params, jnp.asarray(ids, jnp.int32))
    pages = eng.page_table[slot, :-(-n // ps)]
    gap = 0.0
    for i, pool in enumerate(eng.caches):
        seen = state["intermediates"][f"layers_{i}"]["self_attn"]
        want = (apply_rope(seen["k_norm"]["__call__"][0],
                           jnp.arange(n)[None], cfg.rope_theta),
                seen["v_proj"]["__call__"][0])
        for held, w in zip(pool, want):
            held = np.asarray(held)[pages].reshape(-1, held.shape[-1])[:n]
            gap = max(gap, float(np.abs(
                held - np.asarray(w).reshape(n, -1)).max()))
    return gap


# -- the block mask in ops/paged_attention.py --------------------------------
def _pool(lens, ps=8, mp=4, hk=2, d=32, seed=0):
    """A pool written through `paged_write` with `lens[i]` tokens a row, and
    the dense k, v it holds."""
    b, ctx = len(lens), ps * mp
    cache = PagedCacheConfig(num_pages=b * mp + 1, page_size=ps, max_seqs=b,
                             max_pages_per_seq=mp)
    k_pages, v_pages = pa.init_kv_pages(cache, hk, d, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k, v = (jax.random.normal(key, (b, ctx, hk, d)) for key in ks[:2])
    table = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)[:, ::-1]
    pos = jnp.broadcast_to(jnp.arange(ctx)[None], (b, ctx))
    live = pos < jnp.asarray(lens)[:, None]
    k_pages = pa.paged_write(k_pages, k, table, pos, live)
    v_pages = pa.paged_write(v_pages, v, table, pos, live)
    return k_pages, v_pages, table, k, v, ks[2]


def _dense(q, k, v, q_pos, lens, block):
    """Softmax over keys j with floor(j/block) <= floor(i/block), j < len."""
    rep = q.shape[2] // k.shape[2]
    k, v = (np.repeat(np.asarray(t, np.float64), rep, axis=2) for t in (k, v))
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64), k)
    logits /= math.sqrt(q.shape[-1])
    j = np.arange(k.shape[1])
    seen = ((j[None, None] // block <= np.asarray(q_pos)[:, :, None] // block)
            & (j[None, None] < np.asarray(lens)[:, None, None]))
    logits = np.where(seen[:, None], logits, -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("block", [1, 4])
def test_gather_path_is_the_dense_block_masked_softmax(block):
    lens = [20, 12]
    k_pages, v_pages, table, k, v, key = _pool(lens)
    q = jax.random.normal(key, (2, 12, 4, 32))
    q_pos = jnp.asarray([[8 + i for i in range(12)], list(range(12))])
    got = pa.paged_attention(q, k_pages, v_pages, table, q_pos,
                             jnp.asarray(lens), use_kernel=False,
                             block_length=block)
    want = _dense(q, k, v, q_pos, lens, block)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    if block > 1:  # a block's first query sees its last key
        causal = _dense(q, k, v, q_pos, lens, 1)
        assert np.abs(want - causal).max() > 1e-2


@pytest.mark.parametrize("lens", [[4, 24], [32, 8]])
def test_kernel_takes_a_block_of_four_queries_as_the_gather_path_does(lens):
    """The decode kernel (interpret mode) with S = 4 queries a row, folded
    under the KV heads, against the gather path: one block a row, already
    written, so every query sees every key below `seq_lens`."""
    k_pages, v_pages, table, _, _, key = _pool(lens)
    q = jax.random.normal(key, (2, B, 4, 32))
    lens = jnp.asarray(lens)
    q_pos = lens[:, None] - B + jnp.arange(B)[None]
    want = pa.paged_attention(q, k_pages, v_pages, table, q_pos, lens,
                              use_kernel=False, block_length=B)
    got = pa.paged_attention_decode_kernel(q, k_pages, v_pages, table, lens,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # and `paged_attention` picks it by the number of queries
    via = pa.paged_attention(q, k_pages, v_pages, table, q_pos, lens,
                             use_kernel=True, block_length=B)
    np.testing.assert_allclose(np.asarray(via), np.asarray(got), atol=1e-6)


def test_models_whole_sequence_form_is_the_references_clean_stream(tiny):
    model, params, kw, reference = tiny
    ids = jnp.asarray(_ids(12), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0], axis=-1)
    with jax.default_matmul_precision("highest"):
        want = reference._head(
            params, reference._forward(params, ids, [], kw)[0], kw)
    np.testing.assert_allclose(np.asarray(got)[:, :-1],
                               np.asarray(want)[:, :-1], atol=TOL)
    assert np.isneginf(np.asarray(got)[:, kw["mask_token_id"]]).all()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 4, 32))
    k, v = q[:, :, :2], q[:, :, 2:]
    np.testing.assert_allclose(
        np.asarray(block_attention(q, k, v, B)),
        _dense(q, k, v, np.arange(8)[None], [8], B), atol=1e-5)


# -- the engine against the reference ----------------------------------------
@pytest.mark.parametrize("prompt_len", [12, 13, 14, 15])
def test_engine_matches_the_reference_for_every_prompt_remainder(
        tiny, prompt_len):
    """Prefill of the prompt's whole blocks, then four blocks of four
    denoising passes through pages, for a prompt that
    leaves 0, 1, 2 and 3 tokens to its first block; 13 tokens, so the last
    block is cut by `max_tokens`."""
    model, params, kw, reference = tiny
    prompt = _ids(prompt_len, seed=prompt_len)
    outs = _run(_engine(model, params),
                Request("a", prompt, max_tokens=13, logprobs=3))["a"]
    assert len(outs) == 13 and outs[-1].finished
    assert not any(o.finished for o in outs[:-1])
    assert kw["mask_token_id"] not in [o.token for o in outs]
    assert _gap(reference, params, kw, prompt, outs) < TOL


def test_sequential_rows_are_the_pass_by_pass_generation(tiny):
    """`logprobs` (one forward over five streams, what the benchmark's check
    reads) and `generate` (a forward a pass, no stream) agree."""
    _, params, kw, reference = tiny
    prompt = _ids(13, seed=5)
    toks, rows, order, _ = reference.generate(params, prompt, 9, kw)
    assert order == list(range(13, 22))
    ids = jnp.asarray(prompt + toks[:-1], jnp.int32)
    want = np.asarray(reference.logprobs(params, ids, kw))[12:]
    keep = np.arange(want.shape[1]) != kw["mask_token_id"]
    np.testing.assert_allclose(rows[:, keep], want[:, keep], atol=TOL)


def test_two_rows_of_different_lengths_share_a_window(tiny):
    model, params, kw, reference = tiny
    short, long = _ids(3, seed=7), _ids(22, seed=8)
    got = _run(_engine(model, params),
               Request("s", short, max_tokens=10, logprobs=3),
               Request("l", long, max_tokens=17, logprobs=3))
    assert [len(got[r]) for r in "sl"] == [10, 17]
    # a prompt shorter than one block has no prefill: its tokens are the
    # head of its first block
    assert _gap(reference, params, kw, short, got["s"]) < TOL
    assert _gap(reference, params, kw, long, got["l"]) < TOL


def test_prompt_of_three_tokens_runs_no_prefill(tiny):
    model, params, kw, reference = tiny
    eng = _engine(model, params)
    outs = _run(eng, Request("a", _ids(3), max_tokens=6, logprobs=3))["a"]
    assert len(outs) == 6 and not eng._prefill_fns
    assert _gap(reference, params, kw, _ids(3), outs) < TOL


def test_stop_token_inside_a_block_ends_the_request_there(tiny):
    model, params, _, _ = tiny
    prompt = _ids(14)
    free = _run(_engine(model, params),
                Request("a", prompt, max_tokens=12))["a"]
    toks = [o.token for o in free]
    # the first token not seen before it, at a position that is not its
    # block's last
    at = next(i for i in range(1, 12)
              if toks[i] not in toks[:i] and (14 + i) % B != B - 1)
    eng = _engine(model, params)
    pages = eng.allocator.num_free
    outs = _run(eng, Request("a", prompt, max_tokens=12,
                             stop_token=toks[at]))["a"]
    assert [o.token for o in outs] == toks[:at + 1]
    assert outs[-1].finished and not eng.running
    assert eng.allocator.num_free == pages


@pytest.mark.parametrize("pipeline", [True, False])
def test_chained_windows_and_a_second_request_through_the_slot(tiny, pipeline):
    """Windows chained off the device's block and lengths give what windows
    dispatched from the host's mirrors give, and a slot's second request
    starts from its own remainder."""
    model, params, kw, reference = tiny
    eng = _engine(model, params, max_seqs=1, pipeline_dispatch=pipeline)
    first, second = _ids(17, seed=3), _ids(6, seed=4)
    got, decode = _dispatches(lambda: _run(
        eng, Request("a", first, max_tokens=27, logprobs=3),
        Request("b", second, max_tokens=5, logprobs=3)))
    assert [len(got[r]) for r in "ab"] == [27, 5]
    assert _gap(reference, params, kw, first, got["a"]) < TOL
    assert _gap(reference, params, kw, second, got["b"]) < TOL
    # The slot forgot the first request's last block: the second's first
    # window had nothing to commit, and its pages hold its own blocks (a
    # stale commit would have gone to positions 0-3, its prefilled prompt).
    assert [d["fresh_rows"] for d in decode] == [1, 0, 0, 0, 1]
    assert [d["chained"] for d in decode] == [False] + [pipeline] * 3 + [False]
    # one slot: "a" ends in a window with none queued behind it
    # (`all_finishing`), so "b" is admitted with nothing in flight and the
    # chain had no finish and no admission to outlive
    assert {d["across"] for d in decode} == {"none"}
    assert eng.windows_report() == {
        "unchained": 2 if pipeline else 5, "none": 3 if pipeline else 0,
        "finish": 0, "admission": 0}
    assert (eng.last_tokens[0, :B] == -1).all()
    ids = second + [o.token for o in got["b"]]
    assert _pool_gap(eng, params, 0, ids[:8]) < 1e-5


@pytest.mark.parametrize("prompt_len,pipeline", [
    (12, True), (13, False), (14, True), (15, False)])
def test_commit_inside_the_next_blocks_first_pass_stores_the_blocks_kv(
        tiny, prompt_len, pipeline):
    """Three windows, chained on the device or dispatched from the host's
    mirrors, that end on position 35: every block but the last was committed
    by the first pass of the block after it, inside a window and across two,
    so the pool holds at positions 0-31 the K/V of the revealed ids, what a
    forward over the whole sequence computes. The last block was never
    committed: it holds what its last pass wrote, the K/V of ids of which
    one was still MASK."""
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=1, pipeline_dispatch=pipeline)
    prompt = _ids(prompt_len, seed=prompt_len)
    got, decode = _dispatches(lambda: _run(
        eng, Request("a", prompt, max_tokens=36 - prompt_len)))
    assert [(d["chained"], d["fresh_rows"], d["across"]) for d in decode] == [
        (False, 1, "none"), (pipeline, 0, "none"), (pipeline, 0, "none")]
    ids = prompt + [o.token for o in got["a"]]
    assert len(ids) == 36
    assert _pool_gap(eng, params, 0, ids[:32]) < 1e-5
    assert _pool_gap(eng, params, 0, ids) > 1e-3


@pytest.mark.parametrize("prompt_len,max_tokens", [(3, 5), (14, 3)])
def test_request_that_ends_in_its_first_window_commits_nothing(
        tiny, prompt_len, max_tokens):
    """A prompt under one block (nothing prefilled, length 0: the first
    half of the fused pass runs at clamped positions and is stored nowhere)
    and a request that stops inside its first window: one dispatch, every
    row of it fresh, and the reference's logprobs."""
    model, params, kw, reference = tiny
    eng = _engine(model, params)
    prompt = _ids(prompt_len, seed=prompt_len)
    got, decode = _dispatches(lambda: _run(
        eng, Request("a", prompt, max_tokens=max_tokens, logprobs=3)))
    assert [(d["active"], d["fresh_rows"], d["fused_commits"])
            for d in decode] == [(1, 1, 2)]
    assert len(got["a"]) == max_tokens
    assert _gap(reference, params, kw, prompt, got["a"]) < TOL
    assert not eng.running and (eng.last_tokens[:, :B] == -1).all()


def test_low_confidence_rule_matches_the_reference_pass_by_pass():
    """`low_confidence_static`: the most confident masked position first.
    The reference reveals by its own float32 confidences, a forward a pass;
    at this seed no pass's choice is nearer than 1e-3 to the next position's
    (asserted), so the engine's order is the reference's, and the logprobs
    of the pass that revealed each position are compared."""
    model, params, kw, reference = _family("low_confidence_static")
    prompt = _ids(14, seed=14)
    outs = _run(_engine(model, params),
                Request("a", prompt, max_tokens=13, logprobs=3))["a"]
    toks, rows, order, margins = reference.generate(params, prompt, 13, kw)
    assert min(margins) > 1e-3
    assert order != sorted(order)       # not left to right
    assert [o.token for o in outs] == toks
    gap = max(abs(float(rows[i, t]) - lp)
              for i, o in enumerate(outs) for t, lp in o.top_logprobs)
    assert gap < TOL
    # the same weights left to right give other tokens: the order matters
    seq = reference.generate(params, prompt, 13, kw, "sequential")[0]
    assert seq != toks


# -- what the engine does not build for this family ---------------------------
@pytest.mark.parametrize("what", ["mesh", "lora_rank"])
def test_engine_refuses_what_is_not_built_for_block_generation(tiny, what):
    model, params, _, _ = tiny
    kw, cfg = {}, {}
    if what == "mesh":
        from ray_tpu.parallel.mesh import create_mesh

        kw["mesh"] = create_mesh({"tensor": 2}, devices=jax.devices()[:2])
    else:
        cfg["lora_rank"] = 4
    with pytest.raises(NotImplementedError,
                       match="SdarMoeModel.*" + what.split("_")[0]):
        LLMEngine(model, params, EngineConfig(max_seqs=2, **cfg), **kw)


@pytest.mark.parametrize("bad", [{"decode_steps": 6}, {"page_size": 6}])
def test_engine_wants_windows_and_pages_of_whole_blocks(tiny, bad):
    model, params, _, _ = tiny
    with pytest.raises(ValueError, match="block_length 4"):
        _engine(model, params, **bad)


def test_config_refuses_what_block_generation_cannot_mean():
    for bad in ({"block_length": 3}, {"denoising_steps": 3},
                {"remasking": "dynamic"}, {"mask_token_id": 512}):
        with pytest.raises(ValueError):
            SdarMoeConfig.tiny(**bad)


def test_parameter_names_and_dtypes_of_the_published_config():
    """HF's names, the experts of a layer as two stacks; at the published
    widths every matrix bf16, the router and the norm scales float32, and
    the parameters counted from shapes are the arithmetic's."""
    cfg = SdarMoeConfig(num_layers=6)
    shapes = jax.eval_shape(
        lambda k: SdarMoeModel(cfg).init(k, jnp.zeros((1, 4), jnp.int32)),
        jax.random.PRNGKey(0))["params"]
    layer = shapes["layers_0"]
    assert set(layer) == {"input_layernorm", "post_attention_layernorm",
                          "self_attn", "mlp"}
    assert set(layer["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj",
                                       "q_norm", "k_norm"}
    assert layer["self_attn"]["q_norm"]["scale"].shape == (128,)
    mlp = layer["mlp"]
    assert mlp["router"].shape == (2048, 128)
    assert mlp["router"].dtype == jnp.float32
    assert mlp["gate_up"].shape == (128, 2048, 1536)
    assert mlp["down"].shape == (128, 768, 2048)
    assert mlp["gate_up"].dtype == mlp["down"].dtype == jnp.bfloat16
    count = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    per_layer = (2048 * 4096 * 2 + 2048 * 512 * 2 + 2048 * 128
                 + 128 * 3 * 2048 * 768 + 2 * 2048 + 2 * 128)
    assert count == 6 * per_layer + 2 * 151_936 * 2048 + 2048
    assert 4.35e9 < count < 4.37e9


def test_init_params_makes_the_tree_flax_init_makes(tiny):
    model, params, _, _ = tiny
    whole = model.init(jax.random.PRNGKey(1),
                       jnp.zeros((1, 4), jnp.int32))["params"]
    assert jax.tree.structure(whole) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    again = model.init_params(jax.random.PRNGKey(1))
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not bool((params["layers_0"]["mlp"]["down"]
                     == params["layers_1"]["mlp"]["down"]).all())
    # every expert its own draw
    assert not bool((params["layers_0"]["mlp"]["down"][0]
                     == params["layers_0"]["mlp"]["down"][1]).all())


# -- the serving path and what the program says about itself ------------------
def test_server_serves_the_family_and_its_spans_count_the_passes():
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.server import LLMServer, load_model_and_params

    assert isinstance(load_model_and_params(
        {"family": "sdar_moe", "model": "tiny", "seed": 3})[0], SdarMoeModel)
    with pytest.raises(NotImplementedError, match="sdar_moe"):
        load_model_and_params({"family": "sdar_moe", "model": "tiny"},
                              mesh=object())
    srv = LLMServer({"family": "sdar_moe", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": 8,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 8,
                                       "prefill_buckets": (32,)}})
    try:
        before = len(fr.dump_events())
        out = srv.generate_all(_ids(10), max_tokens=7, logprobs=2)
        assert len(out["tokens"]) == 7 and len(out["top_logprobs"]) == 7
        assert srv.engine.prefix_cache is None
        cache = srv.stats()["cache"]
    finally:
        srv._running = False
    assert (cache["kv_layers"], cache["state_layers"]) == (2, 0)
    events = [e for e in fr.dump_events()[before:]
              if e.get("kind") == "span"]
    args = lambda name: [e["args"] for e in events if e["name"] == name]
    decode = args("ray_tpu.engine.dispatch_decode")
    assert decode and all(
        (d["block_length"], d["denoise_passes"], d["commit_passes"],
         d["fused_commits"], d["steps"]) == (4, 8, 0, 2, 8) for d in decode)
    # ten prompt tokens and seven more: two windows, the first fresh
    assert [d["fresh_rows"] for d in decode] == [1, 0]
    emit = [e for e in args("ray_tpu.engine.emit") if "skipped" in e]
    # ten prompt tokens: eight prefilled, two at the head of the first block
    assert sum(e["skipped"] for e in emit) == 2
    assert sum(e["tokens"] for e in emit) == 7
    # a window is 2 blocks x 4 forwards x 2 layers of 16 experts
    for e in emit:
        assert 0 < e["experts_touched"] <= 2 * 4 * 2 * 16
        assert e["experts_touched"] <= e["expert_load_max"] * 16
    first = args("ray_tpu.request.first_token")
    assert len(first) == 1 and first[0]["prompt"] == 10
    assert first[0]["nb"] == 1 and first[0]["prefill_ms"] > 0


def test_other_families_report_one_pass_a_token():
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.tiny())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, EngineConfig(
        max_seqs=2, page_size=8, max_pages_per_seq=8, decode_steps=4))
    before = len(fr.dump_events())
    _run(eng, Request("a", _ids(9), max_tokens=6))
    events = [e for e in fr.dump_events()[before:] if e.get("kind") == "span"]
    decode = [e["args"] for e in events
              if e["name"] == "ray_tpu.engine.dispatch_decode"]
    assert decode and all(
        (d["block_length"], d["denoise_passes"], d["commit_passes"],
         d["fused_commits"], d["fresh_rows"]) == (1, d["steps"], 0, 0, 0)
        and d["steps"] == 2     # half a window of four: a slot is free
        for d in decode)
    emit = [e["args"] for e in events if e["name"] == "ray_tpu.engine.emit"]
    assert emit and all(e["skipped"] == 0 and "experts_touched" not in e
                        for e in emit)
