"""Mellum 2 at a tiny size on the CPU (hidden 64, one period: three
sliding-window layers with a window of 8 and one full layer under YaRN; 16
experts routed top-4; float32, seeded): the model and the engine's rings of
pages against the plain reference `benchmark/references/mellum.py` (dense
attention under the band mask, every expert over every token), the windowed
decode kernel (interpret mode) against the gather form, the banded flash
forward against `attention_reference`, the rotary frequencies against their
equations, and that the causal flash kernels and the unwindowed decode
kernel trace to the programs they traced to before. Logprobs and not tokens:
with seeded weights the largest logit changes on rounding."""

import dataclasses
import hashlib
import math
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.manifest import Manifest  # noqa: E402
from engine_sharing import (cell_at_depth, decode_call,  # noqa: E402
                            prefill_call, reference_logprobs,
                            share_decode_programs)
from ray_tpu._private import flight_recorder  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.llm._internal.paged import PagedCacheConfig  # noqa: E402
from ray_tpu.models.layers import apply_rope, rope_freqs  # noqa: E402
from ray_tpu.models.mellum import (FULL, SLIDING, MellumConfig,  # noqa: E402
                                   MellumModel)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.attention import (attention_reference, flash_attention,  # noqa: E402
                                   sliding_window_attention)
from ray_tpu.ops.paged_attention import (init_ring_pages,  # noqa: E402
                                         paged_attention_decode_kernel,
                                         ring_attention, ring_pages,
                                         ring_table, ring_write)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4   # float32 on the CPU through four layers (seen: 5e-6)
WINDOW, PAGE = 8, 4   # the tiny model's window; the engine's pages


def _kw(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def tiny():
    cfg = MellumConfig.tiny()
    model = MellumModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    params = model.init_params(jax.random.PRNGKey(1))
    return model, params, _kw(cfg), Manifest(REPO).reference("mellum")


def _ids(n, seed=2):
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 512)]


def _engine(model, params, **kw):
    cfg = dict(max_seqs=4, page_size=PAGE, max_pages_per_seq=20,
               prefill_buckets=(64,), decode_steps=4, max_logprobs=5)
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
    and the reference's full forward over prompt + tokens."""
    toks = [o.token for o in outs]
    ids = list(prompt) + toks[:-1]
    # padded to 80 at the end, which a causal model's earlier positions do
    # not see
    ref = reference_logprobs(reference, params, kw, ids, 80)[len(prompt) - 1:]
    return max(abs(float(ref[i, t]) - lp)
               for i, o in enumerate(outs) for t, lp in o.top_logprobs)


# -- (a) the model without a cache against the reference --------------------
def test_model_matches_the_plain_reference(tiny):
    model, params, kw, reference = tiny
    assert model.cfg.layer_types == (SLIDING,) * 3 + (FULL,)
    ids = jnp.asarray(_ids(70), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    want = reference.logprobs(params, ids, kw)
    assert float(jnp.abs(got - want).max()) < TOL
    # the reference's head on some rows is its head on all, cut
    some = jnp.asarray([3, 69])
    np.testing.assert_allclose(reference.logprobs(params, ids, kw, some),
                               want[some], atol=1e-5)
    # and the window matters at these lengths: every layer read as a full
    # one is far from the program
    wrong = reference.logprobs(params, ids, {**kw, "sliding_window": 512})
    assert float(jnp.abs(got - wrong).max()) > 20 * TOL


def test_names_dtypes_and_the_published_count():
    published = MellumConfig()
    assert published.layer_types.count(FULL) == 7
    assert [i for i, k in enumerate(published.layer_types)
            if k == FULL] == [3, 7, 11, 15, 19, 23, 27]
    cut = MellumModel(dataclasses.replace(
        published, layer_types=published.layer_types[:8]))
    shapes = jax.eval_shape(cut.init_params, jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    assert count(shapes["layers_0"]) == count(shapes["layers_3"]) \
        == 417_747_712
    assert count(shapes) == 3_794_968_832
    assert set(shapes) == {f"layers_{i}" for i in range(8)} | {
        "embed_tokens", "norm", "lm_head"}
    layer = shapes["layers_0"]
    assert set(layer["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj",
                                       "q_norm", "k_norm"}
    assert layer["self_attn"]["q_norm"]["scale"].shape == (128,)
    assert layer["mlp"]["gate_up"].shape == (64, 2304, 1792)
    assert layer["mlp"]["down"].shape == (64, 896, 2304)
    assert cut.ring_layer_ids == (0, 1, 2, 4, 5, 6)
    assert cut.state_layer_ids == () and cut.num_logits_to_keep == 1
    assert cut.expert_layer_ids == tuple(range(8))
    # bf16 weights; the norms' scales and the router float32, and the loader
    # keeps them so
    from ray_tpu.models import serving_params

    small = MellumModel(dataclasses.replace(
        published, layer_types=(SLIDING, FULL), vocab_size=1024,
        num_experts=8))
    held = jax.eval_shape(
        lambda rng: serving_params(small, small.init_params(rng)),
        jax.random.PRNGKey(0))
    wide = {jax.tree_util.keystr(p).split("'")[-2] for p, x in
            jax.tree_util.tree_flatten_with_path(held)[0]
            if x.dtype == jnp.float32}
    assert wide == {"scale", "router"}
    with pytest.raises(ValueError, match="layer_types"):
        MellumConfig(layer_types=("attention",))


# -- (b) rotary frequencies by layer kind -----------------------------------
def test_yarn_frequencies_at_the_published_numbers(tiny):
    """`rope_parameters.full_attention` (factor 16 over 8,192, beta 32 and 1,
    base 500000, heads of 128): the correction range is pairs 18 to 35, below
    it the plain frequency, above it a sixteenth, a linear ramp between."""
    corr = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (
        2 * math.log(500000))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (18, 35)
    j = np.arange(64, dtype=np.float64)
    plain = 500000.0 ** (-2 * j / 128)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    want = plain / 16 * ramp + plain * (1 - ramp)
    cfg = MellumConfig()
    freqs, factor = cfg.rope(FULL)
    np.testing.assert_allclose(freqs, want, rtol=1e-5)
    np.testing.assert_allclose(freqs[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(freqs[35:], plain[35:] / 16, rtol=1e-6)
    assert factor == 1.2772588722239782
    assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
    sliding, one = cfg.rope(SLIDING)
    np.testing.assert_allclose(sliding, plain, rtol=1e-6)
    assert one == 1.0
    # the reference works them out on its own
    reference = tiny[3]
    ours, f = reference.inv_freq(_kw(cfg), FULL)
    np.testing.assert_allclose(ours, want, rtol=1e-5)
    assert f == factor


def test_rope_of_the_other_families_is_what_it_was():
    """`apply_rope(x, positions, theta)` as Mistral and SDAR call it: the
    arrays the formula before this family gave, bit for bit; the factor
    multiplies cos and sin, so a rotated vector's norm."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 32))
    pos = jnp.arange(9)[None] + jnp.asarray([[0], [40]])
    freqs = 1.0 / (1e6 ** (jnp.arange(0, 32, 2, dtype=jnp.float32) / 32))
    assert bool((rope_freqs(32, 1e6) == freqs).all())
    angles = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    assert bool((apply_rope(x, pos, 1e6) == want).all())
    scaled = apply_rope(x, pos, 1e6, freqs, 1.25)
    np.testing.assert_allclose(scaled, 1.25 * want, rtol=1e-5, atol=1e-6)


# -- (c) the banded flash forward -------------------------------------------
@pytest.mark.parametrize("s,window,block", [
    (64, 8, 16),     # a band inside one block and its left neighbour
    (64, 24, 8),     # a band of four blocks
    (96, 32, 32),    # the window a whole block: two blocks a q block
    (40, 8, 512),    # the block cut to the sequence: one block, all masks
    (64, 100, 16),   # a window past the sequence: plain causal
])
def test_swa_flash_matches_attention_under_the_band_mask(s, window, block):
    ks = jax.random.split(jax.random.PRNGKey(s + window), 3)
    q = jax.random.normal(ks[0], (2, s, 4, 16))
    k = jax.random.normal(ks[1], (2, s, 2, 16))
    v = jax.random.normal(ks[2], (2, s, 2, 16))
    want = attention_reference(q, k, v, causal=True, window=window)
    got = sliding_window_attention(q, k, v, window=window, block=block,
                                   interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if window < s:
        full = attention_reference(q, k, v, causal=True)
        assert float(jnp.abs(full - want).max()) > 1e-2


# -- (d) rings of pages ------------------------------------------------------
def _ring(slots=3, heads=2, width=16):
    cc = PagedCacheConfig(num_pages=1, page_size=PAGE, max_seqs=slots)
    return init_ring_pages(cc, WINDOW, heads, width, jnp.float32)


def test_a_ring_never_holds_a_position_older_than_its_pages():
    """K whose every channel is its position + 1, written by a prefill of
    unequal rows and then token by token: each cell of a slot's ring holds
    the newest position of its residue, none older than R pages, and every
    position of the window is there."""
    r = ring_pages(WINDOW, PAGE)
    assert r == 3
    k_pages, _ = _ring()
    assert k_pages.shape == (3 * r, PAGE, 32)
    slots = jnp.asarray([2, 0], jnp.int32)
    lens = jnp.asarray([45, 6], jnp.int32)      # five windows; under one
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    stamp = lambda p: jnp.broadcast_to(
        (p + 1.0)[..., None, None], p.shape + (2, 16))
    k_pages = ring_write(k_pages, stamp(pos), slots, pos,
                         pos < lens[:, None], lens, WINDOW)
    all_slots = jnp.arange(3, dtype=jnp.int32)
    held = np.zeros(3, int)
    held[np.asarray(slots)] = np.asarray(lens)
    for step in range(14):                      # decode: one token a row
        for slot in range(3):
            cells = np.asarray(k_pages).reshape(3, r * PAGE, 32)[slot, :, 0]
            have = sorted(int(c) - 1 for c in cells if c > 0)
            n = held[slot]
            assert all(n - p <= r * PAGE for p in have), (slot, n, have)
            want = list(range(max(0, n - WINDOW), n))
            assert set(want) <= set(have)
            # the newest of each residue: nothing twice
            assert len(have) == len(set(p % (r * PAGE) for p in have))
        active = jnp.asarray([True, False, True])   # slot 1 never used
        at = jnp.asarray(held, jnp.int32)[:, None]
        k_pages = ring_write(k_pages, stamp(at), all_slots, at,
                             active[:, None], at[:, 0] + 1, WINDOW)
        held = held + np.asarray(active)
    assert float(jnp.abs(
        k_pages.reshape(3, -1)[1]).max()) == 0.0    # the unused slot's ring


@pytest.mark.parametrize("lens", [
    (1, 3, 4, 5),         # inside the first pages; a page's edge
    (7, 8, 9, 12),        # the window's edge: 8 keys, then the first drops
    (13, 16, 17, 45),     # the ring wraps; far past it
    (0, 0, 31, 32),       # rows that hold nothing walk nothing
])
def test_swa_decode_kernel_matches_the_gather_form_and_dense_attention(lens):
    """The decode step of a sliding layer over rings that a prefill wrote:
    the interpreted kernel (the window's pages walked from len - window),
    the gather form (every ring cell's position worked out from the length)
    and dense attention of the last query inside the band, all three."""
    ks = jax.random.split(jax.random.PRNGKey(sum(lens)), 3)
    b, s = 4, 48
    q = jax.random.normal(ks[0], (b, s, 4, 16))
    k = jax.random.normal(ks[1], (b, s, 2, 16))
    v = jax.random.normal(ks[2], (b, s, 2, 16))
    k_pages, v_pages = _ring(slots=4)
    slots = jnp.asarray([3, 1, 0, 2], jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = pos < lens[:, None]
    k_pages = ring_write(k_pages, k, slots, pos, mask, lens, WINDOW)
    v_pages = ring_write(v_pages, v, slots, pos, mask, lens, WINDOW)
    last = jnp.maximum(lens - 1, 0)
    q_last = jnp.take_along_axis(q, last[:, None, None, None], axis=1)
    gathered = ring_attention(q_last, k_pages, v_pages, slots, lens, WINDOW,
                              use_kernel=False)
    kernel = paged_attention_decode_kernel(
        q_last, k_pages, v_pages, ring_table(slots, ring_pages(WINDOW, PAGE)),
        lens, window=WINDOW, interpret=True, pages_per_chunk=2)
    dense = attention_reference(q, k, v, causal=True, window=WINDOW)
    want = jnp.take_along_axis(dense, last[:, None, None, None], axis=1)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(gathered[live], want[live], atol=2e-5)
    np.testing.assert_allclose(kernel[live], want[live], atol=2e-5)
    assert bool(jnp.isfinite(kernel).all())
    with pytest.raises(NotImplementedError, match="one query"):
        paged_attention_decode_kernel(
            jnp.concatenate([q_last, q_last], 1), k_pages, v_pages,
            ring_table(slots, 3), lens, window=WINDOW, interpret=True)


def test_ring_pages_refuse_a_window_that_is_not_whole_pages():
    cc = PagedCacheConfig(num_pages=1, page_size=3, max_seqs=2)
    with pytest.raises(ValueError, match="multiple of page_size"):
        init_ring_pages(cc, WINDOW, 2, 16)


# -- (e) the engine: rings beside pages --------------------------------------
def test_engine_prefill_wave_then_decode_matches_the_reference(tiny):
    """A wave of three unequal prompts (five windows and a half, three, under
    one) through one prefill, then 20 decode steps each: the rings wrap in
    the prefill (45 tokens into 12 cells) and in decode; the top-five
    logprobs at every generated position against the reference's forward
    over the whole sequence."""
    model, params, kw, reference = tiny
    eng = _engine(model, params)
    assert eng.prefix_cache is None
    prompts = {"long": _ids(45, 3), "mid": _ids(23, 4), "short": _ids(6, 5)}
    got = _run(eng, *(Request(rid, p, max_tokens=20, logprobs=5)
                      for rid, p in prompts.items()))
    assert [k[:2] for k in eng._prefill_fns] == [(64, 3)]
    for rid, prompt in prompts.items():
        assert len(got[rid]) == 20
        assert _gap(reference, params, kw, prompt, got[rid]) < TOL, rid
    # a slot is used again without anything done for its ring: the next
    # prefill overwrites what its decode steps read
    again = _run(eng, Request("again", prompts["mid"], max_tokens=9,
                              logprobs=5))
    assert _gap(reference, params, kw, prompts["mid"], again["again"]) < TOL
    assert [o.token for o in again["again"]] == [
        o.token for o in got["mid"][:9]]


def test_engine_without_pipelining_and_admissions_between_windows(tiny):
    """The same requests arriving one window apart, unpipelined: a prefill
    beside rows that are decoding leaves their rings alone."""
    model, params, kw, reference = tiny
    eng = _engine(model, params, pipeline_dispatch=False, max_seqs=2)
    prompts = {"a": _ids(30, 6), "b": _ids(41, 7), "c": _ids(9, 8)}
    got = {}
    pending = list(prompts.items())
    for _ in range(400):
        if pending:
            rid, p = pending.pop(0)
            eng.add_request(Request(rid, p, max_tokens=12, logprobs=5))
        if not eng.has_work():
            break
        for so in eng.step():
            got.setdefault(so.request_id, []).append(so)
    assert not eng.has_work()
    for rid, prompt in prompts.items():
        assert _gap(reference, params, kw, prompt, got[rid]) < TOL, rid


def test_cache_report_tells_rings_from_pages_and_rings_do_not_grow(tiny):
    model, params, _, _ = tiny
    r = ring_pages(WINDOW, PAGE)
    reports = {}
    for mp in (20, 40):
        eng = _engine(model, params, max_pages_per_seq=mp)
        reports[mp] = eng.cache_report
        ring, pages = eng.caches[0][0], eng.caches[3][0]
        assert ring.shape == (4 * r, PAGE, 2 * 32)
        assert pages.shape == (4 * mp + 1, PAGE, 2 * 32)
    lanes = 128   # 64 lanes of K/V a token fill a whole 128-lane tile
    assert reports[20]["ring_bytes"] == reports[40]["ring_bytes"] \
        == 3 * 2 * 4 * r * PAGE * lanes * 4
    assert (reports[20]["kv_layers"], reports[20]["ring_layers"],
            reports[20]["state_layers"]) == (1, 3, 0)
    assert reports[40]["kv_bytes"] > 1.9 * reports[20]["kv_bytes"]
    # the allocator's pages are the full layer's alone: a 45-token prompt
    # and the room for its first window hold 13 pages of 4, whatever the
    # rings keep
    eng = _engine(model, params)
    eng.add_request(Request("r", _ids(45, 3), max_tokens=2))
    eng.step()
    assert len(eng.allocator.slot_pages[eng.running[
        next(iter(eng.running))].slot]) == 13
    assert eng.allocator.num_free == 4 * 20 + 1 - 13


def test_limits_of_a_model_with_rings_raise_by_name(tiny):
    model, params, _, _ = tiny
    cfg = EngineConfig(max_seqs=2, page_size=PAGE, max_pages_per_seq=8,
                       prefill_buckets=(32,))
    with pytest.raises(NotImplementedError, match="MellumModel has ring "
                       "layers.*LoRA"):
        LLMEngine(model, params, dataclasses.replace(cfg, lora_rank=4))
    from ray_tpu import models
    from ray_tpu.llm._internal.server import load_model_and_params

    assert models.sharding_rules(model) is None
    with pytest.raises(NotImplementedError, match="MellumModel has no "
                       "parameter sharding rules"):
        LLMEngine(model, params, cfg, mesh=object())
    with pytest.raises(NotImplementedError, match="mellum"):
        load_model_and_params({"family": "mellum", "model": "tiny"},
                              mesh=object())
    with pytest.raises(NotImplementedError, match="MellumModel"):
        model.init_cache(None, mesh=object())
    with pytest.raises(ValueError, match="sliding_window 8 is not a "
                       "multiple of page_size 16"):
        LLMEngine(model, params, dataclasses.replace(cfg, page_size=16))
    with pytest.raises(NotImplementedError, match="no LoRA banks"):
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                    lora={})
    # prefix sharing is asked for by default and is off
    eng = LLMEngine(model, params, cfg)
    assert cfg.enable_prefix_cache and eng.prefix_cache is None


def test_served_by_family_name_and_spans_carry_rings_and_windows():
    """`llm_config["family"]` picks the family through the normal path
    (`LLMServer`); `cache_built` tells rings from pages, a decode window's
    span says what its rows attend over, and the expert load rides on the
    one-token windows and the prefills."""
    import time

    from ray_tpu.llm._internal.server import LLMServer

    began = time.time()
    srv = LLMServer({"family": "mellum", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": PAGE,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 2,
                                       "prefill_buckets": (32,)}})
    try:
        assert isinstance(srv.engine.model, MellumModel)
        out = srv.generate_all(_ids(30, 9), max_tokens=7)
        assert len(out["tokens"]) == 7
        stats = srv.stats()
    finally:
        srv._running = False
    cache = stats["cache"]
    assert (cache["kv_layers"], cache["ring_layers"]) == (1, 3)
    spans = [e for e in flight_recorder.dump_events()
             if e.get("kind") == "span" and e["ts"] >= began]
    built = [e["args"] for e in spans
             if e["name"] == "ray_tpu.engine.cache_built"][-1]
    assert (built["ring_layers"], built["ring_bytes"]) == (
        3, cache["ring_bytes"])
    windows = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.dispatch_decode"]
    assert (windows[0]["context_tokens"], windows[0]["window_tokens"]) == (
        30, WINDOW)
    assert all(w["window_tokens"] == WINDOW < w["context_tokens"]
               for w in windows)
    prefill = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.prefill_dispatch"][-1]
    assert prefill["tokens"] == 30 and prefill["head_rows"] == 1
    assert prefill["expert_rows_routed"] == 4 * 32 * 4   # layers x rows x k
    emits = [e["args"] for e in spans if e["name"] == "ray_tpu.engine.emit"
             and "expert_rows_routed" in e["args"]]
    assert emits and all(e["expert_rows_held"] == e["expert_rows_routed"]
                         for e in emits)
    assert stats["expert_load"]["expert_rows_routed"] > 0


# -- (f) the experts ---------------------------------------------------------
def test_top_8_of_64_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(0), (33, 48))
    router = 4.0 * jax.random.normal(jax.random.PRNGKey(1), (48, 64)) / 48 ** .5
    weights, experts = moe.route(x, router, 8)
    assert weights.shape == experts.shape == (33, 8)
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, chosen = jax.lax.top_k(probs, 8)
    assert bool((jnp.sort(experts, -1) == jnp.sort(chosen, -1)).all())
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        jnp.sort(weights, -1),
        jnp.sort(top / top.sum(-1, keepdims=True), -1), atol=1e-6)


# -- (g) what the other cells run is what they ran ---------------------------
def _traced(fn, *shapes):
    text = str(jax.make_jaxpr(fn)(*shapes))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_causal_flash_and_unwindowed_decode_trace_to_the_programs_they_were():
    """`train-2k` is bound at 1% and runs `flash_attention(causal=True)`
    forward and backward; every serving cell runs `paged_decode`. The traced
    programs (the kernels' bodies, grids, block maps and names are in the
    jaxpr's text) hash to what the parent commit's hash to. The lowered
    StableHLO is not hashed: its Mosaic payload carries source line numbers,
    so it changes whenever a line of the file moves."""
    s = jax.ShapeDtypeStruct
    q, kv = s((2, 2048, 8, 128), jnp.bfloat16), s((2, 2048, 2, 128),
                                                  jnp.bfloat16)
    fwd = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                          interpret=False)
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    assert _traced(fwd, q, kv, kv) == "8c9e33acb02e3c85"
    # (the forward rule names its output and row sums for a remat policy
    # since the PR that lets `train-2k`'s layers keep them: the kernels'
    # calls are the ones "d5fcbc3d1d5754d0" held, two `name`s beside them)
    assert _traced(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) \
        == "e8441c57bd170860"
    decode = lambda q, k, v, t, n: paged_attention_decode_kernel(
        q, k, v, t, n, interpret=False)
    pages = s((65, 64, 512), jnp.bfloat16)
    args = (s((8, 1, 32, 128), jnp.bfloat16), pages, pages,
            s((8, 8), jnp.int32), s((8,), jnp.int32))
    assert _traced(decode, *args) == "8f88077814049cc1"
    windowed = lambda q, k, v, t, n: paged_attention_decode_kernel(
        q, k, v, t, n, interpret=False, window=1024)
    text = str(jax.make_jaxpr(windowed)(*args))
    assert "swa_decode" in text and "paged_decode" not in text


@pytest.mark.parametrize("cell,layers,prefill,traced", [
    ("decode-heavy", 2, None, "47958ad5751c0dcc"),
    ("decode-heavy", 2, (128, 16), "9632b2247d55bd12"),
    ("mellum-code-context", 4, (4096, 1), "432f400032fe8cc0"),
])
def test_serving_programs_trace_to_the_programs_they_were(
        monkeypatch, cell, layers, prefill, traced):
    """The engine's own programs at the cells' widths (Mistral at two layers,
    Mellum at one period), traced as on a TPU: the train step's remat
    policy, the names it keeps and the loss's new form are behind
    `paged_kv is None` and the flash kernel's differentiation rule, which
    no serving program reaches. Mistral's decode window and its wave of
    128-token prompts, and Mellum's prefill of one 4,096-token prompt (the
    one serving user of `flash_attention`), hash to what they hashed to at
    the parent of the PR that brought the policy (6092ef5)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth(cell, layers)
    fn, args = (decode_call(model, ec) if prefill is None else
                prefill_call(model, ec, *prefill))    # (bucket, prompts)
    text = str(fn.trace(*args).jaxpr)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == traced
