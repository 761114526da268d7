"""Sarvam-105B's family at a tiny size on the CPU (hidden 64, the dense layer
and two expert layers, 4 heads of 24 = 16 + 8 with values of 16, a latent of
32, 16 sigmoid-routed experts top-4 beside a shared one; seeded): the model
and the engine's latent cache against the plain reference
`benchmark/references/sarvam_mla.py` (keys and values up-projected from every
position's latent, every held expert over every token), the absorbed decode
path against the published one, the latent decode kernel (interpret mode)
against a plain gather and softmax, the flash forward at keys of 192 and
values of 128 against `attention_reference`, the four chips' shares of an
expert layer against the uncut reference, the sigmoid router, and that the
softmax router and the equal-width flash forward trace to the programs they
traced to before. Logprobs and not tokens: with seeded weights the largest
logit changes on rounding."""

import dataclasses
import hashlib
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.manifest import Manifest  # noqa: E402
from engine_sharing import reference_logprobs, share_decode_programs  # noqa: E402
from ray_tpu._private import flight_recorder  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.llm._internal.paged import PagedCacheConfig  # noqa: E402
from ray_tpu.models.sarvam_mla import (LatentAttention, SarvamMlaConfig,  # noqa: E402
                                       SarvamMlaModel)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.attention import attention_reference, flash_attention  # noqa: E402
from ray_tpu.ops.paged_attention import (init_latent_pages,  # noqa: E402
                                         latent_attention, latent_write,
                                         mla_decode)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4   # float32 on the CPU through three layers (seen: 5e-6)
# bf16 weights and activations against the float32 reference on the same
# (bf16) weights, as a median and a share over a limit, not a maximum: at
# hidden 64 with 4 of 16 experts chosen, a near-tie at the fourth place that
# bf16 decides the other way swaps five eighths of a layer's routed output,
# so single positions read to 2.4 where the median of a request's hundred
# logprobs is 0.012-0.023 (three seeds). The float32 case holds the
# mathematics; this one that nothing but rounding separates the two.
MEDIAN_BF16, OVER_BF16 = 0.05, (0.25, 0.2)
PAGE = 4


def _kw(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def reference():
    return Manifest(REPO).reference("sarvam_mla")


def _tiny(**kw):
    cfg = SarvamMlaConfig.tiny(**kw)
    model = SarvamMlaModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    return model, model.init_params(jax.random.PRNGKey(1)), _kw(cfg)


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


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


def _gaps(reference, params, kw, prompt, outs):
    """The logprob gaps between an engine request's reported top tokens and
    the reference's full forward over prompt + tokens."""
    toks = [o.token for o in outs]
    ids = list(prompt) + toks[:-1]
    # padded to 80 at the end, which a causal model's earlier positions do
    # not see
    ref = reference_logprobs(reference, params, kw, ids, 80)[len(prompt) - 1:]
    return [abs(float(ref[i, t]) - lp)
            for i, o in enumerate(outs) for t, lp in o.top_logprobs]


def _gap(*args):
    return max(_gaps(*args))


# -- (a) the model without a cache against the reference --------------------
def test_model_matches_the_plain_reference(tiny, reference):
    model, params, kw = tiny
    ids = jnp.asarray(_ids(70), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    want = reference.logprobs(params, ids, kw)
    assert float(jnp.abs(got - want).max()) < TOL
    # the reference's head on some rows is its head on all, cut
    some = jnp.asarray([3, 69])
    np.testing.assert_allclose(reference.logprobs(params, ids, kw, some),
                               want[some], atol=1e-5)
    # and what the config leaves to the family's convention matters at this
    # size: the softmax scale without YaRN's factor is far from the program
    wrong = reference.logprobs(params, ids, {**kw, "yarn_mscale_all_dim": 0})
    assert float(jnp.abs(got - wrong).max()) > 20 * TOL


def test_names_dtypes_and_the_published_count():
    published = SarvamMlaConfig()
    assert published.softmax_scale == pytest.approx(0.135234, abs=1e-6)
    assert published.rope()[1] == 1.0 and published.latent_width == 576
    cut = SarvamMlaModel(dataclasses.replace(
        published, num_layers=6, experts_held=(0, 32), vocab_size=65_536))
    shapes = jax.eval_shape(cut.init_params, jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    assert count(shapes["layers_0"]) == 295_969_472
    assert count(shapes["layers_1"]) == count(shapes["layers_5"]) \
        == 925_639_488
    assert count(shapes["layers_1"]["self_attn"]) == 94_634_688
    assert count(shapes) == 5_461_041_920
    assert set(shapes) == {f"layers_{i}" for i in range(6)} | {
        "embed_tokens", "norm", "lm_head"}
    attn = shapes["layers_1"]["self_attn"]
    assert jax.tree.map(lambda v: v.shape, attn) == {
        "q_proj": (4096, 64 * 192), "kv_a_proj_with_mqa": (4096, 576),
        "kv_b_proj": (512, 64 * 256), "o_proj": (64 * 128, 4096),
        "q_norm": {"scale": (192,)}, "kv_a_layernorm": {"scale": (512,)}}
    mlp = shapes["layers_1"]["mlp"]
    assert mlp["gate_up"].shape == (32, 4096, 4096)
    assert mlp["down"].shape == (32, 2048, 4096)
    assert mlp["router"].shape == (4096, 128) and mlp["bias"].shape == (128,)
    assert set(shapes["layers_0"]["mlp"]) == {"gate_proj", "up_proj",
                                              "down_proj"}
    assert shapes["layers_0"]["mlp"]["up_proj"]["kernel"].shape == (
        4096, 16_384)
    assert shapes["layers_1"]["shared_experts"]["down_proj"][
        "kernel"].shape == (2048, 4096)
    assert cut.latent_layer_ids == tuple(range(6))
    assert cut.expert_layer_ids == (1, 2, 3, 4, 5)
    assert cut.state_layer_ids == cut.ring_layer_ids == ()
    assert cut.num_logits_to_keep == 1 and cut.latent_width == 576
    # bf16 weights; the norms' scales, the router and its bias float32
    assert attn["q_proj"].dtype == mlp["gate_up"].dtype == jnp.bfloat16
    assert {attn["q_norm"]["scale"].dtype,
            attn["kv_a_layernorm"]["scale"].dtype, mlp["router"].dtype,
            mlp["bias"].dtype} == {jnp.dtype("float32")}
    with pytest.raises(ValueError, match="experts_held"):
        SarvamMlaConfig(experts_held=(100, 32))


# -- (b) the engine through the latent cache --------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_prefill_wave_then_decode_matches_the_reference(
        reference, tiny, dtype):
    """A wave of three unequal prompts through one prefill (the published
    form over the call's own keys, the rows then written), then 20 decode
    steps each through the latent cache (the absorbed form), against the
    reference's full forward pass."""
    if dtype == "float32":
        model, params, kw = tiny
    else:
        model, params, kw = _tiny(dtype=jnp.bfloat16,
                                  param_dtype=jnp.bfloat16)
    prompts = {"a": _ids(45, 3), "b": _ids(13, 4), "c": _ids(30, 5)}
    eng = _engine(model, params)
    got = _run(eng, *(Request(r, p, max_tokens=20, logprobs=5)
                      for r, p in prompts.items()))
    assert sorted(eng._prefill_fns) == [(64, 3, False, True)]
    for r, p in prompts.items():
        assert len(got[r]) == 20
        gaps = _gaps(reference, params, kw, p, got[r])
        if dtype == "float32":
            assert max(gaps) < TOL, r
        else:
            limit, share = OVER_BF16
            assert float(np.median(gaps)) < MEDIAN_BF16, r
            assert sum(g > limit for g in gaps) < share * len(gaps), r


def test_engine_without_pipelining_and_admissions_between_windows(
        tiny, reference):
    model, params, kw = tiny
    eng = _engine(model, params, max_seqs=2, decode_steps=2)
    prompts = {"a": _ids(21, 6), "b": _ids(9, 7), "c": _ids(17, 8)}
    got = _run(eng, *(Request(r, p, max_tokens=9, logprobs=5)
                      for r, p in prompts.items()))
    for r, p in prompts.items():
        assert len(got[r]) == 9
        assert _gap(reference, params, kw, p, got[r]) < TOL, r


# -- (c) the absorbed path against the published one ------------------------
def test_absorbed_decode_is_the_published_attention_on_one_layer():
    """One attention layer: the last position's output in the published form
    over the whole sequence, against a decode step in the absorbed form over
    a pool that holds the positions before it (written by `latent_write` from
    the published call's own rows)."""
    cfg = SarvamMlaConfig.tiny()
    attn = LatentAttention(cfg)
    b, s = 2, 23
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    params = attn.init(jax.random.PRNGKey(1), x, positions)
    want, rows = attn.apply(params, x, positions)
    assert rows.shape == (b, s, cfg.latent_width)
    cache_cfg = PagedCacheConfig(num_pages=13, page_size=PAGE, max_seqs=b,
                                 max_pages_per_seq=6)
    pages = init_latent_pages(cache_cfg, cfg.latent_width, jnp.float32)
    assert pages.shape == (13, PAGE, 128)      # 40 values on whole lanes
    table = jnp.arange(12, dtype=jnp.int32).reshape(b, 6)
    before = jnp.arange(s)[None, :] < s - 1
    pages = latent_write(pages, rows, table, positions,
                         jnp.broadcast_to(before, (b, s)))
    got, pages = attn.apply(
        params, x[:, -1:], positions[:, -1:], pages,
        (table, jnp.ones((b, 1), bool), jnp.full((b,), s, jnp.int32)))
    assert float(jnp.abs(got[:, 0] - want[:, -1]).max()) \
        < 1e-5 * float(jnp.abs(want).max())
    # the step wrote its own row where the published call would have
    np.testing.assert_allclose(
        pages[table[:, (s - 1) // PAGE], (s - 1) % PAGE, :cfg.latent_width],
        rows[:, -1], atol=1e-6)
    # Two controls this comparison refuses (and the chip's check with it,
    # PERF.md section 6): the pool's rotary part left unrotated, and W_UV
    # applied to the wrong head.
    step = lambda params, pages: attn.apply(
        params, x[:, -1:], positions[:, -1:], pages,
        (table, jnp.ones((b, 1), bool), jnp.full((b,), s, jnp.int32)))[0]
    rank = cfg.kv_lora_rank
    raw = x @ params["params"]["kv_a_proj_with_mqa"]
    unrotated = latent_write(
        init_latent_pages(cache_cfg, cfg.latent_width, jnp.float32),
        jnp.concatenate([rows[..., :rank], raw[..., rank:]], -1), table,
        positions, jnp.broadcast_to(before, (b, s)))
    w = params["params"]["kv_b_proj"].reshape(rank, cfg.num_heads, -1)
    nope = cfg.qk_nope_head_dim
    wrong_head = {"params": {**params["params"], "kv_b_proj": jnp.concatenate(
        [w[..., :nope], jnp.roll(w[..., nope:], 1, axis=1)], -1).reshape(
            rank, -1)}}
    fresh = latent_write(
        init_latent_pages(cache_cfg, cfg.latent_width, jnp.float32), rows,
        table, positions, jnp.broadcast_to(before, (b, s)))
    for got in (step(params, unrotated), step(wrong_head, fresh)):
        assert float(jnp.abs(got[:, 0] - want[:, -1]).max()) \
            > 0.05 * float(jnp.abs(want).max())


# -- (d) the decode kernel ----------------------------------------------------
@pytest.mark.parametrize("lens", [(1, 7, 8), (9, 24, 17), (40, 33, 2)])
def test_mla_decode_kernel_matches_a_plain_gather_and_softmax(lens):
    """Interpret mode, pages of 8 and chunks of 2 pages: lengths of 1, a
    page less one, a page, a chunk and a row more, several chunks; heads
    that differ; a table whose pages are scattered over the pool."""
    b, h, width, rank, ps, mp = len(lens), 4, 40, 32, 8, 5
    keys = jax.random.split(jax.random.PRNGKey(sum(lens)), 3)
    pool = jnp.pad(jax.random.normal(keys[0], (b * mp + 1, ps, width)),
                   ((0, 0), (0, 0), (0, 128 - width)))
    q = jnp.pad(jax.random.normal(keys[1], (b, h, width)),
                ((0, 0), (0, 0), (0, 128 - width)))
    table = jax.random.permutation(keys[2], b * mp).reshape(b, mp).astype(
        jnp.int32)
    seq_lens = jnp.asarray(lens, jnp.int32)
    got = mla_decode(q, pool, table, seq_lens, rank, 0.3, pages_per_chunk=2,
                     interpret=True)
    rows = pool[table].reshape(b, mp * ps, 128)
    logits = 0.3 * jnp.einsum("bhw,bkw->bhk", q, rows)
    logits = jnp.where(jnp.arange(mp * ps)[None, None] < seq_lens[:, None,
                                                                  None],
                       logits, -jnp.inf)
    want = jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(logits, -1),
                      rows[..., :rank])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the gather form the CPU serves through is the same function
    np.testing.assert_allclose(
        latent_attention(q[..., :width], pool, table, seq_lens, rank, 0.3,
                         use_kernel=False), want, atol=2e-5)
    # a length past the table is clamped to it, not walked
    over = mla_decode(q, pool, table, jnp.full((b,), 10 ** 6, jnp.int32),
                      rank, 0.3, pages_per_chunk=2, interpret=True)
    full = mla_decode(q, pool, table, jnp.full((b,), mp * ps, jnp.int32),
                      rank, 0.3, pages_per_chunk=2, interpret=True)
    np.testing.assert_allclose(over, full, atol=1e-6)


# -- (e) the flash forward at a value width of its own ------------------------
@pytest.mark.parametrize("s,block", [(256, 128), (384, 128), (128, 128)])
def test_mla_flash_matches_attention_at_keys_of_192_and_values_of_128(
        s, block):
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(keys[0], (2, s, 3, 192))
    k = jax.random.normal(keys[1], (2, s, 3, 192))
    v = jax.random.normal(keys[2], (2, s, 3, 128))
    got = flash_attention(q, k, v, causal=True, scale=0.135,
                          block_q=block, block_k=block, interpret=True)
    want = attention_reference(q, k, v, causal=True, scale=0.135)
    assert got.shape == (2, s, 3, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))(q, k, v))
    assert "mla_flash" in text and "flash_fwd" not in text


# -- (f) the share -------------------------------------------------------------
def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(reference):
    """Expert parallelism's arithmetic at a small size: the routed parts the
    four chips compute (held = 0-3, 4-7, 8-11, 12-15 of 16, each from its own
    slice of the stacks, through the program's `moe_layer`) and the shared
    expert counted once add up to what the uncut reference gives for the
    whole feed-forward."""
    cfg = SarvamMlaConfig.tiny()
    kw = _kw(cfg)
    model = SarvamMlaModel(cfg)
    p = model.init_params(jax.random.PRNGKey(3))["layers_1"]
    u = jax.random.normal(jax.random.PRNGKey(4), (37, cfg.hidden_size))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = reference.routed(p["mlp"], u, kw, f32) + reference._swiglu(
            p["shared_experts"], u, f32)
        shared = reference._swiglu(p["shared_experts"], u, f32)
        parts = []
        for first in (0, 4, 8, 12):
            y, load = moe.moe_layer(
                u, p["mlp"]["router"], p["mlp"]["gate_up"][first:first + 4],
                p["mlp"]["down"][first:first + 4], cfg.num_experts_per_tok,
                held=(first, 4), scoring="sigmoid", bias=p["mlp"]["bias"],
                scale=cfg.routed_scaling_factor)
            parts.append(y)
            assert int(load.rows_routed) == 37 * 4
            # and the reference given the same share computes the same part
            np.testing.assert_allclose(
                y, reference.routed(
                    {**p["mlp"], "gate_up": p["mlp"]["gate_up"][
                        first:first + 4], "down": p["mlp"]["down"][
                            first:first + 4]},
                    u, {**kw, "experts_held": (first, 4)}, f32), atol=1e-5)
    assert float(jnp.abs(sum(parts) + shared - whole).max()) < 1e-5
    # no share is empty or the whole at this size
    assert all(1e-3 < float(jnp.abs(y).max()) < float(jnp.abs(
        whole - shared).max()) + 1.0 for y in parts)


# -- (g) the router ------------------------------------------------------------
def test_sigmoid_route_bias_moves_the_choice_and_not_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(0), (33, 48))
    router = jax.random.normal(jax.random.PRNGKey(1), (48, 128)) / 48 ** .5
    scores = jax.nn.sigmoid(x @ router)
    plain_w, plain_e = moe.route(x, router, 8, scoring="sigmoid", scale=2.5)
    top, chosen = jax.lax.top_k(scores, 8)
    assert bool((jnp.sort(plain_e, -1) == jnp.sort(chosen, -1)).all())
    np.testing.assert_allclose(jnp.sum(plain_w, -1), 2.5, atol=1e-5)
    np.testing.assert_allclose(
        jnp.sort(plain_w, -1),
        jnp.sort(2.5 * top / top.sum(-1, keepdims=True), -1), atol=1e-6)
    # a bias that lifts expert 5 above every score: every token chooses it,
    # and weighs it by its score without the bias
    bias = jnp.zeros((128,)).at[5].set(2.0)
    w, e = moe.route(x, router, 8, scoring="sigmoid", bias=bias, scale=2.5)
    assert bool((e == 5).any(-1).all())
    assert not bool((plain_e == 5).any(-1).all())
    picked = jnp.take_along_axis(scores, e, -1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, atol=1e-5)
    # a zero bias chooses as none does
    zw, ze = moe.route(x, router, 8, scoring="sigmoid",
                       bias=jnp.zeros((128,)), scale=2.5)
    assert bool((ze == plain_e).all())
    np.testing.assert_allclose(zw, plain_w, atol=1e-7)
    with pytest.raises(ValueError, match="scoring 'tanh'"):
        moe.route(x, router, 8, scoring="tanh")


# -- (h) what the other cells run is what they ran ---------------------------
def _traced(fn, *shapes):
    text = str(jax.make_jaxpr(fn)(*shapes))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_softmax_routing_and_equal_width_flash_trace_to_the_programs_they_were():
    """`sdar-decode-heavy`, `granite-prompt-heavy` and `mellum-code-context`
    route by softmax through `route` and `moe_layer`; `train-2k` and
    `mellum-code-context` run the flash forward at one width. The traced
    programs hash to what the parent commit's (5c127e8) hash to; the
    unwindowed decode kernel's hash is held by tests/test_mellum.py, whose
    function this PR does not touch. (`moe_layer`'s two hashes are PR 49's:
    its gate-and-up call writes the activation since, `route` as it was.)"""
    s = jax.ShapeDtypeStruct
    x, r = s((64, 256), jnp.bfloat16), s((256, 16), jnp.float32)
    assert _traced(lambda x, r: moe.route(x, r, 4), x, r) \
        == "0b4f203c00d90db0"
    layer = lambda held: lambda x, r, g, d: moe.moe_layer(
        x, r, g, d, 4, use_kernel=True, interpret=False, held=held)
    gu, dn = s((16, 256, 128), jnp.bfloat16), s((16, 64, 256), jnp.bfloat16)
    assert _traced(layer(None), x, r, gu, dn) == "cc55d6c72369eba0"
    gu, dn = s((8, 256, 128), jnp.bfloat16), s((8, 64, 256), jnp.bfloat16)
    assert _traced(layer((4, 8)), x, r, gu, dn) == "65311b53ab6fc472"
    q, kv = s((2, 2048, 8, 128), jnp.bfloat16), s((2, 2048, 2, 128),
                                                  jnp.bfloat16)
    fwd = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                          interpret=False)
    assert _traced(fwd, q, kv, kv) == "8c9e33acb02e3c85"


# -- (i) the engine's reading of the model -------------------------------------
def test_cache_report_tells_latent_layers_from_kv(tiny):
    model, params, _ = tiny
    eng = _engine(model, params, max_pages_per_seq=10)
    report = eng.cache_report
    assert (report["kv_layers"], report["kv_bytes"],
            report["state_layers"]) == (0, 0, 0)
    assert report["latent_layers"] == 3 and "ring_layers" not in report
    # a row's 40 values on 128 lanes of float32, as the device lays them out
    assert report["latent_bytes"] == 3 * (4 * 10 + 1) * PAGE * 128 * 4
    for pool in eng.caches:
        assert pool.shape == (4 * 10 + 1, PAGE, 128)
    # the allocator's pages grow with the context as K/V pages do
    eng.add_request(Request("r", _ids(30, 3), max_tokens=2))
    eng.step()
    assert len(eng.allocator.slot_pages[eng.running[
        next(iter(eng.running))].slot]) == 9


def test_limits_of_a_model_with_latent_layers_raise_by_name(tiny):
    model, params, _ = tiny
    cfg = EngineConfig(max_seqs=2, page_size=PAGE, max_pages_per_seq=8,
                       prefill_buckets=(32,))
    with pytest.raises(NotImplementedError, match="SarvamMlaModel has latent "
                       "layers.*LoRA"):
        LLMEngine(model, params, dataclasses.replace(cfg, lora_rank=4))
    from ray_tpu import models
    from ray_tpu.llm._internal.server import load_model_and_params

    assert models.sharding_rules(model) is None
    with pytest.raises(NotImplementedError, match="SarvamMlaModel has no "
                       "parameter sharding rules"):
        LLMEngine(model, params, cfg, mesh=object())
    with pytest.raises(NotImplementedError, match="sarvam_mla"):
        load_model_and_params({"family": "sarvam_mla", "model": "tiny"},
                              mesh=object())
    with pytest.raises(NotImplementedError, match="SarvamMlaModel"):
        model.init_cache(None, mesh=object())
    with pytest.raises(NotImplementedError, match="no LoRA banks"):
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32),
                    lora={})
    # prefix sharing is asked for by default and is off
    eng = LLMEngine(model, params, cfg)
    assert cfg.enable_prefix_cache and eng.prefix_cache is None


def test_served_by_family_name_and_spans_carry_the_latent_cache():
    """`llm_config["family"]` picks the family through the normal path
    (`LLMServer`); `cache_built` tells latent layers from K/V, a decode
    window's span says how many tokens its rows attend over, and the expert
    load rides on the one-token windows and the prefills."""
    import time

    from ray_tpu.llm._internal.server import LLMServer

    began = time.time()
    srv = LLMServer({"family": "sarvam_mla", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": PAGE,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 2,
                                       "prefill_buckets": (32,)}})
    try:
        assert isinstance(srv.engine.model, SarvamMlaModel)
        out = srv.generate_all(_ids(30, 9), max_tokens=7)
        assert len(out["tokens"]) == 7
        stats = srv.stats()
    finally:
        srv._running = False
    cache = stats["cache"]
    assert (cache["kv_layers"], cache["latent_layers"]) == (0, 3)
    spans = [e for e in flight_recorder.dump_events()
             if e.get("kind") == "span" and e["ts"] >= began]
    built = [e["args"] for e in spans
             if e["name"] == "ray_tpu.engine.cache_built"][-1]
    assert (built["latent_layers"], built["latent_bytes"]) == (
        3, cache["latent_bytes"])
    windows = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.dispatch_decode"]
    assert windows[0]["context_tokens"] == 30
    assert all("window_tokens" not in w for w in windows)
    assert [w["context_tokens"] for w in windows] == sorted(
        w["context_tokens"] for w in windows)
    prefill = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.prefill_dispatch"][-1]
    assert prefill["tokens"] == 30 and prefill["head_rows"] == 1
    # two expert layers x 32 rows x top-4; every expert is held at this size
    assert prefill["expert_rows_routed"] == 2 * 32 * 4
    emits = [e["args"] for e in spans if e["name"] == "ray_tpu.engine.emit"
             and "expert_rows_routed" in e["args"]]
    assert emits and all(e["expert_rows_held"] == e["expert_rows_routed"]
                         for e in emits)
    assert stats["expert_load"]["expert_rows_routed"] > 0
