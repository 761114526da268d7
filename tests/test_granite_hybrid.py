"""Granite 4.0-H at a tiny size on the CPU (hidden 64, three layers: Mamba-2,
attention, Mamba-2; 8 heads of 16 with 16 states, 8 experts routed top-3 of
which a share is held, a shared expert; float32, seeded): the model and the
engine's state pool against the plain reference
`benchmark/references/granite_hybrid.py` (token recurrence, dense attention,
every held expert over every token), the Mamba-2 scan kernel in interpret
mode against the token-by-token form, and the expert layer's share against
the whole. Logprobs and not tokens: with seeded weights the largest logit
changes on rounding."""

import dataclasses
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.manifest import Manifest  # noqa: E402
from engine_sharing import reference_logprobs, share_decode_programs  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.models.granite_hybrid import (  # noqa: E402
    ATTENTION, MAMBA, ROUTER_LOGIT_STD, GraniteHybridConfig,
    GraniteHybridModel, SharedMlp)
from ray_tpu.models.layers import SparseMoe  # noqa: E402
from ray_tpu.ops import moe, ssm  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4   # float32 on the CPU through three layers (seen: 2e-6)
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


def _kw(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding experts 2-5 of its 8: a share in the middle."""
    cfg = GraniteHybridConfig.tiny(experts_held=(2, 4))
    model = GraniteHybridModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    params = model.init_params(jax.random.PRNGKey(1))
    reference = Manifest(REPO).reference("granite_hybrid")
    return model, params, _kw(cfg), reference


def _ids(n, seed=2):
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 512)]


def _engine(model, params, **kw):
    """A new engine, whose decode programs are compiled once for each
    (model, config) of the module (`engine_sharing`)."""
    cfg = dict(max_seqs=2, page_size=8, max_pages_per_seq=20,
               prefill_buckets=(32, 128), decode_steps=4, max_logprobs=5)
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
    # padded to 128 at the end, which a causal model's earlier positions do
    # not see
    ref = reference_logprobs(reference, params, kw, ids, 128)[len(prompt) - 1:]
    return max(abs(float(ref[i, t]) - lp)
               for i, o in enumerate(outs) for t, lp in o.top_logprobs)


def _scan_inputs(b, length, heads=8, width=16, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, length, heads, width))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, length, heads)) - 2.0)
    # (one group of B and C: [B, L, 1, N])
    bm = jax.random.normal(ks[2], (b, length, n))[:, :, None]
    cm = jax.random.normal(ks[3], (b, length, n))[:, :, None]
    a = -jnp.exp(jax.random.normal(ks[4], (heads,)))
    return x, dt, bm, cm, a, jnp.linspace(0.5, 1.5, heads)


# -- (a) the model without a cache against the reference --------------------
def test_model_matches_the_plain_reference(tiny):
    model, params, kw, reference = tiny
    ids = jnp.asarray(_ids(70), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    want = reference.logprobs(params, ids, kw)
    assert float(jnp.abs(got - want).max()) < TOL
    # the reference's head on some positions is its head on all, cut, and
    # its experts in blocks of positions are its experts over all
    some = reference.logprobs(params, ids[:64], kw, rows=jnp.asarray([3, 63]),
                              block=16)
    np.testing.assert_allclose(some, want[jnp.asarray([3, 63])], atol=1e-5)


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_matters(tiny, name):
    """The reference with one of Granite's four multipliers read as 1 is far
    from the program, which is within `TOL` of the reference as published."""
    model, params, kw, reference = tiny
    assert kw[name] != 1.0
    ids = jnp.asarray(_ids(40), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    wrong = reference.logprobs(params, ids, kw, one=name)
    assert float(jnp.abs(got - wrong).max()) > 20 * TOL, name


def test_layer_kinds_names_and_float32_leaves(tiny):
    model, params, _, _ = tiny
    assert model.cfg.layer_types == (MAMBA, ATTENTION, MAMBA)
    published = GraniteHybridConfig()
    assert published.layer_types.count(ATTENTION) == 4
    assert [i for i, k in enumerate(published.layer_types)
            if k == ATTENTION] == [5, 15, 25, 35]
    assert (published.d_inner, published.conv_dim) == (8192, 8448)
    mamba, attn = params["layers_0"], params["layers_1"]
    assert set(mamba["mamba"]) == {
        "in_proj", "conv1d_weight", "conv1d_bias", "dt_bias", "A_log", "D",
        "norm", "out_proj"}
    assert set(attn["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    for layer in (mamba, attn):
        assert {"block_sparse_moe", "shared_mlp", "input_layernorm",
                "post_attention_layernorm"} <= set(layer)
        assert set(layer["block_sparse_moe"]) == {"router", "gate_up", "down"}
        # the router over all 8, the stacks of the 4 held
        assert layer["block_sparse_moe"]["router"].shape == (64, 8)
        assert layer["block_sparse_moe"]["gate_up"].shape == (4, 64, 32)
        assert layer["block_sparse_moe"]["down"].shape == (4, 16, 64)
    assert set(params) == {f"layers_{i}" for i in range(3)} | {
        "embed_tokens", "norm"}     # the head is the embedding
    # HF's defaults: A = -(1..heads), D = 1, dt in [1e-3, 0.1]
    m = mamba["mamba"]
    np.testing.assert_allclose(jnp.exp(m["A_log"]), np.arange(1, 9),
                               rtol=1e-6)
    assert float(jnp.abs(m["D"] - 1.0).max()) == 0.0
    dt = jax.nn.softplus(m["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.01
    # at the published dtypes: bf16 weights, these leaves float32, and the
    # loader keeps them so (`serving_params` rounds only what the forward
    # would round)
    from ray_tpu.models import serving_params

    big = GraniteHybridModel(GraniteHybridConfig(
        layer_types=(MAMBA, ATTENTION), vocab_size=1024, experts_held=(0, 2)))
    shapes = jax.eval_shape(
        lambda rng: serving_params(big, big.init_params(rng)),
        jax.random.PRNGKey(0))
    wide = {jax.tree_util.keystr(p) for p, x in
            jax.tree_util.tree_flatten_with_path(shapes)[0]
            if x.dtype == jnp.float32}
    assert {w.split("'")[-2] for w in wide} == {
        "A_log", "D", "dt_bias", "norm", "scale", "router"}
    assert shapes["layers_0"]["mamba"]["in_proj"]["kernel"].shape == (
        4096, 8192 + 8448 + 128)


def test_the_cut_configuration_counts_its_parameters():
    """Ten layers (one period) holding 36 of 72 experts: 4.96B, as the
    family file and the configuration's file reckon it."""
    cfg = GraniteHybridConfig(
        layer_types=GraniteHybridConfig().layer_types[:10],
        experts_held=(0, 36))
    model = GraniteHybridModel(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 4_962_732_672
    assert model.state_layer_ids == (0, 1, 2, 3, 4, 6, 7, 8, 9)
    assert model.num_logits_to_keep == 1
    with pytest.raises(ValueError, match="experts_held"):
        GraniteHybridConfig(experts_held=(40, 36))


# -- (b) the scan kernel (interpret mode) against the token-by-token form ---
@pytest.mark.parametrize("length,chunk,lens", [
    (96, 32, (96, 40)),      # a row ends inside its second chunk
    (64, 64, (64, 1)),       # one chunk; a row of one token
    (128, 32, (33, 127)),    # one past a boundary, one short of the end
    (32, 128, (32, 7)),      # the chunk is cut to the bucket
])
def test_ssd_scan_kernel_matches_the_recurrence(length, chunk, lens):
    x, dt, bm, cm, a, d = _scan_inputs(2, length)
    lens = jnp.asarray(lens)
    mask = jnp.arange(length)[None] < lens[:, None]
    dt = jnp.where(mask[..., None], dt, 0.0)
    want, s_want = ssm.ssd_scan_plain(x, dt, bm, cm, a, d)
    got, s_got = ssm.ssd_scan_kernel(x, dt, bm, cm, a, d, lens, chunk=chunk,
                                     heads=4, interpret=True)
    assert s_got.shape == (2, 8, 16, 16) and s_got.dtype == jnp.float32
    np.testing.assert_allclose(s_got, s_want, atol=1e-5)
    # 1e-5 of the largest output: a decay between two positions of a chunk
    # is the exponential of a difference of float32 running sums up to 60
    at = mask[..., None, None]
    scale = float(jnp.abs(want).max())
    assert scale > 1.0
    np.testing.assert_allclose(jnp.where(at, got, 0.0) / scale,
                               jnp.where(at, want, 0.0) / scale, atol=1e-5)
    # what a skipped chunk leaves is zero, not what the buffer held
    chunk = min(chunk, length)
    walked = -(-lens // chunk) * chunk
    skipped = (jnp.arange(length)[None] >= walked[:, None])[..., None, None]
    assert float(jnp.abs(jnp.where(skipped, got, 0.0)).max()) == 0.0


def test_ssd_scan_kernel_refuses_shapes_it_cannot_block():
    x, dt, bm, cm, a, d = _scan_inputs(1, 96)
    with pytest.raises(ValueError, match="chunks of 64"):
        ssm.ssd_scan_kernel(x, dt, bm, cm, a, d, jnp.asarray([96]), chunk=64,
                            interpret=True)
    with pytest.raises(ValueError, match="blocks of 3"):
        ssm.ssd_scan_kernel(x, dt, bm, cm, a, d, jnp.asarray([96]), chunk=32,
                            heads=3, interpret=True)


def test_ssd_step_continues_a_scans_final_state():
    x, dt, bm, cm, a, d = _scan_inputs(3, 41, seed=3)
    want, s_want = ssm.ssd_scan_plain(x, dt, bm, cm, a, d)
    lens = jnp.asarray([40, 40, 40])
    _, s = ssm.ssd_scan_kernel(x[:, :40], dt[:, :40], bm[:, :40], cm[:, :40],
                               a, d, lens, chunk=8, heads=4, interpret=True)
    active = jnp.asarray([True, False, True])
    y, s_new = ssm.ssd_step(x[:, 40], dt[:, 40], bm[:, 40], cm[:, 40], a, d,
                            s, active)
    np.testing.assert_allclose(y[active], want[:, 40][active], atol=1e-5)
    np.testing.assert_allclose(s_new[active], s_want[active], atol=1e-5)
    assert bool((s_new[1] == s[1]).all())
    # the plain form from a state is the plain form over the whole
    y2, s2 = ssm.ssd_scan_plain(x[:, 40:], dt[:, 40:], bm[:, 40:], cm[:, 40:],
                                a, d, s0=s)
    np.testing.assert_allclose(y2[:, 0], want[:, 40], atol=1e-5)
    np.testing.assert_allclose(s2, s_want, atol=1e-5)


# -- (c) the expert layer's share -------------------------------------------
def _expert_layer(t=64, h=32, inter=16, e=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (t, h)),
            3.0 * jax.random.normal(ks[1], (h, e)) / np.sqrt(h),
            0.2 * jax.random.normal(ks[2], (e, h, 2 * inter)),
            0.2 * jax.random.normal(ks[3], (e, inter, h)))


def test_load_counts_the_tiles_that_share_one_read_of_an_expert():
    """Granite's routing at a small width, top-10 of 72 columns with the
    first 36 held: a 2,048-token prompt lays some 284 rows an expert on tiles
    of 128, about three tiles to a read of an expert's weights; a decode
    step's 8 rows give an expert at most 8, one tile of 16 each."""
    x, router, gate_up, down = _expert_layer(2048, e=72)
    gate_up, down = gate_up[:36], down[:36]
    _, load = moe.moe_layer(x, router, gate_up, down, 10, held=(0, 36))
    assert int(load.rows_routed) == 20480 and int(load.touched) == 36
    assert 2.5 < int(load.tiles) / int(load.touched) < 3.5
    assert int(load.tiles) >= -(-int(load.rows_held) // 128)
    _, load = moe.moe_layer(x[:8], router, gate_up, down, 10, held=(0, 36))
    assert 0 < int(load.touched) <= 36
    assert int(load.tiles) == int(load.touched)


def _layer_before_shares(x, router, gate_up, down, top_k):
    """`ops.moe.moe_layer` as it stood before it could hold a share."""
    weights, experts = moe.route(x, router, top_k)
    p = moe.plan(experts, gate_up.shape[0])
    two_i = gate_up.shape[2]
    gu = moe.gmm(jnp.take(x, p.row_token, axis=0), gate_up, p).astype(
        jnp.float32)
    act = jax.nn.silu(gu[:, :two_i // 2]) * gu[:, two_i // 2:]
    y = moe.gmm(act.astype(x.dtype), down, p)
    picked = jnp.take(y, p.dest, axis=0).astype(jnp.float32)
    return jnp.einsum("tk,tkh->th", weights, picked).astype(x.dtype)


@pytest.mark.parametrize("tokens", [64, 2 * moe.COMBINE_TOKENS])
def test_all_experts_held_is_the_layer_as_it_was_bit_for_bit(tokens):
    """Holding every expert is the layer with no share, every float. Against
    the layer as it stood before shares (float32 here): the same rows, whose
    three products a token are summed in the order of its choices since PR
    46 where the einsum took its own, so an output may differ in the last
    bits of that float32 sum and no further."""
    x, router, gate_up, down = _expert_layer(tokens)
    want = _layer_before_shares(x, router, gate_up, down, 3)
    got, load = moe.moe_layer(x, router, gate_up, down, 3)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-7)
    assert (int(load.rows_held), int(load.rows_routed)) == (3 * tokens,) * 2
    same, _ = moe.moe_layer(x, router, gate_up, down, 3, held=(0, 8))
    assert bool((same == got).all())


def test_two_shares_and_the_shared_expert_once_add_up_to_the_whole_layer(
        tiny):
    """A layer of 8 experts routed top-3 over two chips of 4: the parts the
    two shares give, with the shared expert (which each chip computes whole)
    counted once, are the uncut reference's layer output."""
    _, _, kw, reference = tiny
    whole = GraniteHybridConfig.tiny()
    routed = lambda held: SparseMoe(
        whole, num_experts=8, intermediate=whole.intermediate_size, top_k=3,
        router_std=ROUTER_LOGIT_STD, held=held)
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 24, whole.hidden_size))
    p = routed(None).init(jax.random.PRNGKey(8), u)["params"]
    shared = SharedMlp(whole).init(jax.random.PRNGKey(9), u)["params"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    flat = u.reshape(-1, whole.hidden_size)
    with jax.default_matmul_precision("highest"):
        want = (reference._moe(p, flat, {**kw, "experts_held": (0, 8)}, f32)
                + reference._shared(shared, flat, kw, f32))
    parts, rows = [], 0
    for first in (0, 4):
        mine = {"router": p["router"],
                "gate_up": p["gate_up"][first:first + 4],
                "down": p["down"][first:first + 4]}
        y, sown = routed((first, 4)).apply({"params": mine}, u,
                                           mutable=["expert_load"])
        load = moe.Load(*sown["expert_load"]["load"][0])
        assert int(load.rows_routed) == 2 * 24 * 3
        rows += int(load.rows_held)
        parts.append(y)
        # and the reference given one share is that share
        with jax.default_matmul_precision("highest"):
            one = reference._moe(mine, flat,
                                 {**kw, "experts_held": (first, 4)}, f32)
        np.testing.assert_allclose(y.reshape(one.shape), one, atol=1e-5)
    assert rows == 2 * 24 * 3        # every assignment has exactly one home
    assert 0.2 < float(jnp.abs(parts[0]).mean() / jnp.abs(want).mean()) < 5
    got = sum(parts) + SharedMlp(whole).apply({"params": shared}, u)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-5)


def test_an_assignment_to_an_absent_expert_gets_no_row_and_no_tile():
    x, router, gate_up, down = _expert_layer(40)
    _, experts = moe.route(x, router, 3)
    p = moe.plan(experts, 8, tm=16, held=(2, 4))
    here = (experts >= 2) & (experts < 6)
    assert p.sizes.shape == (4,)
    assert int(p.sizes.sum()) == int(here.sum()) < experts.size
    np.testing.assert_array_equal(
        p.sizes, [(experts == e).sum() for e in range(2, 6)])
    assert int(p.tiles_used[0]) == int(((p.sizes + 15) // 16).sum())
    # a held assignment's row holds its token, in its expert's tiles
    tok, slot = np.nonzero(np.asarray(here))
    dest = np.asarray(p.dest)[tok, slot]
    np.testing.assert_array_equal(np.asarray(p.row_token)[dest], tok)
    np.testing.assert_array_equal(
        np.asarray(p.tile_expert)[dest // 16],
        np.asarray(experts)[tok, slot] - 2)
    assert len(set(dest)) == len(dest)
    with pytest.raises(ValueError, match="4 are held"):
        moe.moe_layer(x, router, gate_up, down, 3, held=(2, 4))


def test_a_share_none_of_whose_experts_was_chosen_adds_nothing():
    x, router, gate_up, down = _expert_layer(8)
    # positive activations against negative columns: experts 0-3 come last
    x = jnp.abs(x)
    router = router.at[:, :4].set(-5.0)
    assert int((moe.route(x, router, 3)[1] < 4).sum()) == 0
    for kernel in (False, True):
        y, load = moe.moe_layer(x, router, gate_up[:4], down[:4], 3,
                                held=(0, 4), use_kernel=kernel,
                                interpret=True)
        assert float(jnp.abs(y).max()) == 0.0
        assert (int(load.touched), int(load.rows_held)) == (0, 0)


# -- (d) through the engine: prefill, then decoding across windows ----------
def test_engine_wave_of_unequal_prompts_matches_the_reference(tiny):
    """Three prompts of unequal length in one bucket (128), none a multiple
    of the scan's chunk, one wave; then 23 decode steps through the state
    pool and the paged cache."""
    model, params, kw, reference = tiny
    eng = _engine(model, params, max_seqs=4)
    prompts = {"a": _ids(37, 3), "b": _ids(90, 4), "c": _ids(101, 5)}
    got = _run(eng, *[Request(r, p, max_tokens=24, logprobs=5)
                      for r, p in prompts.items()])
    assert [k[:2] for k in eng._prefill_fns] == [(128, 3)]
    for rid, prompt in prompts.items():
        assert len(got[rid]) == 24
        assert _gap(reference, params, kw, prompt, got[rid]) < TOL, rid


def test_released_slot_starts_the_next_request_from_zero(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=1)
    first, second = _ids(40, 6), _ids(25, 7)
    got = _run(eng, Request("p", first, max_tokens=9, logprobs=5),
               Request("q", second, max_tokens=9, logprobs=5))
    fresh = _run(_engine(model, params, max_seqs=1),
                 Request("x", second, max_tokens=9, logprobs=5))["x"]
    assert [o.token for o in got["q"]] == [o.token for o in fresh]
    np.testing.assert_allclose([o.logprob for o in got["q"]],
                               [o.logprob for o in fresh], atol=1e-5)


# -- (e) what the engine builds, and refuses, for this family ---------------
def test_pool_is_float32_with_the_states_last(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=3)
    assert eng.prefix_cache is None      # whatever enable_prefix_cache says
    assert model.state_layer_ids == (0, 2)
    pages = (3 * 20 + 1, 8, 2 * 16)    # [P, ps, HK * D]
    for i, (a, b) in enumerate(eng.caches):
        if i in model.state_layer_ids:
            assert (a.shape, b.shape) == ((3, 3, 128 + 32), (3, 8, 16, 16))
            assert b.dtype == jnp.float32
        else:
            assert a.shape == b.shape == pages
    # at the published widths the 128 states fill the lanes: nothing padded
    big = GraniteHybridModel(GraniteHybridConfig())
    shapes = jax.eval_shape(
        lambda: big.init_cache(dataclasses.replace(eng.cache_cfg,
                                                   max_seqs=8)))
    tail, s = shapes[0]
    assert (tail.shape, tail.dtype) == ((8, 3, 8448), jnp.bfloat16)
    assert (s.shape, s.dtype) == ((8, 128, 64, 128), jnp.float32)
    assert shapes[5][0].shape[-1] == 8 * 128     # eight K/V heads of 128


def test_spans_carry_the_expert_load_of_prefill_and_decode():
    """`llm_config["family"]` picks the family; its one-token decode windows
    report the expert load on `emit`, its prefills on `prefill_dispatch`."""
    from ray_tpu import models
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.server import LLMServer, load_model_and_params

    began = time.time()
    srv = LLMServer({"family": "granite_hybrid", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": 8,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 2,
                                       "prefill_buckets": (32,)}})
    try:
        assert isinstance(srv.engine.model, GraniteHybridModel)
        out = srv.generate_all(_ids(10), max_tokens=5)
        assert len(out["tokens"]) == 5
        stats = srv.stats()
    finally:
        srv._running = False
    cache, summed = stats["cache"], stats["expert_load"]
    assert (cache["kv_layers"], cache["state_layers"]) == (1, 2)
    # (the ring is the process's: this server's spans are those since then)
    spans = [e for e in fr.dump_events()
             if e.get("kind") == "span" and e["ts"] >= began]
    prefill = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.prefill_dispatch"][-1]
    emits = [e["args"] for e in spans if e["name"] == "ray_tpu.engine.emit"
             and "expert_rows_routed" in e["args"]]
    # the tiny preset holds all 8: every assignment has a row here
    assert prefill["expert_rows_routed"] == 3 * 32 * 3   # layers x rows x k
    assert prefill["expert_rows_held"] == prefill["expert_rows_routed"]
    assert 3 <= prefill["experts_touched"] <= 3 * 8
    assert prefill["scan_positions"] == 2 * 32 and prefill["head_rows"] == 1
    assert emits and all(
        e["expert_rows_routed"] % (3 * 2 * 3) == 0   # layers x slots x k
        and 0 < e["expert_load_max"] <= e["expert_rows_held"]
        for e in emits)
    # tiles in use: a prefill's experts own one or more of 16 rows, a decode
    # step's 6 rows a layer one tile an expert; `stats()` sums the spans'
    # counts (and a window drained unread, which has no `emit`)
    assert prefill["experts_touched"] <= prefill["expert_tiles"] <= 3 * 12
    assert all(e["expert_tiles"] == e["experts_touched"] for e in emits)
    for name in ("experts_touched", "expert_rows_routed", "expert_tiles"):
        assert summed[name] >= prefill[name] + sum(e[name] for e in emits)
    assert summed["expert_rows_routed"] % (3 * 2 * 3) == 0
    model, _ = load_model_and_params(
        {"family": "granite_hybrid", "model": "tiny", "seed": 3})
    assert models.sharding_rules(model) is None
    with pytest.raises(NotImplementedError, match="granite_hybrid"):
        load_model_and_params({"family": "granite_hybrid", "model": "tiny"},
                              mesh=object())
    with pytest.raises(NotImplementedError, match="GraniteHybridModel"):
        model.init_cache(None, mesh=object())


def test_prefill_program_attends_a_row_at_a_time(tiny):
    """The family's prefill holds the scores of one row's queries, not of
    the wave's, and no logits of every position."""
    from benchmark import sizing

    model = tiny[0]
    ec = dict(max_seqs=4, page_size=8, max_pages_per_seq=20)
    text = sizing.lower_prefill(model, ec, 128, 3, None).as_text()
    assert "tensor<3x128x512x" not in text and "tensor<3x1x512x" in text
    # [rows, heads, queries, keys]: one row's, and never the wave's
    assert "tensor<1x4x128x160xf32>" in text
    assert "tensor<3x4x128x160xf32>" not in text


def test_init_params_makes_the_tree_flax_init_makes(tiny):
    model, params, _, _ = tiny
    spec = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    made = model.init_params(jax.random.PRNGKey(4))
    assert spec(made) == spec(params) == spec(jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(4)))
    w0, w2 = (made[f"layers_{i}"]["mamba"]["in_proj"]["kernel"]
              for i in (0, 2))
    assert float(jnp.abs(w0 - w2).max()) > 0.01
