"""Olmo-Hybrid at a tiny size on the CPU (hidden 128, 4 linear heads of
(24, 48), 4 full heads of 32, two periods, float32, seeded): the model, its
two forms of the gated delta rule against the token recurrence written out
here, and the engine's state pool against the plain reference `benchmark/references/olmo_hybrid.py` (token recurrence,
dense attention). Logprobs and not tokens: with seeded weights the largest
logit changes on rounding."""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.manifest import Manifest  # noqa: E402
from engine_sharing import reference_logprobs, share_decode_programs  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.models.olmo_hybrid import (  # noqa: E402
    LINEAR,
    OlmoHybridConfig,
    OlmoHybridModel,
)
from ray_tpu.ops import linear_attention as la  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4   # float32 on the CPU through eight layers (seen: 1e-4)


@pytest.fixture(scope="module")
def tiny():
    cfg = OlmoHybridConfig.tiny()
    model = OlmoHybridModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    params = model.init_params(jax.random.PRNGKey(1))
    kw = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    reference = Manifest(REPO).reference("olmo_hybrid")
    return model, params, kw, reference


def _ids(n, seed=2):
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 512)]


def _engine(model, params, **kw):
    """A new engine, whose decode programs are compiled once for each
    (model, config) of the module (`engine_sharing`)."""
    cfg = dict(max_seqs=2, page_size=8, max_pages_per_seq=16,
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


def _qkvgb(b, s, h=4, dk=24, dv=48, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -4.0 * jax.random.uniform(ks[3], (b, s, h))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta, state=None):
    """The gated delta rule as the paper states it, token by token: the
    oracle of both forms in `ops/linear_attention.py`. Shapes as
    `gdn_chunked`'s."""
    b, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs  # [B,H,*]
        s = s * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision="highest"))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision="highest")

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


# -- (a) the model without a cache against the reference --------------------
def test_model_matches_the_plain_reference(tiny):
    model, params, kw, reference = tiny
    ids = jnp.asarray(_ids(70), jnp.int32)   # not a multiple of the chunk
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    want = reference.logprobs(params, ids, kw)
    assert float(jnp.abs(got - want).max()) < TOL


def test_parameter_names_are_hfs(tiny):
    _, params, _, _ = tiny
    linear, full = params["layers_0"], params["layers_3"]
    assert set(linear["linear_attn"]) == {
        "q_proj", "k_proj", "v_proj", "g_proj", "a_proj", "b_proj", "o_proj",
        "conv_q", "conv_k", "conv_v", "A_log", "dt_bias", "o_norm"}
    assert set(full["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj",
                                      "q_norm", "k_norm"}
    for layer in (linear, full):
        assert {"mlp", "post_attention_layernorm",
                "post_feedforward_layernorm"} <= set(layer)
    # GatedDeltaNet's ranges: A in (0, 16], dt in [1e-3, 0.1]
    a = np.exp(np.asarray(linear["linear_attn"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(linear["linear_attn"]["dt_bias"])))
    assert (0 < a).all() and (a <= 16).all()
    assert (dt >= 9e-4).all() and (dt <= 0.101).all()


def test_published_config_is_bf16_and_counts_its_parameters():
    cfg = OlmoHybridConfig()
    assert (cfg.dtype, cfg.param_dtype) == (jnp.bfloat16, jnp.bfloat16)
    assert cfg.num_layers == 32 and cfg.conv_channels == 11520
    model = OlmoHybridModel(dataclasses.replace(
        cfg, layer_types=cfg.layer_types[:4]))
    shapes = jax.eval_shape(lambda rng: model.init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    # ISSUE 29's count from the config's keys: 215.6M a linear layer, 185.8M
    # a full one, 770.7M in embedding and head.
    assert count(shapes["layers_0"]) == 215_570_172
    assert count(shapes["layers_3"]) == 185_809_920
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) \
        == 2 * 100_352 * 3840
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(shapes["layers_0"]["mlp"]))


# -- (b) the chunkwise form against the token recurrence --------------------
@pytest.mark.parametrize("length", [1, 64, 70, 128])
def test_chunked_form_matches_the_recurrence(length):
    args = _qkvgb(2, length)
    o, s = _recurrence(*args)
    oc, sc = jax.jit(la.gdn_chunked)(*args)
    assert float(jnp.abs(o - oc).max()) < 1e-5
    assert float(jnp.abs(s - sc).max()) < 1e-5


def test_chunked_form_survives_strong_decay():
    """exp(g) far below float32's range inside one chunk: masked before the
    exponential, nothing overflows above the diagonal."""
    q, k, v, g, beta = _qkvgb(1, 128, seed=3)
    g = g * 40.0   # down to -160 a token, -10,000 over a chunk
    o, s = _recurrence(q, k, v, g, beta)
    oc, sc = la.gdn_chunked(q, k, v, g, beta)
    assert bool(jnp.isfinite(oc).all()) and bool(jnp.isfinite(sc).all())
    assert float(jnp.abs(o - oc).max()) < 5e-5
    assert float(jnp.abs(s - sc).max()) < 5e-5


def test_padded_bucket_with_garbage_changes_nothing(tiny):
    """Prefill of a bucket of 128 whose 91 padded ids are garbage: logits at
    the real positions, the state rows and the convolution tails equal those
    of the 37 real tokens alone."""
    model, params, _, _ = tiny
    eng = _engine(model, params)
    real = _ids(37)
    padded = jnp.asarray([real + _ids(91, seed=9)], jnp.int32)
    table = jnp.asarray([list(range(1, 17))], jnp.int32)
    slots = jnp.asarray([1], jnp.int32)

    def prefill(ids, n):
        mask = (jnp.arange(ids.shape[1]) < n)[None]
        return model.apply(
            {"params": params}, ids, paged_kv=eng.caches, page_table=table,
            write_mask=mask, seq_lens=jnp.asarray([n]), slots=slots)

    lp, cp = prefill(padded, 37)
    le, ce = prefill(jnp.asarray([real], jnp.int32), 37)
    # two chunks against one: float32 rounding, as against the reference
    assert float(jnp.abs(lp[0, :37] - le[0]).max()) < TOL
    whole = model.apply({"params": params}, jnp.asarray([real], jnp.int32))
    assert float(jnp.abs(le[0] - whole[0]).max()) < TOL
    for i, kind in enumerate(model.cfg.layer_types):
        if kind == LINEAR:
            for a, b in zip(cp[i], ce[i]):
                np.testing.assert_allclose(a[1], b[1], rtol=1e-4, atol=TOL)
                assert float(jnp.abs(a[0]).max()) == 0.0   # row 0 untouched
            assert float(jnp.abs(cp[i][1][1]).max()) > 0


# -- (c) through the engine: prefill, then decoding across windows ----------
def test_engine_prefill_then_decode_matches_the_reference(tiny):
    model, params, kw, reference = tiny
    eng = _engine(model, params)
    prompt = _ids(37)
    outs = _run(eng, Request("a", prompt, max_tokens=24, logprobs=5))["a"]
    assert len(outs) == 24   # 1 from prefill, 23 decode steps, 6 windows
    assert _gap(reference, params, kw, prompt, outs) < TOL


def test_engine_batch_of_unequal_prompts_matches_the_reference(tiny):
    model, params, kw, reference = tiny
    eng = _engine(model, params, max_seqs=4)
    prompts = {"a": _ids(12, 3), "b": _ids(90, 4), "c": _ids(31, 5)}
    got = _run(eng, *[Request(r, p, max_tokens=10, logprobs=5)
                      for r, p in prompts.items()])
    for rid, prompt in prompts.items():
        assert _gap(reference, params, kw, prompt, got[rid]) < TOL, rid


# -- (d) a slot's state starts from zero for the next request ---------------
def _alone(model, params, prompt, n, **kw):
    return _run(_engine(model, params, **kw),
                Request("x", prompt, max_tokens=n, logprobs=5))["x"]


def _same(outs, fresh):
    assert [o.token for o in outs] == [o.token for o in fresh]
    np.testing.assert_allclose([o.logprob for o in outs],
                               [o.logprob for o in fresh], atol=1e-5)


def test_two_requests_through_one_slot(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=1)
    first, second = _ids(40, 6), _ids(25, 7)
    got = _run(eng, Request("p", first, max_tokens=9, logprobs=5),
               Request("q", second, max_tokens=9, logprobs=5))
    assert got["p"][0].token is not None
    _same(got["q"], _alone(model, params, second, 9, max_seqs=1))


def test_request_finishing_inside_a_chained_window(tiny):
    """B ends on the second token of a window while the next window, chained
    off it on the device, still updates B's state row and pages; C then takes
    B's slot. C must read as on a fresh engine: its prefill, later on the
    device stream, overwrites the row."""
    model, params, _, _ = tiny
    eng = _engine(model, params)
    eng.add_request(Request("A", _ids(20, 8), max_tokens=40, logprobs=5))
    eng.add_request(Request("B", _ids(20, 9), max_tokens=6, logprobs=5))
    whys, seen = [], {}
    real = eng._process_window

    def spy(window, out, why="unpipelined"):
        whys.append(why)
        return real(window, out, why=why)

    eng._process_window = spy
    third = _ids(33, 10)
    while eng.has_work():
        for so in eng.step():
            seen.setdefault(so.request_id, []).append(so)
        if "C" not in seen and len(seen.get("B", [])) == 6 \
                and not any(r.request_id == "C" for r in eng.waiting):
            eng.add_request(Request("C", third, max_tokens=12, logprobs=5))
    # no window was read at once for B's finish, and none drained for C's
    # admission: B's stale row rode in the window C's prefill ran behind
    assert set(whys) <= {"chained", "all_finishing", "idle"}
    assert whys.count("chained") >= 8
    assert eng.windows_report()["finish"] + eng.windows_report()[
        "admission"] >= 2
    assert len(seen["A"]) == 40 and len(seen["C"]) == 12
    _same(seen["C"], _alone(model, params, third, 12))


# -- (e) the pool's layout, and the Pallas kernel, interpreted ---------------
# (H, dk, dv) -> heads side by side along the lanes: the published widths,
# the tiny model's, and a lane-aligned one (the layout of before PR 55)
WIDTHS = {(30, 96, 192): 2, (4, 24, 48): 2, (4, 32, 128): 1}


@pytest.mark.parametrize("dims", WIDTHS)
def test_pool_lays_heads_side_by_side_along_the_lanes(dims):
    h, dk, dv = dims
    p = WIDTHS[dims]
    assert la.state_shape(h, dk, dv) == (h // p, dk, p * dv)
    state = jax.random.normal(jax.random.PRNGKey(3), (2, h, dk, dv))
    rows = la.pack(state)
    assert rows.shape == (2, h // p, dk, p * dv)
    np.testing.assert_array_equal(la.unpack(rows, h), state)
    for head in (0, 1, h - 1):   # head p*i + j: lanes [j*dv, (j+1)*dv) of i
        i, j = divmod(head, p)
        np.testing.assert_array_equal(
            rows[:, i, :, j * dv:(j + 1) * dv], state[:, head])
    if p == 1:   # nothing to move: the array itself
        assert rows is state and la.unpack(rows, h) is rows


@pytest.mark.parametrize("h,dv,p", [
    (30, 192, 2),    # 384 lanes, none padded (6 would do as well: smallest)
    (30, 128, 1), (30, 256, 1), (8, 64, 2),
    (4, 48, 2),      # 96 of 128: as few padded as four side by side
    (7, 192, 7),     # a prime number of heads: all of them or one
    (6, 32, 3),      # 96 of 128 twice; pairs would take three tiles
    (12, 32, 4),
])
def test_heads_side_by_side_follow_from_the_shape_alone(h, dv, p):
    assert la.heads_per_lane_block(h, dv) == p


@pytest.mark.parametrize("dims", WIDTHS)
def test_gdn_decode_kernel_matches_the_recurrence_and_skips_inactive_rows(
        dims):
    h, dk, dv = dims
    widths = dict(h=h, dk=dk, dv=dv)
    q, k, v, g, beta = (x[:, 0] for x in _qkvgb(3, 1, seed=5, **widths))
    _, state = _recurrence(*_qkvgb(3, 9, seed=6, **widths))
    pool = la.pack(state)
    active = jnp.asarray([True, False, True])
    want_o, want_s = la.gdn_decode(q, k, v, g, beta, pool, active,
                                   use_kernel=False)
    one_o, one_s = _recurrence(*(x[:, None] for x in (q, k, v, g, beta)),
                               state=state)
    got_o, got_s = la.gdn_decode_kernel(q, k, v, g, beta, pool, active,
                                        interpret=True)
    assert got_s.shape == want_s.shape == pool.shape
    rows = np.asarray([0, 2])
    np.testing.assert_allclose(want_o[rows], one_o[rows, 0], atol=2e-6)
    np.testing.assert_allclose(got_o[rows], one_o[rows, 0], atol=2e-6)
    np.testing.assert_allclose(la.unpack(got_s, h)[rows], one_s[rows],
                               atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_array_equal(got_s[1], pool[1])   # bit for bit
    np.testing.assert_array_equal(want_s[1], pool[1])
    np.testing.assert_array_equal(got_o[1], 0.0)
    assert float(jnp.abs(got_s[0] - pool[0]).max()) > 1e-3


@pytest.mark.parametrize("dims,blocks", [
    # 15 pairs of [96, 384] float32, 147 KB and no lane padded: 5 a step
    ((30, 96, 192), 5),
    ((4, 24, 48), 2),
    ((4, 32, 128), 4),
    # 64 heads of [128, 128]: 16 fill the megabyte a step may hold
    ((64, 128, 128), 16),
])
def test_gdn_decode_kernel_blocks_heads_at_the_published_widths(dims,
                                                                blocks):
    assert la._blocks_per_step(*la.state_shape(*dims)) == blocks


# -- (f) what the engine builds, and refuses, for a model with state --------
def test_engine_cache_is_what_the_model_says(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=3)
    assert eng.prefix_cache is None      # whatever enable_prefix_cache says
    assert model.state_layer_ids == (0, 1, 2, 4, 5, 6)
    pages = (3 * 16 + 1, 8, 4 * 32)    # [P, ps, HK * D]
    for i, (a, b) in enumerate(eng.caches):
        if i in model.state_layer_ids:
            # four heads of [24, 48], two side by side along the lanes
            assert (a.shape, b.shape) == ((3, 3, 4 * (24 + 24 + 48)),
                                          (3, 2, 24, 96))
            assert b.dtype == jnp.float32
        else:
            assert a.shape == b.shape == pages
    # as the device lays them out: a pair's 96-wide rows fill a 128-lane
    # tile, a quarter of it padding; the tails' 384 fill three
    report = eng.cache_report
    assert report == {"kv_layers": 2, "state_layers": 6,
                      "kv_bytes": 2 * 2 * int(np.prod(pages)) * 4,
                      "state_bytes": 6 * 3 * (3 * 384 + 2 * 24 * 128) * 4,
                      "state_padding_pct": round(
                          100 * (1 - (3 * 384 + 2 * 24 * 96)
                                 / (3 * 384 + 2 * 24 * 128)), 2)}


def test_published_widths_fill_the_lanes():
    """The cell's pool from shapes alone: 16 slots of 15 pairs of
    [96, 2 * 192] float32 and no lane padded, where 30 heads of [96, 192]
    lay in 256 lanes each (24.43% of the state layers' bytes, 141.6 MB of
    the cell's twelve layers)."""
    from benchmark import sizing

    model = OlmoHybridModel(OlmoHybridConfig(layer_types=(LINEAR,) * 3
                                             + ("full_attention",)))
    ec = EngineConfig(max_seqs=16, page_size=64, max_pages_per_seq=20)
    report = {}

    def build(params):
        eng = LLMEngine(model, params, ec)
        report.update(eng.cache_report)
        return eng.caches

    caches = jax.eval_shape(build, sizing.param_shapes(model, None))
    tail, state = caches[0]
    assert (tail.shape, tail.dtype) == ((16, 3, 11520), jnp.bfloat16)
    assert (state.shape, state.dtype) == ((16, 15, 96, 384), jnp.float32)
    assert report["state_bytes"] == 3 * 16 * (3 * 11520 * 2
                                              + 30 * 96 * 192 * 4)
    assert report["state_padding_pct"] == 0.0


@pytest.mark.parametrize("what", ["mesh", "lora_rank"])
def test_engine_refuses_what_is_not_built_for_state_layers(tiny, what):
    model, params, _, _ = tiny
    kw, cfg = {}, {}
    if what == "mesh":
        from ray_tpu.parallel.mesh import create_mesh

        kw["mesh"] = create_mesh({"tensor": 2}, devices=jax.devices()[:2])
    else:
        cfg["lora_rank"] = 4
    with pytest.raises(NotImplementedError, match=what.split("_")[0]):
        LLMEngine(model, params, EngineConfig(max_seqs=2, **cfg), **kw)


def test_llama_has_no_state_layers_and_keeps_its_prefix_cache():
    # (the pages' shapes: tests/test_paged_attention.py, both families)
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    assert model.state_layer_ids == ()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, EngineConfig(max_seqs=2, page_size=8,
                                                max_pages_per_seq=16))
    assert eng.prefix_cache is not None
    assert eng.cache_report["state_layers"] == 0
    assert eng.cache_report["kv_layers"] == cfg.num_layers


# -- the loader and what the program says about the cache -------------------
def test_loader_picks_the_family_by_name():
    from ray_tpu import models
    from ray_tpu.llm._internal.server import load_model_and_params
    from ray_tpu.models.llama import LlamaModel

    model, params = load_model_and_params(
        {"family": "olmo_hybrid", "model": "tiny", "seed": 3})
    assert isinstance(model, OlmoHybridModel)
    assert "linear_attn" in params["layers_0"]
    assert isinstance(load_model_and_params({"model": "tiny"})[0], LlamaModel)
    # a name the program does not have raises with the names it has ...
    for load in (lambda: models.family("mamba"),
                 lambda: load_model_and_params({"family": "olmo-hybrid",
                                                "model": "tiny"})):
        with pytest.raises(ValueError, match="llama.*olmo_hybrid"):
            load()
    # ... but for the benchmark's harness, whose families are files of its
    # own (its tests deploy a family "other" that is the Llama block)
    assert isinstance(load_model_and_params(
        {"family": "other", "model": "tiny", "bench_root": REPO})[0],
        LlamaModel)
    assert models.sharding_rules(model) is None
    with pytest.raises(NotImplementedError, match="olmo_hybrid"):
        load_model_and_params({"family": "olmo_hybrid", "model": "tiny"},
                              mesh=object())


def test_spans_and_stats_say_what_the_cache_holds():
    import time

    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.server import LLMServer

    began = time.time()   # the ring holds other tests' engines' spans too
    srv = LLMServer({"family": "olmo_hybrid", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": 8,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 2,
                                       "prefill_buckets": (32,)}})
    try:
        out = srv.generate_all(_ids(10), max_tokens=7)
        assert len(out["tokens"]) == 7
        cache = srv.stats()["cache"]
    finally:
        srv._running = False
    assert (cache["kv_layers"], cache["state_layers"]) == (2, 6)
    assert cache["kv_bytes"] > 0 and cache["state_bytes"] > 0
    assert cache["state_padding_pct"] == 21.05   # 96 lanes of a tile's 128
    events = [e for e in fr.dump_events()
              if e.get("kind") == "span" and e["ts"] >= began]
    built = [e for e in events
             if e["name"] == "ray_tpu.engine.cache_built"][-1]
    assert built["args"] == cache
    decode = [e["args"] for e in events
              if e["name"] == "ray_tpu.engine.dispatch_decode"
              and e["args"].get("state_rows")]
    assert decode and all(d["state_rows"] == 6 * d["active"] for d in decode)
    prefill = [e["args"] for e in events
               if e["name"] == "ray_tpu.engine.prefill_dispatch"][-1]
    assert prefill["state_rows"] == 6 * prefill["nb"]


def test_init_params_makes_the_tree_flax_init_makes(tiny):
    """The family's seeded initializer (layer by layer) and flax's
    `model.init` agree on names, shapes and dtypes; the same seed gives the
    same weights twice and every layer of a kind its own."""
    model, params, _, _ = tiny
    spec = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    made = model.init_params(jax.random.PRNGKey(4))
    assert spec(made) == spec(params) == spec(jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(4)))
    again = model.init_params(jax.random.PRNGKey(4))
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(made), jax.tree.leaves(again)))
    q0, q1 = (made[f"layers_{i}"]["linear_attn"]["q_proj"]["kernel"]
              for i in (0, 1))
    assert float(jnp.abs(q0 - q1).max()) > 0.01
    big = OlmoHybridModel(OlmoHybridConfig(layer_types=("linear_attention",
                                                         "full_attention")))
    shapes = jax.eval_shape(big.init_params, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda rng: big.init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    assert spec(shapes) == spec(want)


def test_bf16_weights_have_no_common_sign():
    """A projection drawn for bf16 has no mean to speak of and more values
    than a draw made in bf16 itself (128, with a mean of -0.018 standard
    deviations: 16 layers deep the stream was one constant vector and the
    logits hardly depended on the prompt; PERF.md section 6, PR 29)."""
    from ray_tpu.models.initializers import kernel_init

    w = np.asarray(kernel_init(jax.random.PRNGKey(0), (3840, 512),
                               jnp.bfloat16).astype(jnp.float32))
    std = 3840 ** -0.5
    assert abs(w.std() / std - 1.0) < 0.01
    assert abs(w.mean()) < 4 * std / np.sqrt(w.size)
    assert np.abs(w).max() <= 2.0 * std / 0.8796 * 1.01
    assert len(np.unique(w)) > 1000


def test_self_check_runs_for_a_model_with_state_layers():
    """`LLMServer.self_check` (reachable through `OpenAIServer`): the engine
    against the model's own dense forward, which for this family is the
    chunkwise form without a cache."""
    from ray_tpu.llm._internal.server import LLMServer

    srv = LLMServer({"family": "olmo_hybrid", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": 8,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 2, "max_logprobs": 5,
                                       "prefill_buckets": (32,)}})
    try:
        rep = srv.self_check(_ids(12), steps=5)
    finally:
        srv._running = False
    assert len(rep["tokens"]) == 5 and all(rep["argmax_agrees"])
    assert rep["max_logprob_gap"] < TOL
