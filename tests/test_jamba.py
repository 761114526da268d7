"""Jamba at a tiny size on the CPU (hidden 64, d_inner 128, d_state 16,
dt_rank 8, a period of four with one attention layer of 5 query heads on 1
K/V head, float32, seeded): the model and the engine's state pool
against the plain reference `benchmark/references/jamba.py` (token
recurrence, dense attention), and the scan kernel in interpret mode against
the token-by-token form. Logprobs and not tokens: with seeded weights the
largest logit changes on rounding."""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.manifest import Manifest  # noqa: E402
from engine_sharing import reference_logprobs, share_decode_programs  # noqa: E402
from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request  # noqa: E402
from ray_tpu.models.jamba import MAMBA, JambaConfig, JambaModel  # noqa: E402
from ray_tpu.ops import ssm  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4   # float32 on the CPU through four layers (seen: 1e-5)


@pytest.fixture(scope="module")
def tiny():
    cfg = JambaConfig.tiny()
    model = JambaModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    params = model.init_params(jax.random.PRNGKey(1))
    kw = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    reference = Manifest(REPO).reference("jamba")
    return model, params, kw, reference


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


def _scan_inputs(b, length, d=256, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, length, d))
    z = jax.random.normal(ks[1], (b, length, d))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, length, d)) - 2.0)
    bm = jax.random.normal(ks[3], (b, length, n))
    cm = jax.random.normal(ks[4], (b, length, n))
    a = -jnp.exp(jax.random.normal(ks[5], (n, d)))
    return x, dt, bm, cm, z, a, jnp.ones((d,))


# -- (a) the model without a cache against the reference --------------------
def test_model_matches_the_plain_reference(tiny):
    model, params, kw, reference = tiny
    ids = jnp.asarray(_ids(70), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    want = reference.logprobs(params, ids, kw)
    assert float(jnp.abs(got - want).max()) < TOL
    # the reference's head on some positions is its head on all, cut
    some = reference.logprobs(params, ids, kw, rows=jnp.asarray([3, 69]))
    np.testing.assert_allclose(some, want[jnp.asarray([3, 69])], atol=1e-6)


def test_layer_kinds_names_and_float32_leaves(tiny):
    model, params, _, _ = tiny
    assert model.cfg.layer_kinds == (MAMBA, MAMBA, "attention", MAMBA)
    assert JambaConfig().layer_kinds.count("attention") == 2
    assert [i for i, k in enumerate(JambaConfig().layer_kinds)
            if k == "attention"] == [7, 21]
    mamba, attn = params["layers_0"], params["layers_2"]
    assert set(mamba["mamba"]) == {
        "in_proj", "conv1d_weight", "conv1d_bias", "x_proj", "dt_proj",
        "dt_bias", "A_log", "D", "out_proj", "dt_layernorm", "b_layernorm",
        "c_layernorm"}
    assert set(attn["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    for layer in (mamba, attn):
        assert {"feed_forward", "input_layernorm",
                "pre_ff_layernorm"} <= set(layer)
    assert set(params) == {f"layers_{i}" for i in range(4)} | {
        "embed_tokens", "final_layernorm"}     # the head is the embedding
    # Mamba's defaults: A = -(1..16) a channel, D = 1, dt in [1e-3, 0.1]
    m = mamba["mamba"]
    np.testing.assert_allclose(jnp.exp(m["A_log"][:, 5]),
                               np.arange(1, 17), rtol=1e-6)
    assert float(jnp.abs(m["D"] - 1.0).max()) == 0.0
    dt = jax.nn.softplus(m["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.01
    assert float(jnp.abs(m["conv1d_weight"]).max()) <= 0.5
    # at the published dtypes: bf16 weights, these leaves float32, and the
    # loader keeps them so (`serving_params` rounds only what the forward
    # would round)
    from ray_tpu.models import serving_params

    big = JambaModel(JambaConfig(num_layers=2, attn_layer_period=2,
                                 attn_layer_offset=1, vocab_size=1024))
    shapes = jax.eval_shape(
        lambda rng: serving_params(big, big.init_params(rng)),
        jax.random.PRNGKey(0))
    wide = {jax.tree_util.keystr(p) for p, x in
            jax.tree_util.tree_flatten_with_path(shapes)[0]
            if x.dtype == jnp.float32}
    assert {w.split("'")[-2] for w in wide} == {"A_log", "D", "dt_bias",
                                                "scale"}
    assert shapes["layers_0"]["mamba"]["A_log"].shape == (16, 5120)


def test_published_config_counts_its_parameters():
    model = JambaModel(JambaConfig())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 3_029_337_472            # 3.03B, the head tied
    assert model.state_layer_ids == tuple(
        i for i in range(28) if i not in (7, 21))
    assert model.num_logits_to_keep == 1


# -- (b) the scan kernel (interpret mode) against the token-by-token form ---
@pytest.mark.parametrize("length,chunk,lens", [
    (96, 32, (96, 40)),      # a row ends inside its second chunk
    (64, 64, (64, 1)),       # one chunk; a row of one token
    (128, 32, (33, 127)),    # one past a boundary, one short of the end
    (32, 128, (32, 7)),      # the chunk is cut to the bucket
])
def test_scan_kernel_matches_the_recurrence(length, chunk, lens):
    x, dt, bm, cm, z, a, d = _scan_inputs(2, length)
    lens = jnp.asarray(lens)
    mask = (jnp.arange(length)[None] < lens[:, None])[..., None]
    dt = jnp.where(mask, dt, 0.0)
    x = jnp.where(mask, x, 0.0)
    want, h_want = ssm.ssm_scan_plain(x, dt, bm, cm, z, a, d)
    got, h_got = ssm.ssm_scan_kernel(x, dt, bm, cm, z, a, d, lens,
                                     chunk=chunk, channels=128,
                                     interpret=True)
    assert h_got.shape == (2, 16, 256) and h_got.dtype == jnp.float32
    np.testing.assert_allclose(h_got, h_want, atol=1e-5)
    np.testing.assert_allclose(jnp.where(mask, got, 0.0),
                               jnp.where(mask, want, 0.0), atol=1e-4)
    # what a skipped chunk leaves is zero, not what the buffer held
    chunk = min(chunk, length)
    walked = -(-lens // chunk) * chunk
    skipped = (jnp.arange(length)[None] >= walked[:, None])[..., None]
    assert float(jnp.abs(jnp.where(skipped, got, 0.0)).max()) == 0.0


def test_scan_kernel_refuses_shapes_it_cannot_block():
    x, dt, bm, cm, z, a, d = _scan_inputs(1, 96)
    with pytest.raises(ValueError, match="chunks of 64"):
        ssm.ssm_scan_kernel(x, dt, bm, cm, z, a, d, jnp.asarray([96]),
                            chunk=64, interpret=True)


def test_step_matches_the_recurrence_and_skips_inactive_rows():
    x, dt, bm, cm, z, a, d = _scan_inputs(3, 9, d=128, seed=3)
    want, h_want = ssm.ssm_scan_plain(x, dt, bm, cm, z, a, d)
    _, h = ssm.ssm_scan_plain(x[:, :8], dt[:, :8], bm[:, :8], cm[:, :8],
                              z[:, :8], a, d)
    active = jnp.asarray([True, False, True])
    y, h_new = ssm.ssm_step(x[:, 8], dt[:, 8], bm[:, 8], cm[:, 8], z[:, 8],
                            a, d, h, active)
    np.testing.assert_allclose(y[active], want[:, 8][active], atol=1e-5)
    np.testing.assert_allclose(h_new[active], h_want[active], atol=1e-5)
    assert bool((h_new[1] == h[1]).all())


def test_conv_with_a_tail_continues_the_sequence():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 8))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    bias = jnp.arange(8.0)
    whole, window = ssm.causal_conv(x, taps, bias)
    assert window.shape == (2, 15, 8)
    last, _ = ssm.causal_conv(x[:, 11:], taps, bias, tail=x[:, 8:11])
    np.testing.assert_allclose(last[:, 0], whole[:, 11], atol=1e-6)


# -- (c) padding changes nothing --------------------------------------------
def test_prompt_padded_to_twice_its_length_leaves_the_exact_state(tiny):
    """Prefill of 64 positions of which the last 32 are garbage ids: logits
    at the real positions, the state rows and the convolution tails equal
    those of the 32 real tokens alone."""
    model, params, _, _ = tiny
    eng = _engine(model, params)
    real = _ids(32)
    padded = jnp.asarray([real + _ids(32, seed=9)], jnp.int32)
    table = jnp.asarray([list(range(1, 21))], jnp.int32)
    slots = jnp.asarray([1], jnp.int32)

    def prefill(ids, n, **kw):
        mask = (jnp.arange(ids.shape[1]) < n)[None]
        return model.apply(
            {"params": params}, ids, paged_kv=eng.caches, page_table=table,
            write_mask=mask, seq_lens=jnp.asarray([n]), slots=slots, **kw)

    lp, cp = prefill(padded, 32)
    le, ce = prefill(jnp.asarray([real], jnp.int32), 32)
    assert float(jnp.abs(lp[0, :32] - le[0]).max()) < 1e-5
    whole = model.apply({"params": params}, jnp.asarray([real], jnp.int32))
    assert float(jnp.abs(le[0] - whole[0]).max()) < TOL
    for i in model.state_layer_ids:
        for a, b in zip(cp[i], ce[i]):
            np.testing.assert_allclose(a[1], b[1], rtol=1e-4, atol=1e-5)
            assert float(jnp.abs(a[0]).max()) == 0.0   # row 0 untouched
        assert float(jnp.abs(cp[i][1][1]).max()) > 0
    # the head on one position a row is the head on all, cut
    one, _ = prefill(padded, 32, logits_at=jnp.asarray([31]))
    assert one.shape == (1, 1, 512)
    np.testing.assert_allclose(one[0, 0], lp[0, 31], atol=1e-6)


# -- (d) through the engine: prefill, then decoding across windows ----------
def test_engine_wave_of_unequal_prompts_matches_the_reference(tiny):
    """Three prompts of unequal length in one bucket (128), none a multiple
    of the scan's chunk, one wave; then 23 decode steps, 6 windows."""
    model, params, kw, reference = tiny
    eng = _engine(model, params, max_seqs=4)
    prompts = {"a": _ids(37, 3), "b": _ids(90, 4), "c": _ids(101, 5)}
    got = _run(eng, *[Request(r, p, max_tokens=24, logprobs=5)
                      for r, p in prompts.items()])
    assert [k[:2] for k in eng._prefill_fns] == [(128, 3)]
    for rid, prompt in prompts.items():
        assert len(got[rid]) == 24
        assert _gap(reference, params, kw, prompt, got[rid]) < TOL, rid


def _alone(model, params, prompt, n, **kw):
    return _run(_engine(model, params, **kw),
                Request("x", prompt, max_tokens=n, logprobs=5))["x"]


def test_released_slot_starts_the_next_request_from_zero(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=1)
    first, second = _ids(40, 6), _ids(25, 7)
    got = _run(eng, Request("p", first, max_tokens=9, logprobs=5),
               Request("q", second, max_tokens=9, logprobs=5))
    fresh = _alone(model, params, second, 9, max_seqs=1)
    assert [o.token for o in got["q"]] == [o.token for o in fresh]
    np.testing.assert_allclose([o.logprob for o in got["q"]],
                               [o.logprob for o in fresh], atol=1e-5)


# -- (e) what the engine builds, and refuses, for this family ---------------
def test_pool_is_float32_with_the_channels_last(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=3)
    assert eng.prefix_cache is None      # whatever enable_prefix_cache says
    assert model.state_layer_ids == (0, 1, 3)
    pages = (3 * 20 + 1, 8, 1 * 16)    # [P, ps, HK * D]
    for i, (a, b) in enumerate(eng.caches):
        if i in model.state_layer_ids:
            assert (a.shape, b.shape) == ((3, 3, 128), (3, 16, 128))
            assert b.dtype == jnp.float32
        else:
            assert a.shape == b.shape == pages
    # as laid out: a K/V row of 16 floats fills a 128-lane tile
    assert eng.cache_report == {
        "kv_layers": 1, "state_layers": 3,
        "kv_bytes": 2 * pages[0] * pages[1] * 128 * 4,
        "state_bytes": 3 * 3 * (3 + 16) * 128 * 4, "state_padding_pct": 0.0}
    # at the published widths the channels fill the lanes: nothing is padded
    big = JambaModel(JambaConfig())
    shapes = jax.eval_shape(
        lambda: big.init_cache(dataclasses.replace(eng.cache_cfg,
                                                   max_seqs=8)))
    tail, h = shapes[0]
    assert (tail.shape, tail.dtype) == ((8, 3, 5120), jnp.bfloat16)
    assert (h.shape, h.dtype) == ((8, 16, 5120), jnp.float32)
    assert shapes[7][0].shape[-1] == 128     # one K/V head of 128


def test_prefill_program_holds_no_logits_of_every_position(tiny):
    """The family's prefill runs the head on one position a row: its lowered
    program has no [nb, bucket, V] array; Llama's, which computes them all
    and keeps a row, has."""
    from benchmark import sizing
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = tiny[0]
    ec = dict(max_seqs=4, page_size=8, max_pages_per_seq=20)
    every = "tensor<3x128x512x"
    text = sizing.lower_prefill(model, ec, 128, 3, None).as_text()
    assert every not in text and "tensor<3x1x512x" in text
    llama = LlamaModel(LlamaConfig.tiny())
    assert every in sizing.lower_prefill(llama, ec, 128, 3, None).as_text()


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


def test_loader_picks_the_family_by_name():
    from ray_tpu import models
    from ray_tpu.llm._internal.server import load_model_and_params

    model, params = load_model_and_params(
        {"family": "jamba", "model": "tiny", "seed": 3})
    assert isinstance(model, JambaModel)
    assert "mamba" in params["layers_0"] and "self_attn" in params["layers_2"]
    assert models.sharding_rules(model) is None
    with pytest.raises(NotImplementedError, match="jamba"):
        load_model_and_params({"family": "jamba", "model": "tiny"},
                              mesh=object())
    with pytest.raises(NotImplementedError, match="JambaModel"):
        model.init_cache(None, mesh=object())
    with pytest.raises(NotImplementedError, match="LoRA"):
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                    lora={})


def test_spans_say_what_a_prefill_scans_and_where_the_head_runs():
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.server import LLMServer

    def last_prefill(family):
        srv = LLMServer({"family": family, "model": "tiny",
                         "engine_config": {"max_seqs": 2, "page_size": 8,
                                           "max_pages_per_seq": 16,
                                           "decode_steps": 2,
                                           "prefill_buckets": (32,)}})
        try:
            out = srv.generate_all(_ids(10), max_tokens=5)
            assert len(out["tokens"]) == 5
            cache = srv.stats()["cache"]
        finally:
            srv._running = False
        events = [e for e in fr.dump_events() if e.get("kind") == "span"]
        return cache, [e["args"] for e in events if e["name"] ==
                       "ray_tpu.engine.prefill_dispatch"][-1]

    cache, span = last_prefill("jamba")
    assert (cache["kv_layers"], cache["state_layers"]) == (1, 3)
    assert cache["state_bytes"] == 3 * 2 * (3 + 16) * 128 * 4
    assert (span["nb"], span["bucket"], span["state_rows"]) == (1, 32, 3)
    assert span["scan_positions"] == 3 * 32 and span["head_rows"] == 1
    _, span = last_prefill("llama")
    assert span["scan_positions"] == 0 and span["head_rows"] == 32


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
    w0, w1 = (made[f"layers_{i}"]["mamba"]["in_proj"]["kernel"]
              for i in (0, 1))
    assert float(jnp.abs(w0 - w1).max()) > 0.01
    big = JambaModel(JambaConfig(num_layers=2, attn_layer_period=2,
                                 attn_layer_offset=1))
    shapes = jax.eval_shape(big.init_params, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda rng: big.init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    assert spec(shapes) == spec(want)


def test_self_check_runs_for_the_family():
    """`LLMServer.self_check` (reachable through `OpenAIServer`): the engine
    against the model's own dense forward, the recurrence token by token
    without a cache."""
    from ray_tpu.llm._internal.server import LLMServer

    srv = LLMServer({"family": "jamba", "model": "tiny",
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
