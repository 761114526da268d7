"""Nemotron-H (Nemotron 3 Super's blocks) at a tiny size on the CPU (hidden
64, five blocks `ME*EM`: Mamba-2 with two groups of B and C, latent experts,
attention; 8 relu^2 experts routed top-3 by sigmoid in a latent of 32, of
which a share is held, a shared expert; float32, seeded): the model and the
engine's caches (a state a slot, paged K/V, and NOTHING on an expert block)
against the plain reference `benchmark/references/nemotron_h.py`, the grouped
scan kernel in interpret mode against the token-by-token form, the grouped
matmul's relu^2 body against plain `jax.numpy`, and the expert block's four
shares against the whole. Logprobs and not tokens: with seeded weights the
largest logit changes on rounding."""

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
from ray_tpu.models.nemotron_h import (  # noqa: E402
    ATTENTION, EXPERT_DOWN_STD, EXPERTS, MAMBA, LatentMoe, NemotronHConfig,
    NemotronHModel)
from ray_tpu.ops import moe, ssm  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4   # float32 on the CPU through five blocks (seen: 3e-6)


def _kw(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding experts 2-5 of its 8: a share in the middle."""
    cfg = NemotronHConfig.tiny(experts_held=(2, 4))
    model = NemotronHModel(cfg)
    # The family's one seeded initializer: what the loader runs on the chip.
    params = model.init_params(jax.random.PRNGKey(1))
    reference = Manifest(REPO).reference("nemotron_h")
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


def _scan_inputs(b, length, groups, heads=8, width=16, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, length, heads, width))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, length, heads)) - 2.0)
    bm = jax.random.normal(ks[2], (b, length, groups, n))
    cm = jax.random.normal(ks[3], (b, length, groups, n))
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
    # the reference's head on some positions is its head on all, cut
    some = reference.logprobs(params, ids, kw, rows=jnp.asarray([3, 63]))
    np.testing.assert_allclose(some, want[jnp.asarray([3, 63])], atol=1e-5)


@pytest.mark.parametrize("wrong,why", [
    ({"routed_scaling_factor": 1.0}, "the routed sum not scaled"),
    ({"num_experts_per_tok": 2}, "one expert fewer a token"),
])
def test_the_reference_read_otherwise_is_far_from_the_program(tiny, wrong,
                                                              why):
    """What the family adds is in the numbers: the reference with one of
    them read otherwise is far from the program, which is within `TOL` of
    the reference as published."""
    model, params, kw, reference = tiny
    ids = jnp.asarray(_ids(40), jnp.int32)
    got = jax.nn.log_softmax(
        model.apply({"params": params}, ids[None])[0].astype(jnp.float32), -1)
    off = reference.logprobs(params, ids, {**kw, **wrong})
    assert float(jnp.abs(got - off).max()) > 20 * TOL, why


def test_block_kinds_names_and_float32_leaves(tiny):
    model, params, _, _ = tiny
    assert model.cfg.hybrid_override_pattern == "ME*EM"
    published = NemotronHConfig()
    pattern = published.hybrid_override_pattern
    assert (len(pattern), pattern.count(MAMBA), pattern.count(ATTENTION),
            pattern.count(EXPERTS)) == (88, 40, 8, 40)
    assert pattern[:11] == "MEMEMEM*EME"
    assert published.conv_dim == 8192 + 2 * 8 * 128 == 10_240
    assert set(params) == {f"layers_{i}" for i in range(5)} | {
        "embed_tokens", "norm_f", "lm_head"}
    # every block: one norm and one mixer, and nothing else
    assert all(set(params[f"layers_{i}"]) == {"norm", "mixer"}
               for i in range(5))
    assert set(params["layers_0"]["mixer"]) == {
        "in_proj", "conv1d_weight", "conv1d_bias", "dt_bias", "A_log", "D",
        "norm", "out_proj"}
    assert set(params["layers_2"]["mixer"]) == {"q_proj", "k_proj", "v_proj",
                                                "o_proj"}
    experts = params["layers_1"]["mixer"]
    assert set(experts) == {"experts", "fc1_latent_proj", "fc2_latent_proj",
                            "shared_experts"}
    # no gate: one `up` stack in the latent, and the share that is held
    assert {k: v.shape for k, v in experts["experts"].items()} == {
        "router": (64, 8), "bias": (8,), "up": (4, 32, 16),
        "down": (4, 16, 32)}
    shapes = jax.eval_shape(NemotronHModel(published).init_params,
                            jax.random.PRNGKey(0))
    wide = {jax.tree_util.keystr(p) for p, x in
            jax.tree_util.tree_flatten_with_path(shapes)[0]
            if x.dtype == jnp.float32}
    assert {w.split("'")[-2] for w in wide} == {
        "A_log", "D", "dt_bias", "norm", "scale", "router", "bias"}
    assert shapes["layers_0"]["mixer"]["in_proj"]["kernel"].shape == (
        4096, 8192 + 10_240 + 128)
    assert shapes["layers_1"]["mixer"]["experts"]["up"].shape == (
        512, 1024, 2688)


def test_the_cut_configuration_counts_its_parameters():
    """Eleven blocks (the first pipeline stage) holding 128 of 512 experts:
    5.45B, as the family file and the configuration's file reckon it."""
    cfg = NemotronHConfig(
        hybrid_override_pattern=NemotronHConfig().hybrid_override_pattern[
            :11], experts_held=(0, 128))
    model = NemotronHModel(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 5_453_470_080
    assert model.state_layer_ids == (0, 2, 4, 6, 9)
    assert model.expert_layer_ids == model.cacheless_layer_ids == (
        1, 3, 5, 8, 10)
    assert model.num_logits_to_keep == 1
    with pytest.raises(ValueError, match="experts_held"):
        NemotronHConfig(experts_held=(400, 128))
    with pytest.raises(ValueError, match="hybrid_override_pattern holds"):
        NemotronHConfig(hybrid_override_pattern="ME-")
    with pytest.raises(ValueError, match="128 heads in 3 groups"):
        NemotronHConfig(mamba_n_groups=3)


# -- (b) the grouped scan (interpret mode) against the token-by-token form --
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("length,chunk,lens", [
    (96, 32, (96, 40)),      # a row ends inside its second chunk
    (128, 32, (33, 127)),    # one past a boundary, one short of the end
    (32, 128, (32, 7)),      # the chunk is cut to the bucket
])
def test_grouped_ssd_scan_kernel_matches_the_recurrence(groups, length, chunk,
                                                        lens):
    """Head h reads group h // (8 / groups) of B and C; a block of the
    kernel's heads lies inside one group (at 8 groups a head a block), and
    C B^T is made anew at each group's first block."""
    x, dt, bm, cm, a, d = _scan_inputs(2, length, groups)
    lens = jnp.asarray(lens)
    mask = jnp.arange(length)[None] < lens[:, None]
    dt = jnp.where(mask[..., None], dt, 0.0)
    want, s_want = ssm.ssd_scan_plain(x, dt, bm, cm, a, d)
    got, s_got = ssm.ssd_scan_kernel(x, dt, bm, cm, a, d, lens, chunk=chunk,
                                     heads=2, interpret=True)
    assert s_got.shape == (2, 8, 16, 16) and s_got.dtype == jnp.float32
    np.testing.assert_allclose(s_got, s_want, atol=1e-5)
    at = mask[..., None, None]
    scale = float(jnp.abs(want).max())
    assert scale > 1.0
    np.testing.assert_allclose(jnp.where(at, got, 0.0) / scale,
                               jnp.where(at, want, 0.0) / scale, atol=1e-5)
    # the token-by-token form is the recurrence a head at a time with its
    # own group's B and C spelled out
    per = 8 // groups
    for h in (0, 3, 7):
        one, _ = ssm.ssd_scan_plain(
            x[:, :, h:h + 1], dt[:, :, h:h + 1],
            bm[:, :, h // per][:, :, None], cm[:, :, h // per][:, :, None],
            a[h:h + 1], d[h:h + 1])
        np.testing.assert_allclose(one[:, :, 0], want[:, :, h], atol=1e-5)
    if groups > 1:
        # and the groups are not one another's: the first group's B and C
        # for every head is another result
        wrong, _ = ssm.ssd_scan_plain(x, dt, bm[:, :, :1], cm[:, :, :1], a, d)
        assert float(jnp.abs(wrong - want).max()) > 0.1 * scale


def test_grouped_ssd_step_continues_a_scans_final_state():
    x, dt, bm, cm, a, d = _scan_inputs(3, 41, 2, seed=3)
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
    with pytest.raises(ValueError, match="8 heads of 2 groups in blocks of 3"):
        ssm.ssd_scan_kernel(x, dt, bm, cm, a, d, jnp.asarray([41] * 3),
                            chunk=41, heads=3, interpret=True)


# -- (c) the grouped matmul's relu^2 body, and the latent rows --------------
def _latent_layer(t=64, h=48, latent=32, inter=16, e=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (t, h)),
            jax.random.normal(ks[1], (t, latent)),
            jax.random.normal(ks[2], (h, e)) / np.sqrt(h),
            0.2 * jax.random.normal(ks[3], (e, latent, inter)),
            0.2 * jax.random.normal(ks[4], (e, inter, latent)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.0)])
def test_gmm_relu2_body_is_the_squared_relu_of_the_product(dtype, tol):
    """`gmm(..., act="relu2")` through the interpreted kernel and through the
    einsum against plain `jax.numpy`: the product of a row with its expert's
    `up`, rounded as a call without `act` returns it, the ReLU squared in
    float32 and rounded once (bit for bit in bf16)."""
    x, rows, router, up, down = _latent_layer()
    _, experts = moe.route(x, router, 3)
    p = moe.plan(experts, 8, tm=16)
    lhs = jnp.take(rows, p.row_token, axis=0).astype(dtype)
    up = up.astype(dtype)
    used = int(p.tiles_used[0]) * 16
    product = moe.gmm(lhs, up, p, use_kernel=False)
    assert product.dtype == dtype and product.shape == (lhs.shape[0], 16)
    want = jnp.square(jnp.maximum(product.astype(jnp.float32), 0.0)
                      ).astype(dtype)
    mine = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32), jnp.take(
        up, jnp.repeat(p.tile_expert, 16), axis=0).astype(jnp.float32))
    np.testing.assert_allclose(product[:used].astype(jnp.float32),
                               mine[:used], atol=1e-5 if tol else 0.05)
    for how in (dict(use_kernel=False),
                dict(use_kernel=True, interpret=True)):
        got = moe.gmm(lhs, up, p, act="relu2", **how)
        assert got.dtype == dtype and got.shape == want.shape
        np.testing.assert_allclose(got[:used].astype(jnp.float32),
                                   want[:used].astype(jnp.float32), atol=tol)
        assert float(got[:used].min()) >= 0.0
    with pytest.raises(ValueError, match="act 'gelu'"):
        moe.gmm(lhs, up, p, act="gelu")


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_layer_routes_on_one_array_and_gathers_rows_from_another(held):
    """The router reads x [T, 48]; the rows that are gathered, multiplied and
    combined are the latent's [T, 32]; an expert is down(relu(up l)^2)."""
    x, rows, router, up, down = _latent_layer()
    first, count = held or (0, 8)
    kw = dict(scoring="sigmoid", bias=jnp.linspace(-0.02, 0.02, 8), scale=5.0)
    got, load = moe.moe_layer(x, router, up[first:first + count],
                              down[first:first + count], 3, held=held,
                              act="relu2", rows=rows, **kw)
    assert got.shape == rows.shape
    weights, experts = moe.route(x, router, 3, **kw)
    np.testing.assert_allclose(weights.sum(-1), 5.0, atol=1e-5)
    want = jnp.zeros_like(rows)
    for e in range(first, first + count):
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        want = want + w[:, None] * (
            jnp.square(jnp.maximum(rows @ up[e], 0.0)) @ down[e])
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert int(load.rows_routed) == 64 * 3
    assert int(load.rows_held) == int(
        ((experts >= first) & (experts < first + count)).sum())
    # the router did not read the latent: other rows, the same choices
    _, again = moe.moe_layer(x, router, up[first:first + count],
                             down[first:first + count], 3, held=held,
                             act="relu2", rows=2.0 * rows, **kw)
    assert [int(v) for v in again] == [int(v) for v in load]


@pytest.mark.parametrize("fault", [None, "sum zeroed", "square dropped",
                                   "wrong share held"])
def test_routed_experts_at_the_published_widths_and_faults_planted_there(
        fault, monkeypatch):
    """What the chip's `correct` cannot hold (PERF.md 7.33: a flip at the
    router's 22nd place moves nearly what a fault in the routed sum moves):
    the routed experts at ONE EXPERT'S PUBLISHED WIDTHS (a latent of 1,024,
    2,688 wide, bf16; sixteen rows, a decode step's tiles of 16, the
    kernels' real blocks [1024, 2688] and [2688, 1024]), 8 of 32 held,
    through the interpreted kernels against the reference's `_routed` in
    float32 on the same rounded weights and latents. The sound layer lies
    within bf16's rounding of it (seen: 0.029 of the RMS at the worst
    element); each fault planted in the routed path reads a hundred times
    that and is refused by the same tolerance."""
    reference = Manifest(REPO).reference("nemotron_h")
    t, hid, latent, inter, e, k, held = 16, 256, 1024, 2688, 32, 6, (8, 8)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (t, hid))
    rows = jax.random.normal(ks[1], (t, latent)).astype(jnp.bfloat16)
    p = {"router": jax.random.normal(ks[2], (hid, e)) * hid ** -0.5,
         "bias": jnp.linspace(-0.02, 0.02, e),
         "up": (jax.random.normal(ks[3], (held[1], latent, inter))
                * latent ** -0.5).astype(jnp.bfloat16),
         "down": (jax.random.normal(ks[4], (held[1], inter, latent))
                  * inter ** -0.5).astype(jnp.bfloat16)}
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference._routed(
            p, x, f32(rows), dict(num_experts_per_tok=k,
                                  routed_scaling_factor=5.0,
                                  experts_held=held), f32))
    rms = float(np.sqrt(np.mean(want ** 2)))
    assert rms > 0.5 and int(np.any(want != 0, axis=-1).sum()) >= t // 2
    if fault == "sum zeroed":
        real = moe.combine
        monkeypatch.setattr(moe, "combine", lambda *a, **kw: jnp.zeros_like(
            real(*a, **kw)))
    elif fault == "square dropped":
        monkeypatch.setattr(moe, "_relu2",
                            lambda up: jnp.maximum(up, 0).astype(up.dtype))
    elif fault == "wrong share held":
        held = (16, 8)
    got, load = moe.moe_layer(
        x, p["router"], p["up"], p["down"], k, held=held, act="relu2",
        rows=rows, use_kernel=True, interpret=True, scoring="sigmoid",
        bias=p["bias"], scale=5.0)
    assert got.shape == (t, latent) and got.dtype == jnp.bfloat16
    assert int(load.rows_routed) == t * k
    worst = float(np.max(np.abs(np.asarray(got, np.float32) - want))) / rms
    if fault is None:
        assert worst < 0.06, worst
    else:
        assert worst > 1.0, (fault, worst)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_whole(tiny):
    """Expert parallelism's arithmetic: the four quarters of the experts,
    each routed over all 8 columns on the hidden state and computed in the
    latent by the chip that holds it, and what every chip computes alike (the
    shared expert on the hidden state; both latent projections are linear,
    so the up-projection of the sum is the sum of the up-projections),
    counted once, add up to what the reference gives for the uncut block."""
    _, _, kw, reference = tiny
    whole = NemotronHConfig.tiny()
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    flat = u.reshape(-1, 64)
    p = LatentMoe(whole).init(jax.random.PRNGKey(6), u)["params"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference._experts(p, flat, {**kw, "experts_held": (0, 8)},
                                  f32)
        shared = reference._relu2(
            flat @ p["shared_experts"]["up_proj"]["kernel"]
        ) @ p["shared_experts"]["down_proj"]["kernel"]
    parts, rows = [], 0
    for first in (0, 2, 4, 6):
        cfg = dataclasses.replace(whole, experts_held=(first, 2))
        mine = {**p, "experts": {
            **p["experts"], "up": p["experts"]["up"][first:first + 2],
            "down": p["experts"]["down"][first:first + 2]}}
        y, sown = LatentMoe(cfg).apply({"params": mine}, u,
                                       mutable=["expert_load"])
        load = moe.Load(*sown["expert_load"]["experts"]["load"][0])
        assert int(load.rows_routed) == 2 * 24 * 3
        rows += int(load.rows_held)
        parts.append(y.reshape(want.shape))
        # and the reference given one share is that share
        with jax.default_matmul_precision("highest"):
            one = reference._experts(
                mine, flat, {**kw, "experts_held": (first, 2)}, f32)
        np.testing.assert_allclose(parts[-1], one, atol=1e-5)
    assert rows == 2 * 24 * 3        # every assignment has exactly one home
    assert float(jnp.abs(parts[0] - shared).mean()) > 0.01   # a real share
    got = sum(parts) - 3 * shared    # the shared expert counted once
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- (d) through the engine: prefill, then decoding across windows ----------
def test_engine_wave_of_unequal_prompts_matches_the_reference(tiny):
    """Three prompts of unequal length in one bucket (128), none a multiple
    of the scan's chunk, one wave; then 23 decode steps through the state
    pool, the paged cache and the blocks that hold nothing."""
    model, params, kw, reference = tiny
    eng = _engine(model, params, max_seqs=4)
    prompts = {"a": _ids(37, 3), "b": _ids(90, 4), "c": _ids(101, 5)}
    got = _run(eng, *[Request(r, p, max_tokens=24, logprobs=5)
                      for r, p in prompts.items()])
    assert [k[:2] for k in eng._prefill_fns] == [(128, 3)]
    for rid, prompt in prompts.items():
        assert len(got[rid]) == 24
        assert _gap(reference, params, kw, prompt, got[rid]) < TOL, rid
    # the expert blocks' entries went through both programs as they came
    assert [eng.caches[i] for i in model.cacheless_layer_ids] == [(), ()]


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
def test_cache_has_a_pool_a_mixer_and_nothing_for_an_expert_block(tiny):
    model, params, _, _ = tiny
    eng = _engine(model, params, max_seqs=3)
    assert eng.prefix_cache is None      # whatever enable_prefix_cache says
    assert model.state_layer_ids == (0, 4)
    assert model.expert_layer_ids == model.cacheless_layer_ids == (1, 3)
    pages = (3 * 20 + 1, 8, 2 * 16)    # [P, ps, HK * D]
    for i, entry in enumerate(eng.caches):
        if i in model.cacheless_layer_ids:
            assert entry == ()
            continue
        a, b = entry
        if i in model.state_layer_ids:
            # the tail over x and both groups' B and C; the states last
            assert (a.shape, b.shape) == ((3, 3, 128 + 2 * 2 * 16),
                                          (3, 8, 16, 16))
            assert b.dtype == jnp.float32
        else:
            assert a.shape == b.shape == pages
    report = eng.cache_report
    assert (report["kv_layers"], report["state_layers"],
            report["cacheless_layers"]) == (1, 2, 2)
    # one layer's pages, as the device lays them out (32 values on 128 lanes)
    assert report["kv_bytes"] == 2 * (3 * 20 + 1) * 8 * 128 * 4
    assert report["state_bytes"] == 2 * 3 * (3 * 256 + 8 * 16 * 128) * 4
    # at the published widths the 128 states fill the lanes: nothing padded,
    # and the cut configuration's cache is five pools, one layer's pages and
    # five empty entries
    cut = NemotronHModel(NemotronHConfig(
        hybrid_override_pattern="MEMEMEM*EME", experts_held=(0, 128)))
    shapes = jax.eval_shape(
        lambda: cut.init_cache(dataclasses.replace(
            eng.cache_cfg, max_seqs=16, num_pages=16 * 20 + 1, page_size=64)))
    assert [len(jax.tree.leaves(s)) for s in shapes] == [
        2, 0, 2, 0, 2, 0, 2, 2, 0, 2, 0]
    tail, s = shapes[0]
    assert (tail.shape, tail.dtype) == ((16, 3, 10_240), jnp.bfloat16)
    assert (s.shape, s.dtype) == ((16, 128, 64, 128), jnp.float32)
    assert shapes[7][0].shape == (321, 64, 2 * 128)   # two K/V heads of 128
    # 20.3 MiB a slot over the five pools, 1 KiB a token on one layer
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(shapes))
    assert held == 5 * 16 * (4 * 2 ** 20 + 61_440) + 2 * 321 * 64 * 256 * 2


def test_spans_and_stats_count_the_cacheless_blocks_apart():
    """`llm_config["family"]` picks the family; `stats()["cache"]` and the
    `cache_built` mark say `cacheless_layers`; its one-token decode windows
    report the expert load on `emit`, its prefills on `prefill_dispatch`,
    both with `state_rows` over the Mamba-2 blocks alone."""
    from ray_tpu import models
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.server import LLMServer, load_model_and_params

    began = time.time()
    srv = LLMServer({"family": "nemotron_h", "model": "tiny",
                     "engine_config": {"max_seqs": 2, "page_size": 8,
                                       "max_pages_per_seq": 16,
                                       "decode_steps": 2,
                                       "prefill_buckets": (32,)}})
    try:
        assert isinstance(srv.engine.model, NemotronHModel)
        out = srv.generate_all(_ids(10), max_tokens=5)
        assert len(out["tokens"]) == 5
        stats = srv.stats()
    finally:
        srv._running = False
    cache, summed = stats["cache"], stats["expert_load"]
    assert (cache["kv_layers"], cache["state_layers"],
            cache["cacheless_layers"]) == (1, 2, 2)
    events = [e for e in fr.dump_events() if e["ts"] >= began]
    built = [e["args"] for e in events
             if e["name"] == "ray_tpu.engine.cache_built"][-1]
    assert built == cache
    spans = [e for e in events if e.get("kind") == "span"]
    prefill = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.prefill_dispatch"][-1]
    decodes = [e["args"] for e in spans
               if e["name"] == "ray_tpu.engine.dispatch_decode"]
    emits = [e["args"] for e in spans if e["name"] == "ray_tpu.engine.emit"
             and "expert_rows_routed" in e["args"]]
    # the tiny preset holds all 8: every assignment has a row here
    assert prefill["expert_rows_routed"] == 2 * 32 * 3   # blocks x rows x k
    assert prefill["expert_rows_held"] == prefill["expert_rows_routed"]
    assert 3 <= prefill["experts_touched"] <= 2 * 8
    # rows x the two Mamba-2 blocks; the head on one position
    assert (prefill["state_rows"], prefill["scan_positions"],
            prefill["head_rows"]) == (2, 2 * 32, 1)
    assert decodes and all(d["state_rows"] == 2 * d["active"]
                           for d in decodes)
    assert emits and all(
        e["expert_rows_routed"] % (2 * 2 * 3) == 0   # blocks x slots x k
        and 0 < e["expert_load_max"] <= e["expert_rows_held"]
        and e["expert_tiles"] == e["experts_touched"] for e in emits)
    for name in ("experts_touched", "expert_rows_routed", "expert_tiles"):
        assert summed[name] >= prefill[name] + sum(e[name] for e in emits)
    model, _ = load_model_and_params(
        {"family": "nemotron_h", "model": "tiny", "seed": 3})
    assert models.sharding_rules(model) is None
    with pytest.raises(NotImplementedError, match="nemotron_h"):
        load_model_and_params({"family": "nemotron_h", "model": "tiny"},
                              mesh=object())
    with pytest.raises(NotImplementedError, match="NemotronHModel"):
        model.init_cache(None, mesh=object())
    with pytest.raises(NotImplementedError, match="has no LoRA banks"):
        model.apply({"params": {}}, jnp.zeros((1, 8), jnp.int32), lora={})


def test_init_params_makes_the_tree_flax_init_makes(tiny):
    model, params, _, _ = tiny
    spec = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    made = model.init_params(jax.random.PRNGKey(4))
    assert spec(made) == spec(params) == spec(jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(4)))
    w0, w4 = (made[f"layers_{i}"]["mixer"]["in_proj"]["kernel"]
              for i in (0, 4))
    assert float(jnp.abs(w0 - w4).max()) > 0.01      # a key a block
    # the routed experts' down stacks at EXPERT_DOWN_STD of lecun's
    down = made["layers_1"]["mixer"]["experts"]["down"]
    up = made["layers_1"]["mixer"]["experts"]["up"]
    assert 0.8 < float(jnp.std(up)) * np.sqrt(32) < 1.2
    assert 0.8 < float(jnp.std(down)) * np.sqrt(16) / EXPERT_DOWN_STD < 1.2
    assert float(jnp.abs(made["layers_1"]["mixer"]["experts"]["bias"]
                         ).max()) > 0.0
