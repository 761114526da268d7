"""`ray_tpu/ops/moe.py` at a tiny size on the CPU, float32, seeded: the
dropless top-k layer against an oracle that walks token by token and expert
by expert, the tile-aligned layout's invariants, and the Pallas grouped
matmul (interpret mode) against the einsum it stands in for."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import moe  # noqa: E402

E, K, H, I = 16, 8, 64, 32
# float32 sums of eight experts' outputs in another order (seen: 2e-6)
TOL = 2e-5


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (H, E), jnp.float32) * H ** -0.5,
            jax.random.normal(ks[1], (E, H, 2 * I), jnp.float32) * H ** -0.5,
            jax.random.normal(ks[2], (E, I, H), jnp.float32) * I ** -0.5)


def _oracle(x, router, gate_up, down, top_k):
    """Token by token: softmax, the top_k largest, renormalised, each chosen
    expert's SwiGLU. Returns (y, how many tokens each expert got)."""
    x, router, gate_up, down = (np.asarray(a, np.float64)
                                for a in (x, router, gate_up, down))
    y = np.zeros_like(x)
    load = np.zeros(router.shape[1], int)
    inter = down.shape[1]
    for t, xt in enumerate(x):
        logits = xt @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:top_k]
        for e in chosen:
            gu = xt @ gate_up[e]
            g, u = gu[:inter], gu[inter:]
            y[t] += p[e] / p[chosen].sum() * ((g / (1 + np.exp(-g)) * u)
                                              @ down[e])
            load[e] += 1
    return y, load


def _skewed(tokens=24, seed=1):
    """Inputs and a router under which expert 0 gets every token, expert 1
    exactly the first half, and experts 13-15 none."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, H), jnp.float32)
    x = x.at[:, 0].set(1.0).at[:, 1].set(
        jnp.where(jnp.arange(tokens) < tokens // 2, 1.0, -1.0))
    router, gate_up, down = _weights()
    router = router.at[:2].set(0.0)
    router = router.at[0, 0].set(50.0).at[0, 13:].set(-50.0)
    router = router.at[1, 1].set(50.0)
    return x, router, gate_up, down


def test_layer_matches_the_token_by_token_oracle_under_skewed_routing():
    x, router, gate_up, down = _skewed()
    y, (touched, fullest, *_) = moe.moe_layer(x, router, gate_up, down, K)
    want, load = _oracle(x, router, gate_up, down, K)
    assert load[0] == 24 and load[1] == 12 and not load[13:].any()
    assert load.sum() == 24 * K                      # nothing dropped
    np.testing.assert_allclose(np.asarray(y), want, atol=TOL, rtol=TOL)
    assert int(touched) == (load > 0).sum() == 13
    assert int(fullest) == 24


@pytest.mark.parametrize("tokens", [1, 5, 64])
def test_layer_matches_the_oracle_on_random_routing(tokens):
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, H))
    router, gate_up, down = _weights(seed=3)
    y, (touched, *_) = moe.moe_layer(x, router, gate_up, down, K)
    want, load = _oracle(x, router, gate_up, down, K)
    np.testing.assert_allclose(np.asarray(y), want, atol=TOL, rtol=TOL)
    assert int(touched) == (load > 0).sum()


def test_route_is_float32_renormalised_and_keeps_a_near_tie():
    x, router, *_ = _skewed()
    w, experts = moe.route(x.astype(jnp.bfloat16), router, K)
    assert w.dtype == jnp.float32 and experts.shape == (24, K)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    assert (np.asarray(experts) == 0).any(axis=-1).all()
    # two experts 1e-4 apart in probability: float32 tells them apart
    r = jnp.zeros((H, E)).at[0, 3].set(1.0).at[0, 5].set(1.0 + 1e-4)
    _, order = moe.route(jnp.zeros((1, H)).at[0, 0].set(1.0), r, 2)
    assert list(np.asarray(order[0])) == [5, 3]


@pytest.mark.parametrize("tokens,tm", [(24, 16), (24, None), (3, 16),
                                       (512, 32)])
def test_plan_lays_every_assignment_on_a_tile_of_its_expert(tokens, tm):
    x, router, *_ = _skewed(tokens)
    _, experts = moe.route(x, router, K)
    p = moe.plan(experts, E, tm)
    tm = p.tm
    sizes, dest = np.asarray(p.sizes), np.asarray(p.dest)
    assert sizes.sum() == tokens * K
    rows = len(p.row_token)
    assert rows % tm == 0 and rows >= tokens * K
    assert rows <= (tokens * K + min(tokens * K, E) * (tm - 1) + tm - 1)
    # every assignment has a row of its own, which holds its token, on a
    # tile that takes its expert's weights
    assert len(set(dest.reshape(-1))) == tokens * K
    token = np.asarray(p.row_token)[dest]
    assert (token == np.arange(tokens)[:, None]).all()
    tile_expert = np.asarray(p.tile_expert)
    assert (tile_expert[dest // tm] == np.asarray(experts)).all()
    used = int(p.tiles_used[0])
    assert used == sum(-(-s // tm) for s in sizes)
    assert dest.max() < used * tm
    # an expert nobody chose owns no tile; skipped tiles repeat the last
    assert set(tile_expert) == set(np.flatnonzero(sizes))
    assert (tile_expert[used:] == tile_expert[used - 1]).all()


@pytest.mark.parametrize("tokens,n", [(24, 2 * I), (24, 256), (130, H)])
def test_gmm_kernel_in_interpret_mode_matches_the_einsum(tokens, n):
    """The Pallas kernel over the tiles some expert owns (column blocks of
    128 where `n` allows more than one); rows of the skipped tiles are
    nobody's."""
    x, router, *_ = _skewed(tokens)
    _, experts = moe.route(x, router, K)
    p = moe.plan(experts, E)
    rhs = jax.random.normal(jax.random.PRNGKey(7), (E, H, n)) * H ** -0.5
    lhs = jnp.take(x, p.row_token, axis=0)
    old = moe.RHS_BLOCK_BYTES
    moe.RHS_BLOCK_BYTES = H * 128 * 4   # several column blocks at n = 256
    try:
        got = moe.gmm(lhs, rhs, p, use_kernel=True, interpret=True)
    finally:
        moe.RHS_BLOCK_BYTES = old
    want = moe.gmm(lhs, rhs, p, use_kernel=False)
    live = int(p.tiles_used[0]) * p.tm
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live],
                               atol=TOL, rtol=TOL)
    dest = np.asarray(p.dest)
    direct = np.einsum("th,tkhn->tkn", np.asarray(x),
                       np.asarray(rhs)[np.asarray(experts)])
    np.testing.assert_allclose(np.asarray(got)[dest], direct, atol=TOL,
                               rtol=TOL)


def test_layer_through_the_kernel_matches_the_layer_through_the_einsum():
    x, router, gate_up, down = _skewed()
    a, _ = moe.moe_layer(x, router, gate_up, down, K, use_kernel=True,
                         interpret=True)
    b, _ = moe.moe_layer(x, router, gate_up, down, K, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                               rtol=TOL)


def test_tiles_are_short_at_decode_and_long_at_prefill():
    # SDAR's decode step: 64 tokens x 8 over 128 experts, 4 rows a group
    assert moe.tile_rows(64 * 8, 128) == 16
    # its largest prefill: 16 prompts of 128 tokens
    assert moe.tile_rows(16 * 128 * 8, 128) == 128
    assert moe.tile_rows(10 ** 6, 128) == moe.MAX_TILE_ROWS
    # a weight block [2048, 768] bf16 is 3 MiB: gate and up are a block each
    assert moe._rhs_columns(2048, 1536, 2) == 768
    assert moe._rhs_columns(768, 2048, 2) == 2048
    assert moe._rhs_columns(H, 2 * I, 4) == 2 * I
