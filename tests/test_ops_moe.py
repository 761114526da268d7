"""`ray_tpu/ops/moe.py` at a tiny size on the CPU, float32, seeded: the
dropless top-k layer against an oracle that walks token by token and expert
by expert, the tile-aligned layout's invariants, and the Pallas grouped
matmul (interpret mode) against the einsum it stands in for."""

import functools

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
def test_gmm_kernel_in_interpret_mode_matches_the_einsum(tokens, n,
                                                         monkeypatch):
    """The Pallas kernel over the tiles some expert owns (column blocks of
    128 where `n` allows more than one); rows of the skipped tiles are
    nobody's."""
    x, router, *_ = _skewed(tokens)
    _, experts = moe.route(x, router, K)
    p = moe.plan(experts, E)
    rhs = jax.random.normal(jax.random.PRNGKey(7), (E, H, n)) * H ** -0.5
    lhs = jnp.take(x, p.row_token, axis=0)
    # several column blocks at n = 256
    monkeypatch.setattr(moe, "_rhs_columns", lambda tm, k, n, *_: min(n, 128))
    got = moe.gmm(lhs, rhs, p, use_kernel=True, interpret=True)
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
    # a weight block takes every column at the widths served: SDAR's gate
    # and up [2048, 1536] and down [768, 2048], Granite's [4096, 1536] (12
    # MiB, 27.5 MiB a step of 128 rows with its double buffers) and [768,
    # 4096]; wider stacks are cut into whole lanes that divide them
    for tm in (16, 128):
        assert moe._rhs_columns(tm, 2048, 1536, 2) == 1536
        assert moe._rhs_columns(tm, 768, 2048, 2) == 2048
        assert moe._rhs_columns(tm, 4096, 1536, 2) == 1536
        assert moe._rhs_columns(tm, 768, 4096, 2) == 4096
    assert moe._rhs_columns(128, 4096, 28672, 2) == 2048
    assert moe._rhs_columns(16, H, 2 * I, 4) == 2 * I


@pytest.mark.parametrize("tm", [16, 128])
@pytest.mark.parametrize("k,inter,want", [
    (4096, 768, 768),      # Granite: every gate and every up column a step
    (2048, 768, 768),      # SDAR
    (2304, 896, 896),      # Mellum
    (4096, 2048, 1024),    # Sarvam: two column blocks, as its plain call has
    (4096, 14336, 1024),   # a dense model's width: fourteen
    (H, I, I),             # no whole lanes: all of `I`
])
def test_the_activating_call_takes_the_columns_two_weight_blocks_leave(
        tm, k, inter, want):
    """`gmm(..., act=True)` holds two weight blocks a step (the expert's
    gate columns and its up columns) and two float32 products: the column
    block is the widest under which they, the rows and the output fit the
    kernel's share of VMEM, so the rows are read as often as the plain call
    over the same stack reads them."""
    tg = moe._rhs_columns(tm, k, inter, 2, 2)
    assert tg == want and inter % tg == 0
    # two weight blocks, the rows and the output double-buffered in bf16,
    # two float32 products
    step = 2 * (2 * k * tg + tm * k + tm * tg) * 2 + 2 * 4 * tm * tg
    assert step <= moe.VMEM_BLOCKS_SHARE * moe.VMEM_LIMIT_BYTES
    # the passes over the rows: those of the plain call over [K, 2I]
    assert inter // tg == 2 * inter // moe._rhs_columns(tm, k, 2 * inter, 2)


# ---------------------------------------------------------------------------
# The schedule: the regimes the cells run, bit for bit and fetch by fetch
# ---------------------------------------------------------------------------
def _round_robin(tokens, k, first, count):
    """[tokens, k] experts: token t's j-th choice is first + (t + j) % count,
    so each of `count` experts gets tokens * k / count rows."""
    return (first + (jnp.arange(tokens)[:, None] + jnp.arange(k)[None])
            % count).astype(jnp.int32)


def _granite_share(tokens):
    """Top-10 of 72 seeded router columns, the first 36 held."""
    logits = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 72))
    return jax.lax.top_k(logits, 10)[1].astype(jnp.int32), 72, (0, 36)


# name -> (experts [T, k], router columns, held, tm, tiles an expert)
REGIMES = {
    # a decode step: tiles of 16, every expert one tile
    "decode_one_tile_an_expert": lambda: (_round_robin(8, 4, 0, 8), 8, None,
                                          16, 1),
    # a one-prompt prefill: tiles of 128, 300 rows an expert
    "prefill_3_tiles_an_expert": lambda: (_round_robin(300, 4, 0, 4), 4,
                                          None, 128, 3),
    # a wave's prefill: 2,200 rows an expert
    "prefill_18_tiles_an_expert": lambda: (_round_robin(2200, 2, 0, 2), 2,
                                           None, 128, 18),
    # a chip's share: half of the assignments have no row here
    "held_share_with_absent_experts": lambda: (*_granite_share(64), None,
                                               None),
    # a share none of whose experts was chosen: no tile in use
    "no_tile_in_use": lambda: (_round_robin(8, 4, 0, 8), 16, (8, 8), None,
                               None),
    # two experts of sixteen take everything: fourteen tiles are skipped
    "tail_of_skipped_tiles": lambda: (_round_robin(24, 2, 5, 2), 16, None,
                                      16, 2),
}


@pytest.mark.parametrize("regime", list(REGIMES))
def test_kernel_is_the_einsum_bit_for_bit_in_every_regime(regime,
                                                          monkeypatch):
    """The interpreted kernel, two column blocks wide so that the order of
    the grid matters, against the batched einsum: every row some assignment
    owns is the same float, whatever the tiles an expert owns."""
    experts, columns, held, tm, tiles_each = REGIMES[regime]()
    p = moe.plan(experts, columns, tm, held=held)
    count = columns if held is None else held[1]
    used, sizes = int(p.tiles_used[0]), np.asarray(p.sizes)
    if tiles_each is not None:
        assert p.tm == tm
        assert (-(-sizes[sizes > 0] // p.tm) == tiles_each).all()
    if regime == "no_tile_in_use":
        assert used == 0 and not sizes.any()
    if regime == "tail_of_skipped_tiles":
        assert len(p.tile_expert) - used >= 14
    if regime == "held_share_with_absent_experts":
        assert 0 < sizes.sum() < experts.size       # some rows elsewhere
    k, n = 64, 256
    ks = jax.random.split(jax.random.PRNGKey(len(regime)), 2)
    lhs = jax.random.normal(ks[0], (len(p.row_token), k), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (count, k, n), jnp.bfloat16) * k ** -0.5
    monkeypatch.setattr(moe, "_rhs_columns", lambda *_: 128)
    got = moe.gmm(lhs, rhs, p, use_kernel=True, interpret=True)
    want = moe.gmm(lhs, rhs, p, use_kernel=False)
    live = used * p.tm
    assert got.shape == want.shape == (len(p.row_token), n)
    assert bool((got[:live] == want[:live]).all())


def _product_then_activation(lhs, rhs, p, gmm=moe.gmm, **how):
    """The gate-and-up call as the layer made it before the call wrote the
    activation: the product [M, 2I] stored in the rows' dtype, converted to
    float32 whole, silu(gate) * up as a pass of its own."""
    gu = gmm(lhs, rhs, p, **how).astype(jnp.float32)
    inter = rhs.shape[2] // 2
    return (jax.nn.silu(gu[:, :inter]) * gu[:, inter:]).astype(lhs.dtype)


# name -> (I, columns a weight block is cut to or None for `_rhs_columns`'s)
ACT_BLOCKS = {
    "two_column_blocks": (256, 128),
    "one_column_block": (128, None),
    "no_whole_lanes": (96, None),
    "three_column_blocks": (384, 128),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("blocks", list(ACT_BLOCKS))
@pytest.mark.parametrize("regime", list(REGIMES))
def test_activating_kernel_is_product_then_activation_bit_for_bit(
        regime, blocks, dtype, monkeypatch):
    """`gmm(..., act=True)` through the interpreted kernel and through the
    einsum against the parent's three steps (the product through the kernel,
    its float32 copy, the activation): every row some assignment owns is the
    same float, under a share, with a tail of unused tiles, with no tile in
    use, with `I` not whole lanes and with one, two and three column blocks
    (the up block then stands that many blocks ahead of the gate block). In
    float32 nothing is rounded between the product and the activation, and
    the CPU's matmul sums a row in an order that follows the block's width:
    to `TOL` there."""
    experts, columns, held, tm, _ = REGIMES[regime]()
    inter, cut = ACT_BLOCKS[blocks]
    p = moe.plan(experts, columns, tm, held=held)
    count = columns if held is None else held[1]
    k = 64
    ks = jax.random.split(jax.random.PRNGKey(len(regime) + inter), 2)
    lhs = jax.random.normal(ks[0], (len(p.row_token), k), dtype)
    rhs = (jax.random.normal(ks[1], (count, k, 2 * inter)) * k ** -0.5
           ).astype(dtype)
    if cut is not None:
        monkeypatch.setattr(moe, "_rhs_columns", lambda *_: cut)
    else:
        assert moe._rhs_columns(p.tm, k, inter, lhs.dtype.itemsize, 2) == inter
    got = moe.gmm(lhs, rhs, p, use_kernel=True, interpret=True, act=True)
    plain = moe.gmm(lhs, rhs, p, use_kernel=False, act=True)
    want = _product_then_activation(lhs, rhs, p, use_kernel=True,
                                    interpret=True)
    live = int(p.tiles_used[0]) * p.tm
    assert got.dtype == want.dtype == plain.dtype == dtype
    assert got.shape == want.shape == plain.shape == (len(p.row_token), inter)
    assert bool(jnp.isfinite(got[:live].astype(jnp.float32)).all())
    for made in (got, plain):
        if dtype == jnp.bfloat16:
            assert bool((made[:live] == want[:live]).all())
        else:
            np.testing.assert_allclose(np.asarray(made)[:live],
                                       np.asarray(want)[:live], atol=TOL,
                                       rtol=TOL)


def _fetches(index_map, grid, tile_expert, used):
    """Walk `grid` in the order Pallas does (last axis innermost) through one
    of the kernel's index maps: (steps at which the block's index differs
    from the step before, the first step among them; the indices)."""
    outer, inner = np.meshgrid(np.arange(grid[0]), np.arange(grid[1]),
                               indexing="ij")
    index = index_map(outer.reshape(-1), inner.reshape(-1),
                      jnp.asarray(tile_expert), jnp.asarray(used))
    index = np.stack([np.broadcast_to(np.asarray(i), outer.size)
                      for i in index], axis=1)
    changed = np.ones(outer.size, bool)
    changed[1:] = (index[1:] != index[:-1]).any(axis=1)
    return changed, index


def _parent_rhs_map(last):
    """The weight block's index as the parent's grid (tiles, column blocks)
    took it."""
    def rhs_map(t, j, tile_expert, used):
        skip = t >= used[0]
        tile = jnp.where(skip, jnp.maximum(used[0] - 1, 0), t)
        return (tile_expert[tile], 0, jnp.where(skip, last, j))
    return rhs_map


@pytest.mark.parametrize("call", ["plain", "act"])
@pytest.mark.parametrize("regime", list(REGIMES))
@pytest.mark.parametrize("cols", [1, 4])
def test_an_expert_is_fetched_once_a_column_block(regime, cols, call):
    """The grid walked through the kernel's own index maps, no chip: a
    weight block is fetched where its index differs from the step before.
    Fetches = experts touched x column blocks, however many tiles an expert
    owns; a skipped step fetches no weights and no rows and writes no block
    of its own; and where every expert owns one tile the parent's order
    fetched as many. The call that writes the activation takes a second
    weight block a step, `cols` column blocks ahead of the first (the
    expert's up columns behind its gate columns), fetched where the first
    is and nowhere else."""
    experts, columns, held, tm, tiles_each = REGIMES[regime]()
    p = moe.plan(experts, columns, tm, held=held)
    tile_expert, used = np.asarray(p.tile_expert), np.asarray(p.tiles_used)
    tiles, touched = len(tile_expert), int((np.asarray(p.sizes) > 0).sum())
    grid = (cols, tiles)
    rhs, rhs_index = _fetches(moe._rhs_map, grid, tile_expert, used)
    if call == "act":
        up, up_index = _fetches(functools.partial(moe._up_map, cols), grid,
                                tile_expert, used)
        assert (up == rhs).all()
        assert (up_index[:, :2] == rhs_index[:, :2]).all()
        assert (up_index[:, 2] == rhs_index[:, 2] + cols).all()
        assert up_index[:, 2].max() < 2 * cols
    lhs, _ = _fetches(moe._lhs_map, grid, tile_expert, used)
    out, out_index = _fetches(moe._out_map, grid, tile_expert, used)
    skipped = np.tile(np.arange(tiles) >= used[0], cols)
    skipped[0] = False            # (the first step fetches, whatever it is)
    assert not (rhs | lhs | out)[skipped].any()
    # (a share with no tile in use: one block, fetched at the first step)
    assert rhs.sum() == max(touched * cols, 1)
    assert lhs.sum() == out.sum() == max(int(used[0]) * cols, 1)
    work = ~np.tile(np.arange(tiles) >= used[0], cols)
    # every step that works takes its own tile's expert and its own column
    assert (rhs_index[work, 0] == np.tile(tile_expert, cols)[work]).all()
    assert (rhs_index[work, 2] == np.repeat(np.arange(cols), tiles)[work]
            ).all()
    assert len({tuple(i) for i in out_index[work]}) == work.sum()
    parent, _ = _fetches(_parent_rhs_map(cols - 1), (tiles, cols),
                         tile_expert, used)
    assert rhs.sum() <= parent.sum()
    if tiles_each is not None and tiles_each > 1 and cols > 1:
        assert parent.sum() == max(int(used[0]), 1) * cols > rhs.sum()


# ---------------------------------------------------------------------------
# The layout a tile at a time, against the layout a row at a time
# ---------------------------------------------------------------------------
def _plan_per_row(experts, num_experts, tm=None, held=None):
    """The plain oracle: `plan` as it stood before it was built a tile at a
    time. Every one of the padded rows looks up its tile's expert, that
    expert's first row, size and first sorted place, and its own assignment:
    five gathers indexed by the `tiles * tm` rows. A padding row repeats its
    expert's last row."""
    t, k = experts.shape
    a = t * k
    flat = experts.reshape(a)
    tm = tm or moe.tile_rows(a, num_experts)
    bins = num_experts
    if held is not None:
        first, num_experts = held
        local = flat - first
        here = (local >= 0) & (local < num_experts)
        flat, bins = jnp.where(here, local, num_experts), num_experts + 1
    tiles = (a + min(a, num_experts) * (tm - 1) + tm - 1) // tm
    order = jnp.argsort(flat, stable=True)
    place = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32))
    sizes = jnp.zeros((bins,), jnp.int32).at[flat].add(1)[:num_experts]
    starts = jnp.cumsum(sizes) - sizes
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    pstarts = ends - padded
    tiles_used = ends[-1:] // tm
    tile = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), tiles_used - 1)
    tile_expert = jnp.searchsorted(ends, tile * tm, side="right").astype(
        jnp.int32)
    row = jnp.arange(tiles * tm, dtype=jnp.int32)
    e = tile_expert[row // tm]
    within = jnp.minimum(row - pstarts[e], sizes[e] - 1)
    row_token = order[jnp.clip(starts[e] + within, 0, a - 1)] // k
    dest = pstarts[flat] + place - starts[flat]
    if held is not None:
        dest = jnp.where(here, dest, -1)
    return moe.Plan(tm, row_token, dest.reshape(t, k), tile_expert,
                    tiles_used, sizes)


def _routed(tokens, k, columns, skewed, seed=0):
    """[tokens, k] distinct experts a token. Uniform: the top k of seeded
    logits. Skewed: the logits fall with the expert's number (the first few
    take most rows) and experts 5 and `columns - 1` are chosen by nobody."""
    logits = jax.random.normal(jax.random.PRNGKey(seed + tokens),
                               (tokens, columns))
    if skewed:
        logits = logits - 2.0 * jnp.log1p(
            jnp.arange(columns, dtype=jnp.float32))
        logits = logits.at[:, 5].set(-jnp.inf).at[:, -1].set(-jnp.inf)
    return jax.lax.top_k(logits, k)[1].astype(jnp.int32)


def _share(columns, held):
    """The middle half of the router's columns, or all of them."""
    return (columns // 4, columns // 2) if held else None


@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "skewed"])
@pytest.mark.parametrize("held", [False, True], ids=["all", "held"])
@pytest.mark.parametrize("columns", [36, 64, 72, 128])
@pytest.mark.parametrize("k", [1, 8, 10])
@pytest.mark.parametrize("tokens", [1, 8, 64, 300, 2048])
def test_plan_a_tile_at_a_time_is_the_plan_a_row_at_a_time(tokens, k, columns,
                                                           held, skewed):
    """`dest`, `tile_expert`, `tiles_used` and `sizes` element for element;
    `row_token` at every row some `dest` names, and some token's number at
    every other (nothing reads a padding row's product, but it is computed:
    it must be of real activations)."""
    experts = _routed(tokens, k, columns, skewed)
    share = _share(columns, held)
    # (jitted: one compilation a layout, not one an operation)
    got, want = (jax.jit(functools.partial(fn, num_experts=columns,
                                           held=share))(experts)
                 for fn in (moe.plan, _plan_per_row))
    assert got.tm == want.tm
    for name in ("dest", "tile_expert", "tiles_used", "sizes"):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert (a == b).all(), name
    rows, dest = np.asarray(got.row_token), np.asarray(want.dest)
    assert got.row_token.dtype == want.row_token.dtype
    assert rows.shape == want.row_token.shape
    named = dest[dest >= 0]
    assert (rows[named] == np.asarray(want.row_token)[named]).all()
    assert rows.min() >= 0 and rows.max() < tokens
    if skewed and columns > k + 2:
        sizes = np.asarray(got.sizes)
        first = share[0] if held else 0
        # the experts nobody chose, where this share holds them, own no tile
        for e in (5 - first, columns - 1 - first):
            if 0 <= e < len(sizes):
                assert sizes[e] == 0
                assert e not in set(np.asarray(got.tile_expert))


# (tokens, k, router columns, held): Mellum's and SDAR's decode, Granite's
# decode and a short prefill on a share, a prefill combined in blocks
LAYERS = [(8, 8, 64, False), (64, 8, 128, False), (8, 10, 72, True),
          (300, 10, 72, True), (2048, 8, 64, False), (2048, 10, 36, True)]


def _layer_inputs(tokens, k, columns, held):
    """Seeded bf16 activations and stacks of a layer, its float32 router and
    the share of the columns it holds."""
    share = _share(columns, held)
    count = share[1] if held else columns
    ks = jax.random.split(jax.random.PRNGKey(tokens + columns), 4)
    x = jax.random.normal(ks[0], (tokens, H), jnp.bfloat16)
    router = jax.random.normal(ks[1], (H, columns)) * 4 * H ** -0.5
    gate_up = (jax.random.normal(ks[2], (count, H, 2 * I)) * H ** -0.5
               ).astype(jnp.bfloat16)
    down = (jax.random.normal(ks[3], (count, I, H)) * I ** -0.5
            ).astype(jnp.bfloat16)
    return x, router, gate_up, down, share


@pytest.mark.parametrize("through", ["einsum", "kernel"])
@pytest.mark.parametrize("tokens,k,columns,held", LAYERS)
def test_layer_is_the_per_row_planned_layer_bit_for_bit(tokens, k, columns,
                                                        held, through,
                                                        monkeypatch):
    """A row's product depends on its own row and its tile's expert alone, so
    what a padding row holds reaches no output: the layer over the new layout
    is the layer over the old one, every float, through the einsum and
    through the kernel (interpret mode)."""
    x, router, gate_up, down, share = _layer_inputs(tokens, k, columns, held)
    run = lambda: moe.moe_layer(x, router, gate_up, down, k,
                                use_kernel=through == "kernel",
                                interpret=True, held=share)
    got, load = run()
    monkeypatch.setattr(moe, "plan", _plan_per_row)
    want, load_want = run()
    assert got.dtype == want.dtype and got.shape == want.shape == x.shape
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert bool((got == want).all())
    assert [int(v) for v in load] == [int(v) for v in load_want]


@pytest.mark.parametrize("through", ["einsum", "kernel"])
@pytest.mark.parametrize("tokens,k,columns,held", LAYERS)
def test_layer_is_the_layer_with_the_activation_outside_bit_for_bit(
        tokens, k, columns, held, through, monkeypatch):
    """The layer whose gate-and-up call writes the activation against the
    layer that stored the product, converted it whole and activated it in a
    pass of its own: same dtypes, same rounding points, every float."""
    x, router, gate_up, down, share = _layer_inputs(tokens, k, columns, held)
    run = lambda: moe.moe_layer(x, router, gate_up, down, k,
                                use_kernel=through == "kernel",
                                interpret=True, held=share)
    got, load = run()
    gmm, calls = moe.gmm, []

    def outside(lhs, rhs, p, act=False, **how):
        calls.append(act)
        return (_product_then_activation(lhs, rhs, p, gmm, **how) if act
                else gmm(lhs, rhs, p, **how))

    monkeypatch.setattr(moe, "gmm", outside)
    want, load_want = run()
    assert calls == ["swiglu", False]
    assert got.dtype == want.dtype and got.shape == want.shape == x.shape
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert bool((got == want).all())
    assert [int(v) for v in load] == [int(v) for v in load_want]


# ---------------------------------------------------------------------------
# The census: what `plan` asks of the compiler at the shapes served
# ---------------------------------------------------------------------------
# name -> (tokens, k, router columns, held, the padded rows)
SERVED = {
    "mellum_decode": (8, 8, 64, None, 1024),
    "sdar_forward": (64, 8, 128, None, 2432),
    "granite_decode": (8, 10, 72, (0, 36), 624),
    "mellum_prefill": (4096, 8, 64, None, 40960),
}


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _per_row_work(fn, experts, rows):
    """The gathers and scatters of `fn`'s jaxpr that look up `rows` or more
    indices, and its loops that carry an array of `rows` or more elements."""
    found = []
    for eqn in _equations(jax.make_jaxpr(fn)(experts).jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            lookups = int(np.prod(eqn.invars[1].aval.shape[:-1],
                                  dtype=np.int64))
            if lookups >= rows:
                found.append((name, lookups))
        elif name in ("while", "scan"):
            carried = max(int(np.prod(v.aval.shape, dtype=np.int64))
                          for v in [*eqn.invars, *eqn.outvars])
            if carried >= rows:
                found.append((name, carried))
    return found


@pytest.mark.parametrize("shape", list(SERVED))
def test_plan_asks_for_no_gather_scatter_or_loop_over_the_padded_rows(shape):
    """The layout describes `tokens * k` assignments on `tiles` tiles: no
    index array as long as the padded rows (`tiles * tm`, sixteen times the
    assignments at Mellum's decode) may come back. The per-row oracle trips
    the same census five times, so the census sees what it is for."""
    tokens, k, columns, held, rows = SERVED[shape]
    experts = jnp.zeros((tokens, k), jnp.int32)
    new = functools.partial(moe.plan, num_experts=columns, held=held)
    old = functools.partial(_plan_per_row, num_experts=columns, held=held)
    assert jax.eval_shape(new, experts).row_token.shape == (rows,)
    assert rows > tokens * k
    assert _per_row_work(new, experts, rows) == []
    assert _per_row_work(old, experts, rows) == [("gather", rows)] * 5


# ---------------------------------------------------------------------------
# The rows moved once each way: the combine, and the census of the layer
# ---------------------------------------------------------------------------
def _combine_per_token(y, weights, dest):
    """The plain reference: token by token, its `k` rows in the order j = 0
    .. k-1, every product and sum rounded to float32; a -1 names no row."""
    y, weights, dest = (np.asarray(y, np.float32),
                        np.asarray(weights, np.float32), np.asarray(dest))
    out = np.zeros((dest.shape[0], y.shape[1]), np.float32)
    for t in range(dest.shape[0]):
        for j in range(dest.shape[1]):
            if dest[t, j] >= 0:
                out[t] = out[t] + weights[t, j] * y[dest[t, j]]
    return out


def _bf16_ulp(v):
    """The distance between neighbouring bfloat16 values at |v| (8 bits of
    significand; the smallest normal's below it)."""
    v = np.maximum(np.abs(np.asarray(v, np.float32)), 2.0 ** -126)
    return np.exp2(np.floor(np.log2(v)) - 7)


# LAYERS and a blocked prefill on a share: 2 * COMBINE_TOKENS tokens
COMBINED = LAYERS + [(2 * moe.COMBINE_TOKENS, 10, 72, True)]


@pytest.mark.parametrize("tokens,k,columns,held", COMBINED)
def test_combine_is_the_per_token_sum_within_one_bf16_ulp(tokens, k, columns,
                                                          held):
    """`combine` over a layout of `plan`'s, the rows no `dest` names NaN (an
    unused tile's row may hold anything): every output is the float32 sum
    of the token's own rows in the order of its choices, rounded once."""
    share = _share(columns, held)
    experts = _routed(tokens, k, columns, skewed=False)
    p = jax.jit(functools.partial(moe.plan, num_experts=columns,
                                  held=share))(experts)
    dest = np.asarray(p.dest)
    assert (dest < 0).any() == held
    rows = len(p.row_token)
    ks = jax.random.split(jax.random.PRNGKey(tokens + k), 2)
    y = np.array(jax.random.normal(ks[0], (rows, H)), np.float32)
    named = np.zeros(rows, bool)
    named[dest[dest >= 0]] = True
    y[~named] = np.nan
    y = jnp.asarray(y, jnp.bfloat16)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (tokens, k)), axis=-1)
    got = jax.jit(functools.partial(moe.combine, masked=held))(
        y, weights, p.dest)
    assert got.dtype == y.dtype and got.shape == (tokens, H)
    got = np.asarray(got, np.float32)
    want = _combine_per_token(y, weights, dest)
    assert np.isfinite(got).all()
    assert (np.abs(got - want) <= _bf16_ulp(want)).all()


def _layer_as_it_was(x, router, gate_up, down, top_k, use_kernel=None,
                     interpret=None, held=None):
    """The oracle of the census: `moe_layer`'s glue before the rows were
    moved once each way. Both gathers in `fill` mode, the chosen rows as
    [tokens, k, H] under an einsum."""
    num_experts, two_i = router.shape[1], gate_up.shape[2]
    weights, experts = moe.route(x, router, top_k)
    p = moe.plan(experts, num_experts, held=held)
    run = functools.partial(moe.gmm, p=p, use_kernel=use_kernel,
                            interpret=interpret)
    gu = run(jnp.take(x, p.row_token, axis=0), gate_up).astype(jnp.float32)
    act = jax.nn.silu(gu[:, :two_i // 2]) * gu[:, two_i // 2:]
    y = run(act.astype(x.dtype), down)

    def combine(w, dest):
        picked = jnp.take(y, dest, axis=0).astype(jnp.float32)
        if held is not None:
            picked = jnp.where((dest >= 0)[..., None], picked, 0.0)
        return jnp.einsum("tk,tkh->th", w, picked).astype(x.dtype)

    t, block = x.shape[0], 1024                 # (its blocks were of 1,024)
    if t > block and t % block == 0:
        blocks = lambda a: a.reshape(t // block, block, -1)
        return jax.lax.map(lambda b: combine(*b),
                           (blocks(weights), blocks(p.dest))).reshape(x.shape)
    return combine(weights, p.dest)


@pytest.mark.parametrize("tokens,k,columns,held", COMBINED)
def test_layer_is_the_layer_as_it_was_within_one_bf16_ulp(tokens, k, columns,
                                                          held):
    """Same routing, same rows, same precision at every step: the sum of a
    token's rows is taken in the order of its choices where the einsum took
    its own, so an output may move by its last bit and no further."""
    x, router, gate_up, down, share = _layer_inputs(tokens, k, columns, held)
    got, _ = jax.jit(functools.partial(moe.moe_layer, top_k=k, held=share)
                     )(x, router, gate_up, down)
    want = jax.jit(functools.partial(_layer_as_it_was, top_k=k, held=share)
                   )(x, router, gate_up, down)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert (np.abs(got - want) <= _bf16_ulp(want)).all()


def _row_traffic(fn, shape):
    """What a layer's jaxpr at a served shape asks for that moves the
    layout's rows more than once: gathers in `fill` mode (a second pass over
    their rows puts NaN where an index is out of bounds) and values of shape
    [tokens, k, H] (a block's tokens where the combine runs in blocks)."""
    tokens, k, columns, held, rows = SERVED[shape]
    count = columns if held is None else held[1]
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        fn, top_k=k, held=held, use_kernel=True, interpret=True))(
            s((tokens, H), jnp.bfloat16), s((H, columns), jnp.float32),
            s((count, H, 2 * I), jnp.bfloat16),
            s((count, I, H), jnp.bfloat16))
    blocks = {tokens, min(tokens, 1024), min(tokens, moe.COMBINE_TOKENS)}
    found, gathered = set(), set()
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name == "gather":
            gathered.add(eqn.outvars[0].aval.shape)
            if eqn.params["mode"] == jax.lax.GatherScatterMode.FILL_OR_DROP:
                found.add("fill")
        for v in eqn.outvars:
            if v.aval.shape in {(b, k, H) for b in blocks}:
                found.add("[tokens, k, H]")
    assert (rows, H) in gathered                # (the rows' gather is there)
    return found


@pytest.mark.parametrize("shape", list(SERVED))
def test_layer_moves_its_rows_once_each_way(shape):
    """The census of `moe_layer` at the shapes served; the layer as it was
    trips both, so the census sees what it is for."""
    assert _row_traffic(moe.moe_layer, shape) == set()
    assert _row_traffic(_layer_as_it_was, shape) == {"fill", "[tokens, k, H]"}
