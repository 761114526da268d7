"""A dropless mixture-of-experts feed-forward: every token goes through its
`top_k` experts, whatever the load (the Qwen3-MoE layer; reference: ray.llm
leaves the layer to vLLM's fused CUDA MoE, here it is plain functions a model
file calls).

    p = softmax(x @ router) in float32;  the top_k largest, divided by their
    sum;  y = sum_e w_e * down_e(silu(gate_e x) * up_e x)

(or, `route(scoring="sigmoid")`: sigmoid scores, chosen with a learned bias,
weighed without it, the sum scaled; or, `moe_layer(act="relu2")`: experts
without a gate, down_e(relu(up_e r)^2); or, `moe_layer(rows=)`: experts that
work on another array's rows than the one the router reads, a narrower latent
of the token)

TPU-first design: everything is static-shaped. The `T * top_k` assignments
are sorted by expert and laid out so that every expert's rows start on a tile
of `tm` rows (`Plan`): a tile then belongs to one expert, and the grouped
matmul (`gmm`, on the TPU the Pallas kernel `moe_gmm`) is a grid over tiles
whose weight block is picked by the tile's expert. An expert no token chose
owns no tile, so its weights are never read; any other's are read once a
call, however many tiles it owns: the tiles are walked innermost under a
fixed block of columns, an expert's tiles lie side by side, and a block whose
index does not change from one step to the next is not fetched again. The
block takes as many columns as VMEM holds (all of them at the widths served
so far). At decode (a few rows an expert) the kernel is bound by streaming
the experts' weights, so its tiles are short (16 rows); at prefill (hundreds
of rows an expert, tiles of 128) by the arithmetic of the tiles that share a
block.

The layout is built a tile at a time, from sorts, comparisons and selects:
the padded rows (`tiles * tm`, sixteen times the assignments at a decode step
of 8 rows) index nothing. On the TPU a gather of scalars costs 20 us for any
1,024 indices or fewer, a scatter runs an index at a time, and a gather of
windows becomes a loop of a step a window, where a sort of 32,768 pairs is
under 20 us: so `plan` has no scalar gather, no scatter and no loop, at any
shape.

Outside the two grouped matmuls the layer moves the layout's rows once each
way: the tokens' activations are gathered onto the rows by indices the
gather knows to be in bounds (a gather that does not is followed by a pass
that reads and rewrites all `[M, H]` rows to put NaN where an index would be
out: two thirds of its time), and the chosen rows come back through
`combine`: gathered `k`-major, a block of tokens at a time, into `k` whole
`[tokens, H]` slices that one fusion sums in float32 (a `[tokens, k, H]`
array, `k` rows on a bf16 tile's sixteen sublanes, costs a relayout). A
Pallas kernel that fetched a token's rows by DMA is not on offer: `y` lies in
HBM in tiles of eight rows, two rows a 32-bit word, and the chip's compiler
takes no slice of it that is not whole tiles.

The gate-and-up call writes the activation (`gmm(..., act=True)`; on the TPU
a kernel body of its own under the same name, `moe_gmm`: a trace's two calls
a layer differ by their results, `[M, I]` and `[M, H]`): a grid step takes
the tile's rows and two blocks of the one `gate_up` stack, the expert's gate
columns and the up columns that stand `I` further on, makes both float32
products in VMEM, rounds each to the rows' dtype where the product used to
be stored, and writes `silu(gate) * up` of the tile: `[M, I]`, what the down
call reads. The product `[M, 2I]`, its float32 copy and the activation's
pass over every padded row, used or not, are no arrays of the program (as
XLA passes they were an eighth to a quarter of a one-prompt layer, builder's
chip runs, PR 49; as the one fusion XLA allows, the activation's buffer lay
on top of both its neighbours, a wave's prefill 0.26-0.47 GiB higher:
compile, PR 46). An expert without a gate has one `up` stack and a body
of its own under the same name (`gmm(..., act="relu2")`): one block a step,
the product rounded, the ReLU squared in float32 and rounded once.

A chip that shares a layer with others by expert parallelism holds some of
the router's columns (`held = (first, count)`, static): routing stays over
all of them, an assignment to an expert that is held elsewhere gets no row
and no tile here, and the layer returns its own experts' part of the sum.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM the kernel may take (a v5e core has 128 MiB), and the share of it that
# a grid step's blocks may fill.
VMEM_LIMIT_BYTES = 48 * 2 ** 20
VMEM_BLOCKS_SHARE = 0.75
MIN_TILE_ROWS, MAX_TILE_ROWS = 16, 128
# Tokens whose chosen rows are gathered and summed at a time. Measured, not
# derived: a block of 256 tokens of Granite's (ten rows of 4,096 each: 21 MB
# gathered) runs in 0.40 ms a 1,024 tokens, where blocks of 512 and 1,024 (42
# and 84 MB) take 0.59 and 0.60 and blocks of 128 0.41 (builder's chip runs,
# PR 46); a wave's 16,384 tokens at once would be gigabytes.
COMBINE_TOKENS = 256


def route(x: jax.Array, router: jax.Array, top_k: int, dtype=jnp.float32,
          scoring: str = "softmax", bias: Optional[jax.Array] = None,
          scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """x [T,H], router [H,E] -> (weights [T,k] summing to `scale`, experts
    [T,k]). Scores, the choice and the renormalisation are computed in
    `dtype` (float32: a near-tie between two experts must not be decided by
    the activations' rounding). `scoring` "softmax": the top_k largest
    probabilities. "sigmoid" (bias-balanced routing): an expert's score is
    sigmoid(logit), the top_k largest of score + `bias` [E] are chosen, and
    the chosen weigh by their scores without it."""
    logits = jnp.dot(x.astype(dtype), router.astype(dtype),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            scores if bias is None else scores + bias.astype(dtype), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        raise ValueError(f"route: scoring {scoring!r}")
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


class Plan(NamedTuple):
    """Where each assignment's row lies among the experts' tiles."""
    tm: int                 # rows a tile
    row_token: jax.Array    # [M] the token whose activations row r holds
    dest: jax.Array         # [T,k] the row of token t's j-th assignment
    tile_expert: jax.Array  # [M/tm] the expert whose weights tile i takes
    tiles_used: jax.Array   # [1] tiles that hold a row; the rest are skipped
    sizes: jax.Array        # [E] rows an expert (of those held) got


class Load(NamedTuple):
    """What one call of the layer routed, int32 scalars."""
    touched: jax.Array      # experts held here that some token chose
    fullest: jax.Array      # rows of the fullest of them
    rows_held: jax.Array    # assignments that fell on experts held here
    rows_routed: jax.Array  # all assignments: tokens * top_k
    tiles: jax.Array        # tiles in use (over `touched`: tiles a weight read)


def tile_rows(assignments: int, num_experts: int) -> int:
    """Rows a tile: the mean load of an expert, between 16 (a bf16 tile's
    sublanes) and 128."""
    tm = MIN_TILE_ROWS
    while tm < assignments // num_experts and tm < MAX_TILE_ROWS:
        tm *= 2
    return tm


def plan(experts: jax.Array, num_experts: int, tm: Optional[int] = None,
         held: Optional[Tuple[int, int]] = None) -> Plan:
    """experts [T,k] -> the tile-aligned layout. Its length is static: every
    expert's rows rounded up to whole tiles cannot pass `T*k + E*(tm-1)`.
    `held = (first, count)`: only the assignments to experts first ..
    first+count-1 of the `num_experts` get a row (`dest` of another is -1);
    tiles and `sizes` are over those `count`, numbered from 0 as the weight
    stacks hold them. A tile's rows are `tm` assignments in sorted order from
    its first, so a padding row (one no `dest` names; nothing reads its
    product) holds the token of an assignment that follows its expert's last
    (token 0 behind the last of all): always some token's activations."""
    t, k = experts.shape
    a = t * k
    flat = experts.reshape(a)
    # (the mean rows of an expert are those over all the router's columns)
    tm = tm or tile_rows(a, num_experts)
    if held is not None:
        first, num_experts = held
        local = flat - first
        here = (local >= 0) & (local < num_experts)
        # An absent expert's assignments sort behind every held one's.
        flat = jnp.where(here, local, num_experts)
    tiles = (a + min(a, num_experts) * (tm - 1) + tm - 1) // tm
    order = jnp.argsort(flat, stable=True)  # sorted place -> assignment
    place = jnp.argsort(order)              # assignment -> sorted place
    chose = flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32)  # [a,E]
    sizes = jnp.sum(chose, axis=0, dtype=jnp.int32)
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    # Rows of padding before an expert's first row: what a row's index is
    # ahead of its assignment's sorted place.
    ahead = ends - padded - (jnp.cumsum(sizes) - sizes)
    dest = place + jnp.sum(jnp.where(chose, ahead, 0), axis=1)
    if held is not None:
        dest = jnp.where(here, dest, -1)
    tiles_used = ends[-1:] // tm
    # A tile past the last used one repeats it: the kernel skips it, and its
    # blocks are the ones already in VMEM.
    tile = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), tiles_used - 1)
    # The experts whose rows end at or before a tile's first are those before
    # its own, and their padding is what the tile's first row is ahead by.
    before = ends <= (tile * tm)[:, None]                         # [tiles,E]
    tile_expert = jnp.sum(before, axis=1, dtype=jnp.int32)
    start = jnp.clip(
        tile * tm - jnp.sum(jnp.where(before, padded - sizes, 0), axis=1),
        0, a - 1)                          # the tile's first sorted place
    # A tile's rows are the `tm` sorted assignments from its start: a window
    # that lies in two neighbouring blocks of `tm`. One lookup a tile takes
    # the pair, and the window is shifted to its front by the bits of its
    # offset, a select each.
    token = jnp.pad(order // k, (0, -a % tm + tm)).reshape(-1, tm)
    window = jnp.concatenate([token[:-1], token[1:]], axis=1)[start // tm]
    offset, shift = (start % tm)[:, None], 1
    while shift < tm:
        window = jnp.where((offset & shift) != 0,
                           jnp.roll(window, -shift, axis=1), window)
        shift *= 2
    row_token = window[:, :tm].reshape(tiles * tm)
    return Plan(tm, row_token, dest.reshape(t, k), tile_expert, tiles_used,
                sizes)


def _gmm_kernel(tile_expert_ref, tiles_used_ref, lhs_ref, rhs_ref, out_ref):
    @pl.when(pl.program_id(1) < tiles_used_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """silu(gate) * up in float32, rounded once to the operands' dtype."""
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
            ).astype(gate.dtype)


def _gmm_act_kernel(tile_expert_ref, tiles_used_ref, lhs_ref, gate_ref,
                    up_ref, out_ref):
    @pl.when(pl.program_id(1) < tiles_used_ref[0])
    def _():
        rows = lhs_ref[...]
        # (each product rounded as the call that stored it rounded it)
        gate, up = (jnp.dot(rows, ref[0], preferred_element_type=jnp.float32)
                    .astype(out_ref.dtype) for ref in (gate_ref, up_ref))
        out_ref[...] = _swiglu(gate, up)


def _relu2(up: jax.Array) -> jax.Array:
    """relu(up)^2 in float32, rounded once to the operand's dtype."""
    r = jnp.maximum(up.astype(jnp.float32), 0.0)
    return (r * r).astype(up.dtype)


def _gmm_relu2_kernel(tile_expert_ref, tiles_used_ref, lhs_ref, rhs_ref,
                      out_ref):
    @pl.when(pl.program_id(1) < tiles_used_ref[0])
    def _():
        # (the product rounded as the call that stored it rounded it)
        out_ref[...] = _relu2(jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype))


def _rhs_columns(tm: int, k: int, n: int, itemsize: int,
                 blocks: int = 1) -> int:
    """Columns of a weight block [k, tn]: the most that divide `n`, are whole
    lanes and leave a grid step's blocks (`blocks` of the weights, the tile's
    rows and its output, each double-buffered, and a float32 product a weight
    block) within the kernel's share of VMEM; all of `n` where it has no
    whole lanes. The more columns, the fewer times the rows are read: once a
    block of columns."""
    def step_bytes(tn):
        return (2 * (blocks * k * tn + tm * k + tm * tn) * itemsize
                + blocks * 4 * tm * tn)

    whole = [tn for tn in range(128, n + 1, 128) if n % tn == 0] or [n]
    fit = [tn for tn in whole
           if step_bytes(tn) <= VMEM_BLOCKS_SHARE * VMEM_LIMIT_BYTES]
    return max(fit, default=whole[0])


# The block each array takes at grid step (column block j, tile t), the tiles
# innermost: `Plan.tile_expert` does not decrease, so under one column block
# the weight block's index changes only where the expert does, and every
# expert some token chose is fetched once a column block.
def _held(j, t, used):
    """(tile, column block) whose blocks step (j, t) takes: its own, or for a
    skipped tile (t >= tiles in use) those of the last tile in use, which the
    step before it left in VMEM: it fetches nothing and writes nothing new."""
    # (no tile at all in use, a share none of whose experts was chosen: every
    # step takes one block, fetched once)
    return (jnp.clip(t, 0, jnp.maximum(used[0] - 1, 0)),
            jnp.where(used[0] > 0, j, 0))


def _lhs_map(j, t, tile_expert, used):
    return (_held(j, t, used)[0], 0)


def _rhs_map(j, t, tile_expert, used):
    tile, col = _held(j, t, used)
    return (tile_expert[tile], 0, col)


def _up_map(ahead, j, t, tile_expert, used):
    """The second weight block of a call that takes an expert's gate columns
    and its up columns: the same expert's, `ahead` column blocks further on
    (so it changes where the first does, and is fetched as often)."""
    expert, _, col = _rhs_map(j, t, tile_expert, used)
    return (expert, 0, col + ahead)


def _out_map(j, t, tile_expert, used):
    return _held(j, t, used)


def gmm(lhs: jax.Array, rhs: jax.Array, p: Plan,
        use_kernel: Optional[bool] = None,
        interpret: Optional[bool] = None,
        act: Optional[str] = None) -> jax.Array:
    """Grouped matmul: lhs [M,K] (rows as `p` lays them), rhs [E,K,N] ->
    [M,N], row r times the weights of its tile's expert. Rows of unused tiles
    are left as they are (nothing reads them). On the TPU a Pallas kernel,
    grid (column blocks, tiles), one `dot` of a tile's rows [tm, K] with a
    block [K, tn] a step; elsewhere one batched einsum over tiles.

    `act` "swiglu" (or True, as its callers wrote it while it was the only
    one): rhs is a gate-and-up stack [E,K,2I] and the call returns the
    activation [M,I], silu(gate) * up of the two halves of the product, each
    rounded to the rows' dtype first (the product as a call without `act`
    returns it) and multiplied in float32. The kernel takes two blocks
    [K, tn] of the stack a step, column block j of `I` and the one I / tn
    further on, and the product is never an array. `act` "relu2": rhs is an
    up stack [E,K,I] and the call returns relu(product)^2 [M,I], the product
    rounded first and squared in float32."""
    act = {False: None, True: "swiglu"}.get(act, act)
    if act not in (None, "swiglu", "relu2"):
        raise ValueError(f"gmm: act {act!r}")
    gated = act == "swiglu"
    m, k = lhs.shape
    n = rhs.shape[2] // 2 if gated else rhs.shape[2]
    tm = p.tm
    tiles = m // tm
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        out = jnp.einsum("tmk,tkn->tmn", lhs.reshape(tiles, tm, k),
                         jnp.take(rhs, p.tile_expert, axis=0),
                         preferred_element_type=jnp.float32)
        out = out.reshape(m, -1).astype(lhs.dtype)
        if gated:
            return _swiglu(out[:, :n], out[:, n:])
        return _relu2(out) if act else out
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tn = _rhs_columns(tm, k, n, rhs.dtype.itemsize, 2 if gated else 1)
    weights = [pl.BlockSpec((1, k, tn), _rhs_map)]
    if gated:
        weights.append(pl.BlockSpec((1, k, tn),
                                    functools.partial(_up_map, n // tn)))
    return pl.pallas_call(
        (_gmm_act_kernel if gated else
         _gmm_relu2_kernel if act else _gmm_kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[pl.BlockSpec((tm, k), _lhs_map), *weights],
            out_specs=pl.BlockSpec((tm, tn), _out_map)),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )(p.tile_expert, p.tiles_used, lhs, *[rhs] * len(weights))


@functools.partial(jax.jit, static_argnames="masked")
def combine(y: jax.Array, weights: jax.Array, dest: jax.Array,
            masked: bool = False) -> jax.Array:
    """y [M,H] (the rows as `Plan` lays them), weights [T,k] float32, dest
    [T,k] -> [T,H] in y's dtype: token t's sum over j of weights[t,j] *
    y[dest[t,j]], in float32 in the order j = 0 .. k-1, rounded once.

    The chosen rows are gathered `k`-major, every token's j-th row side by
    side: [k * tokens, H] is then `k` whole arrays of [tokens, H] (a view,
    no relayout: where [tokens, k, H] puts a token's `k` rows on a bf16
    tile's sixteen sublanes, ten of them do not fill it), each converted
    inside the one fusion that sums them. One gather a block whatever `k`
    (at a decode step's 8 tokens ten gathers would cost ten launches).
    `masked`: a `dest` of -1 (an assignment whose expert is held elsewhere)
    names no row and nothing is read into the sum for it, whatever the row
    its clamped index names holds (an unused tile's is never written).
    Every other index lies in [0, M) by construction: the gather says so
    (`clip`), and no second pass over its rows fills in for an index out
    of bounds. Jitted, so that a program's layers trace it once (its `k`
    slices a layer, traced ten times over, were 0.6 s of a program's
    set-up)."""
    def block(w, dest):
        t, k = dest.shape
        index = jnp.maximum(dest, 0) if masked else dest
        picked = jnp.take(y, index.T.reshape(k * t), axis=0, mode="clip")
        out = None
        for j in range(k):
            row = picked[j * t:(j + 1) * t].astype(jnp.float32)
            if masked:
                row = jnp.where((dest[:, j] >= 0)[:, None], row, 0.0)
            term = w[:, j, None] * row
            out = term if out is None else out + term
        return out.astype(y.dtype)

    t = dest.shape[0]
    if t > COMBINE_TOKENS and t % COMBINE_TOKENS == 0:
        blocks = lambda a: a.reshape(t // COMBINE_TOKENS, COMBINE_TOKENS, -1)
        return jax.lax.map(lambda b: block(*b),
                           (blocks(weights), blocks(dest))).reshape(t, -1)
    return block(weights, dest)


def moe_layer(x: jax.Array, router: jax.Array, up: jax.Array,
              down: jax.Array, top_k: int,
              use_kernel: Optional[bool] = None,
              interpret: Optional[bool] = None,
              held: Optional[Tuple[int, int]] = None, act: str = "swiglu",
              rows: Optional[jax.Array] = None, **routing):
    """The layer over x [T,H]: router [H,E] float32, up [E,K,2I] (an
    expert's gate columns, then its up columns; `act` "relu2": [E,K,I], an
    expert down(relu(up r)^2)), down [E,I,K]. The experts' rows are x's (K =
    H), or those of `rows` [T,K], another array of the same tokens (a latent
    of theirs: the router still reads x). Returns (y [T,K], `Load` of this
    call). Two grouped matmuls: the up call returns the rows' activation
    [M,I] (`gmm(..., act=)`), which the down call takes as it is. With `held
    = (first, count)` the stacks are [count, ...], the experts of the
    router's columns first .. first+count-1, and y is their part of the sum:
    what a token's other chosen experts would add is computed where they are
    held. `routing`: `route`'s `scoring`, `bias` and `scale`."""
    num_experts = router.shape[1]
    count = num_experts if held is None else held[1]
    if up.shape[0] != count or down.shape[0] != count:
        raise ValueError(f"moe_layer: stacks of {up.shape[0]} and "
                         f"{down.shape[0]} experts where {count} are held")
    weights, experts = route(x, router, top_k, **routing)
    p = plan(experts, num_experts, held=held)
    run = functools.partial(gmm, p=p, use_kernel=use_kernel,
                            interpret=interpret)
    # (`row_token` lies in [0, T) by construction: the gather says so, and no
    # pass over the rows it took puts NaN where an index would be out)
    rows = jnp.take(x if rows is None else rows, p.row_token, axis=0,
                    mode="clip")
    y = run(run(rows, up, act=act), down)                   # [M,I] -> [M,K]
    out = combine(y, weights, p.dest, masked=held is not None)
    t = x.shape[0]
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    return out, Load(i32(jnp.sum(p.sizes > 0)), i32(jnp.max(p.sizes)),
                     i32(jnp.sum(p.sizes)), i32(t * top_k),
                     i32(p.tiles_used[0]))
