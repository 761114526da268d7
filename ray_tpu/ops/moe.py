"""A dropless mixture-of-experts feed-forward: every token goes through its
`top_k` experts, whatever the load (the Qwen3-MoE layer; reference: ray.llm
leaves the layer to vLLM's fused CUDA MoE, here it is plain functions a model
file calls).

    p = softmax(x @ router) in float32;  the top_k largest, divided by their
    sum;  y = sum_e w_e * down_e(silu(gate_e x) * up_e x)

TPU-first design: everything is static-shaped. The `T * top_k` assignments
are sorted by expert and laid out so that every expert's rows start on a tile
of `tm` rows (`Plan`): a tile then belongs to one expert, and the grouped
matmul (`gmm`, on the TPU the Pallas kernel `moe_gmm`) is a grid over tiles
whose weight block is picked by the tile's expert. An expert no token chose
owns no tile, so its weights are never read; one that got up to `tm` rows is
read once. At decode (a few rows an expert) the kernel is bound by streaming
the experts' weights, so its tiles are short (16 rows) and its weight blocks
large (3 MiB).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Bytes of one weight block the kernel streams (double-buffered in VMEM).
RHS_BLOCK_BYTES = 3 * 2 ** 20
MIN_TILE_ROWS, MAX_TILE_ROWS = 16, 128


def route(x: jax.Array, router: jax.Array, top_k: int,
          dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """x [T,H], router [H,E] -> (weights [T,k] summing to one, experts [T,k]).
    Probabilities, the choice and the renormalisation are computed in `dtype`
    (float32: a near-tie between two experts must not be decided by the
    activations' rounding)."""
    logits = jnp.dot(x.astype(dtype), router.astype(dtype),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


class Plan(NamedTuple):
    """Where each assignment's row lies among the experts' tiles."""
    tm: int                 # rows a tile
    row_token: jax.Array    # [M] the token whose activations row r holds
    dest: jax.Array         # [T,k] the row of token t's j-th assignment
    tile_expert: jax.Array  # [M/tm] the expert whose weights tile i takes
    tiles_used: jax.Array   # [1] tiles that hold a row; the rest are skipped
    sizes: jax.Array        # [E] rows an expert got


def tile_rows(assignments: int, num_experts: int) -> int:
    """Rows a tile: the mean load of an expert, between 16 (a bf16 tile's
    sublanes) and 128."""
    tm = MIN_TILE_ROWS
    while tm < assignments // num_experts and tm < MAX_TILE_ROWS:
        tm *= 2
    return tm


def plan(experts: jax.Array, num_experts: int,
         tm: Optional[int] = None) -> Plan:
    """experts [T,k] -> the tile-aligned layout. Its length is static: every
    expert's rows rounded up to whole tiles cannot pass `T*k + E*(tm-1)`."""
    t, k = experts.shape
    a = t * k
    tm = tm or tile_rows(a, num_experts)
    tiles = (a + min(a, num_experts) * (tm - 1) + tm - 1) // tm
    flat = experts.reshape(a)
    order = jnp.argsort(flat, stable=True)  # sorted place -> assignment
    place = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32))     # assignment -> sorted place
    sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    starts = jnp.cumsum(sizes) - sizes
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    pstarts = ends - padded
    tiles_used = ends[-1:] // tm
    # A tile past the last used one repeats it: the kernel skips it, and its
    # blocks are the ones already in VMEM.
    tile = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), tiles_used - 1)
    tile_expert = jnp.searchsorted(ends, tile * tm, side="right").astype(
        jnp.int32)
    row = jnp.arange(tiles * tm, dtype=jnp.int32)
    e = tile_expert[row // tm]
    within = jnp.minimum(row - pstarts[e], sizes[e] - 1)  # padding repeats
    row_token = order[jnp.clip(starts[e] + within, 0, a - 1)] // k
    dest = (pstarts[flat] + place - starts[flat]).reshape(t, k)
    return Plan(tm, row_token, dest, tile_expert, tiles_used, sizes)


def _gmm_kernel(tile_expert_ref, tiles_used_ref, lhs_ref, rhs_ref, out_ref):
    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _rhs_columns(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight block [k, tn]: the most that divide `n`, are whole
    lanes and keep the block within RHS_BLOCK_BYTES (all of `n` where it is
    small)."""
    fit = [tn for tn in range(128, n + 1, 128)
           if n % tn == 0 and k * tn * itemsize <= RHS_BLOCK_BYTES]
    return max(fit, default=n)


def gmm(lhs: jax.Array, rhs: jax.Array, p: Plan,
        use_kernel: Optional[bool] = None,
        interpret: Optional[bool] = None) -> jax.Array:
    """Grouped matmul: lhs [M,K] (rows as `p` lays them), rhs [E,K,N] ->
    [M,N], row r times the weights of its tile's expert. Rows of unused tiles
    are left as they are (nothing reads them). On the TPU a Pallas kernel,
    grid (tiles, column blocks); elsewhere one batched einsum over tiles."""
    m, k = lhs.shape
    _, _, n = rhs.shape
    tm = p.tm
    tiles = m // tm
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        out = jnp.einsum("tmk,tkn->tmn", lhs.reshape(tiles, tm, k),
                         jnp.take(rhs, p.tile_expert, axis=0),
                         preferred_element_type=jnp.float32)
        return out.reshape(m, n).astype(lhs.dtype)
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tn = _rhs_columns(k, n, rhs.dtype.itemsize)
    last = n // tn - 1

    def held(t, j, used):
        """(tile, column block) whose blocks step (t, j) takes: its own, or
        for a skipped tile those of the last step that did any work."""
        skip = t >= used[0]
        return (jnp.where(skip, used[0] - 1, t), jnp.where(skip, last, j))

    def lhs_map(t, j, tile_expert, used):
        return (held(t, j, used)[0], 0)

    def rhs_map(t, j, tile_expert, used):
        tile, col = held(t, j, used)
        return (tile_expert[tile], 0, col)

    def out_map(t, j, tile_expert, used):
        return held(t, j, used)

    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, n // tn),
            in_specs=[pl.BlockSpec((tm, k), lhs_map),
                      pl.BlockSpec((1, k, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map)),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
        name="moe_gmm",
    )(p.tile_expert, p.tiles_used, lhs, rhs)


def moe_layer(x: jax.Array, router: jax.Array, gate_up: jax.Array,
              down: jax.Array, top_k: int,
              use_kernel: Optional[bool] = None,
              interpret: Optional[bool] = None):
    """The layer over x [T,H]: router [H,E] float32, gate_up [E,H,2I] (an
    expert's gate columns, then its up columns), down [E,I,H]. Returns
    (y [T,H], (experts touched, rows of the fullest expert)), the pair as
    int32 scalars of this call."""
    num_experts, _, two_i = gate_up.shape
    weights, experts = route(x, router, top_k)
    p = plan(experts, num_experts)
    run = functools.partial(gmm, p=p, use_kernel=use_kernel,
                            interpret=interpret)
    gu = run(jnp.take(x, p.row_token, axis=0), gate_up).astype(jnp.float32)
    act = jax.nn.silu(gu[:, :two_i // 2]) * gu[:, two_i // 2:]
    y = run(act.astype(x.dtype), down)                      # [M,H]
    picked = jnp.take(y, p.dest, axis=0).astype(jnp.float32)  # [T,k,H]
    out = jnp.einsum("tk,tkh->th", weights, picked).astype(x.dtype)
    return out, (jnp.sum(p.sizes > 0).astype(jnp.int32),
                 jnp.max(p.sizes).astype(jnp.int32))
