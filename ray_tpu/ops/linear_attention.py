"""Gated delta rule (arXiv:2412.06464): the linear-attention layer whose
memory is a fixed state per sequence and head, not keys and values per token.

Per token t and head, with q, k in R^dk (k of unit norm), v in R^dv, a decay
alpha = exp(g) in (0, 1] and a write strength beta, on a state S in R^{dk x dv}
that is zero before the first token:

    S <- alpha S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

Two forms of the same mathematics, both in float32 (the tests hold them to
the recurrence above, token by token):

- `gdn_chunked`: the chunkwise form (section 3 of the paper, WY
  representation) for a whole padded bucket, in `jax.numpy`: within a chunk of
  64 tokens the 64 updates become one unit-triangular solve and a few matmuls,
  and only the chunk boundaries carry a state.
- `gdn_decode`: one token a row against the state pool. On a TPU one Pallas
  kernel (`name="gdn_decode"`) reads each state once and writes it once, in
  place; elsewhere `jax.numpy`.

The pool's layout is written here and nowhere else (`state_shape`, `pack`,
`unpack`): a slot's heads lie `p` side by side along the lanes, `p` from the
head count and the value width alone, so that a row fills whole 128-lane
tiles where a divisor of the heads allows (30 heads of 192 values: pairs,
384 lanes).

Lightning attention (arXiv:2401.04658) is the same memory under a simpler
rule: a constant decay lambda in (0, 1] a head and no delta correction,

    S <- lambda S + k^T v;   o = q S

`lightning_chunked` is its chunkwise form for a padded bucket and
`lightning_step` its one-token step against the state pool, both plain
`jax.numpy` in float32 (as `gdn_chunked` and `ops/ssm.py`'s steps are).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.paged_attention import LANES

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
# Largest state block (as laid out on 128-lane tiles) one kernel step holds;
# the pipeline keeps four of them (in and out, double-buffered).
_STATE_BLOCK_BYTES = 1 << 20


def gdn_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The same from a zero state, chunk by chunk. A position that must change
    nothing (padding) is given g = 0 and beta = 0 by the caller. q, k
    [B,S,H,dk], v [B,S,H,dv], g, beta [B,S,H] -> (o [B,S,H,dv], state
    [B,H,dk,dv]); any S (padded up to a multiple of `chunk` inside)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-s // chunk)
    pad = n * chunk - s

    def chunks(x):  # [B,S,H,...] -> [N,B,H,C,...]
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)  # log decay since the chunk began
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # decay[t, s] = exp(gamma_t - gamma_s) for s <= t; masked before the
    # exponential, which overflows above the diagonal.
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kb = k * beta[..., None]
    # (I + A) U = beta V - (beta K e^gamma) S0, A strictly lower triangular.
    a = jnp.einsum("nbhtk,nbhsk->nbhts", kb, k, precision=_HIGHEST) * decay
    a = jnp.where(jnp.tril(lower, -1), a, 0.0) + jnp.eye(chunk)
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * jnp.exp(gamma)[..., None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a, rhs, lower=True, unit_diagonal=True)
    u_own, k_carry = solved[..., :dv], solved[..., dv:]
    qk = jnp.einsum("nbhtk,nbhsk->nbhts", q, k, precision=_HIGHEST) * decay
    q_in = q * jnp.exp(gamma)[..., None]
    # What each token leaves in the state at the chunk's end.
    k_out = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    end = jnp.exp(gamma[..., -1])[..., None, None]

    def step(state, xs):
        u_own, k_carry, qk, q_in, k_out, end = xs
        u = u_own - jnp.einsum("bhtk,bhkv->bhtv", k_carry, state,
                               precision=_HIGHEST)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_in, state, precision=_HIGHEST)
             + jnp.einsum("bhts,bhsv->bhtv", qk, u, precision=_HIGHEST))
        state = state * end + jnp.einsum("bhtk,bhtv->bhkv", k_out, u,
                                         precision=_HIGHEST)
        return state, o

    state, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), jnp.float32),
        (u_own, k_carry, qk, q_in, k_out, end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)  # [B,N,C,H,dv]
    return o.reshape(b, n * chunk, h, dv)[:, :s], state


# ---------------------------------------------------------------------------
# The state pool: the one place its layout is written
# ---------------------------------------------------------------------------
def _laid_out(width: int) -> int:
    """The lanes a minor axis of `width` fills: whole tiles of 128."""
    return -(-width // LANES) * LANES


def heads_per_lane_block(h: int, dv: int) -> int:
    """How many heads `p` of a slot's state lie side by side along the lanes:
    the smallest divisor of `h` that leaves the fewest padded lanes (`dv`
    192, `h` 30 -> 2: 384 lanes and none padded; `dv` a multiple of 128 ->
    1)."""
    return min((p for p in range(1, h + 1) if h % p == 0),
               key=lambda p: (h // p * _laid_out(p * dv), p))


def state_shape(h: int, dk: int, dv: int) -> Tuple[int, int, int]:
    """A slot's row of the pool, `[h/p, dk, p*dv]` float32: head `p*i + j` is
    the lanes `[j*dv, (j+1)*dv)` of lane block `i`."""
    p = heads_per_lane_block(h, dv)
    return h // p, dk, p * dv


def pack(state):
    """`[B,H,dk,dv]`, a state a head, -> the pool's rows
    `[B, *state_shape(H, dk, dv)]`."""
    b, h, dk, dv = state.shape
    p = heads_per_lane_block(h, dv)
    if p == 1:
        return state
    return state.reshape(b, h // p, p, dk, dv).swapaxes(2, 3).reshape(
        b, h // p, dk, p * dv)


def unpack(rows, h: int):
    """The pool's rows `[B, H/p, dk, p*dv]` -> `[B,H,dk,dv]`."""
    b, blocks, dk, width = rows.shape
    p = h // blocks
    if p == 1:
        return rows
    return rows.reshape(b, blocks, dk, p, width // p).swapaxes(2, 3).reshape(
        b, h, dk, width // p)


# ---------------------------------------------------------------------------
# Decode: one token a row against the state pool
# ---------------------------------------------------------------------------
def _blocks_per_step(blocks: int, dk: int, width: int) -> int:
    """Lane blocks `[dk, width]` one kernel step holds: the most that divide
    a row's and fit `_STATE_BLOCK_BYTES` as laid out."""
    fit = max(1, _STATE_BLOCK_BYTES // (dk * _laid_out(width) * 4))
    return max(d for d in range(1, blocks + 1)
               if blocks % d == 0 and d <= fit)


def _gdn_decode_kernel(active_ref, cols_ref, rows_ref, s_ref, o_ref, s_out,
                       *, blocks: int, p: int):
    """Grid (rows, steps of `blocks` lane blocks). A lane block is `p` heads
    side by side, `dv` lanes each. cols [dk, 4*p*blocks]: per head the
    columns k, alpha*beta*k, q, alpha; rows [blocks, p*dv]: beta*v. With
    them
        u = beta v - S^T (alpha beta k);  S' = alpha S + k u^T;  o = S'^T q
    is the recurrence above, each state read once and written once. A lane
    takes its own head's columns (a select a column for `p` 2), so the sums
    down the sublanes never cross heads."""
    row = pl.program_id(0)
    dk, width = s_ref.shape[2:]
    dv = width // p

    @pl.when(active_ref[row] != 0)
    def _update():
        # below[j]: the lanes of heads 0..j of a lane block (none for p = 1)
        below = [jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
                 < (j + 1) * dv for j in range(p - 1)]

        def column(at):  # [dk, width]: lane l meets head l // dv's column
            head = lambda j: cols_ref[0, 0, :, at + 4 * j:at + 4 * j + 1]
            col = head(p - 1)
            for j in range(p - 2, -1, -1):
                col = jnp.where(below[j], head(j), col)
            return col

        for i in range(blocks):  # static: every slice is a constant
            s = s_ref[0, i]  # [dk, p*dv]
            k, kab, q, alpha = (column(4 * p * i + c) for c in range(4))
            u = rows_ref[0, 0, i:i + 1, :] - jnp.sum(
                s * kab, axis=0, keepdims=True)  # [1, p*dv]
            s = alpha * s + k * u
            s_out[0, i] = s
            o_ref[0, 0, i:i + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)

    @pl.when(active_ref[row] == 0)
    def _keep():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def gdn_decode_kernel(q, k, v, g, beta, state, active,
                      interpret: Optional[bool] = None):
    """q, k [B,H,dk], v [B,H,dv], g, beta [B,H] float32, state the pool's
    rows [B, *state_shape(H, dk, dv)] float32, active [B] bool -> (o
    [B,H,dv], state). The state is updated in place
    (`input_output_aliases`); an inactive row's is left as it was."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, h, dk = q.shape
    dv = v.shape[-1]
    blocks, _, width = state.shape[1:]
    p = h // blocks
    hb = _blocks_per_step(blocks, dk, width)
    nb = blocks // hb
    alpha = jnp.exp(g)
    cols = jnp.stack(
        [k, k * (alpha * beta)[..., None], q,
         jnp.broadcast_to(alpha[..., None], k.shape)], axis=-1)  # [B,H,dk,4]
    cols = cols.reshape(b, nb, hb * p, dk, 4).transpose(
        0, 1, 3, 2, 4).reshape(b, nb, dk, 4 * p * hb)
    rows = (v * beta[..., None]).reshape(b, nb, hb, width)
    o, state = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, blocks=hb, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, 1, dk, 4 * p * hb),
                             lambda r, c, act: (r, c, 0, 0)),
                pl.BlockSpec((1, 1, hb, width),
                             lambda r, c, act: (r, c, 0, 0)),
                pl.BlockSpec((1, hb, dk, width),
                             lambda r, c, act: (r, c, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, width),
                             lambda r, c, act: (r, c, 0, 0)),
                pl.BlockSpec((1, hb, dk, width),
                             lambda r, c, act: (r, c, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, nb, hb, width), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # Operand 3 (after the prefetched `active`) is the state: same buffer
        # in and out, so the pool is never copied.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_decode",
    )(active.astype(jnp.int32), cols, rows, state)
    return o.reshape(b, h, dv), state


def gdn_decode(q, k, v, g, beta, state, active,
               use_kernel: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """One token a row: shapes as `gdn_decode_kernel`. The Pallas kernel on a
    TPU; elsewhere `jax.numpy` on the unpacked rows, packed again (as
    `paged_attention`'s `use_kernel`)."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta = f32(q), f32(k), f32(v), f32(g), f32(beta)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return gdn_decode_kernel(q, k, v, g, beta, state, active)
    s = unpack(state, q.shape[1]) * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=_HIGHEST))
    s = s + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HIGHEST)
    return o, jnp.where(active[:, None, None, None], pack(s), state)


# ---------------------------------------------------------------------------
# Lightning attention: a constant decay a head
# ---------------------------------------------------------------------------
LIGHTNING_CHUNK = 128


def lightning_chunked(q, k, v, log_decay, lengths=None,
                      chunk: int = LIGHTNING_CHUNK):
    """o_t = sum_{j<=t} lambda^(t-j) (q_t . k_j) v_j from a zero state, and
    the state after a row's last position. q, k, v [B,S,H,D] (any scale on q
    is the caller's), log_decay [H] = log lambda <= 0, `lengths` [B]: a
    row's positions from there on are padding (their outputs mean nothing,
    and they neither decay nor write the state handed back); None: S. ->
    (o [B,S,H,D] float32, state [B,H,D,D] float32).

    Chunk c of C positions, of which n_c are not padding:
        O = ((Q K^T) * D) V + Lambda * (Q S_c),  D_ij = lambda^(i-j) (i >= j),
        Lambda_i = lambda^(i+1);
        S_(c+1) = lambda^(n_c) S_c + sum_(j<n_c) lambda^(n_c-1-j) k_j^T v_j
    Only non-negative powers of lambda, so nothing overflows. Everything a
    chunk needs but its incoming state is one batched product; the states
    are a linear recurrence over the chunks' sums."""
    b, s, h, d = q.shape
    n = -(-s // chunk)
    pad = n * chunk - s
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)

    def chunks(x):  # [B,S,H,D] -> [B,N,H,C,D]
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
        return x.reshape(b, n, chunk, h, d).transpose(0, 1, 3, 2, 4)

    q, k, v = chunks(q), chunks(k), chunks(v)
    log_decay = log_decay.astype(jnp.float32)[:, None]          # [H,1]
    at = jnp.arange(chunk, dtype=jnp.float32)
    # (masked before the exponential, which overflows above the diagonal)
    within = jnp.exp(jnp.where(
        at[:, None] >= at[None, :],
        log_decay[..., None] * (at[:, None] - at[None, :]), -jnp.inf))
    scores = jnp.einsum("bnhtd,bnhsd->bnhts", q, k, precision=_HIGHEST)
    own = jnp.einsum("bnhts,bnhsd->bnhtd", scores * within, v,
                     precision=_HIGHEST)
    # A chunk's positions that are not padding, [B,N]; what each leaves in
    # the state at the end of them.
    held = jnp.clip(lengths[:, None] - jnp.arange(n) * chunk, 0, chunk
                    ).astype(jnp.float32)
    left = held[..., None, None] - 1.0 - at                     # [B,N,1,C]
    weight = jnp.where(left >= 0, jnp.exp(log_decay * jnp.maximum(left, 0.0)),
                       0.0)                                     # [B,N,H,C]
    sums = jnp.einsum("bnhtk,bnhtv->bnhkv", k * weight[..., None], v,
                      precision=_HIGHEST)
    through = jnp.exp(log_decay[:, 0] * held[..., None])        # [B,N,H]

    def step(state, xs):
        kept, added = xs
        return state * kept[..., None, None] + added, state

    state, before = jax.lax.scan(
        step, jnp.zeros((b, h, d, d), jnp.float32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(sums, 1, 0)))
    carried = jnp.einsum("bnhtk,bnhkv->bnhtv", q, jnp.moveaxis(before, 0, 1),
                         precision=_HIGHEST)
    o = own + carried * jnp.exp(log_decay * (at + 1.0))[..., None]
    o = o.transpose(0, 1, 3, 2, 4).reshape(b, n * chunk, h, d)
    return o[:, :s], state


def lightning_step(q, k, v, log_decay, state, active):
    """One token a row against the state pool: S <- lambda S + k^T v, o = q S
    with the new S. q, k, v [B,H,D], log_decay [H], state [B,H,D,D] float32,
    active [B] bool (an inactive row's state stays) -> (o [B,H,D] float32,
    state). With the pool donated, the update is in place."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v = f32(q), f32(k), f32(v)
    new = (state * jnp.exp(f32(log_decay))[:, None, None]
           + k[..., :, None] * v[..., None, :])
    o = jnp.einsum("bhk,bhkv->bhv", q, new, precision=_HIGHEST)
    return o, jnp.where(active[:, None, None, None], new, state)
