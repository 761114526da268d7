"""The selective state space of a Mamba-1 layer (arXiv:2312.00752): a
diagonal recurrence per channel whose step, input and output maps depend on
the token,

    h_t = exp(dt_t * A) . h_{t-1} + (dt_t x_t) (x) B_t      h [N, D] float32
    y_t = h_t^T C_t + D . x_t,    out_t = y_t . silu(z_t)

with D channels (the layer's inner width), N states a channel, dt [L, D],
B and C [L, N]. Nothing here multiplies matrices: it is `exp`, multiply and
add over L * N * D elements, the vector units' work while the MXU waits.

Everywhere the channels are the minor axis: a state is [N, D], so the D
channels lie on the lanes and the N = 16 states on the sublanes ([D, N] would
pad 16 to 128 lanes and move eight times the bytes).

`ssm_scan` walks the positions of a prefill (a Pallas kernel on a TPU),
`ssm_step` is the one-token update of the engine's state pool, and
`causal_conv` the depthwise convolution in front of both with the tail it
leaves for the next token. Plain `jax.numpy` forms of the same run on the
CPU.

Mamba-2 (SSD, arXiv:2405.21060) is another recurrence: the channels come in
heads of P, a head has ONE scalar decay a token, and B and C come in G
groups, each shared by H / G heads in a row (head h reads group g = h // (H /
G); Granite has one group, Nemotron-H eight),

    S_t[h] = exp(dt_t[h] A[h]) . S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g] [P, N]
    y_t[h] = S_t[h] C_t[g] + D[h] . x_t[h]

so that a chunk of Q positions is matrix products (`ssd_scan`): with c_i the
running sum of dt A inside the chunk, Y = ((C B^T) o L) (dt x) + exp(c) .
(C S_0^T), L_ij = exp(c_i - c_j) for j <= i, and the chunk hands on S_Q =
exp(c_Q) S_0 + (exp(c_Q - c) dt x)^T B, C B^T once a group. A state is
[H, P, N] float32, the N states on the lanes. `ssd_step` is its one-token
update, `ssd_scan_plain` the recurrence token by token.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Positions one grid step of the kernel walks (its loop is unrolled: every
# slice is a constant) and channels a program holds: a state block [16, 512]
# float32 is 8 vector registers.
SCAN_CHUNK = 128
SCAN_CHANNELS = 512
# Mamba-2: positions of a chunk (the published `mamba_chunk_size`) and heads a
# grid step of `ssd_scan` takes (its loop over them is unrolled).
SSD_CHUNK = 256
SSD_HEADS = 8


def causal_conv(x: jax.Array, taps: jax.Array, bias: jax.Array,
                tail: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over positions, float32: x [B, S, D],
    taps [W, D] (taps[W-1] multiplies the position itself), bias [D];
    `tail` [B, W-1, D] holds the inputs before position 0 (zeros without).
    Returns (y [B, S, D] float32, window [B, W-1+S, D]): the caller cuts the
    next tail out of the window at the row's true length."""
    width = taps.shape[0]
    s = x.shape[1]
    if tail is None:
        window = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    else:
        window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    taps = taps.astype(jnp.float32)
    y = sum(window[:, j:j + s].astype(jnp.float32) * taps[j]
            for j in range(width))
    return y + bias.astype(jnp.float32), window


def ssm_scan_plain(x, dt, b, c, z, a, d, h0=None):
    """The recurrence token by token (`lax.scan`), float32: x, dt, z
    [B, L, D], b, c [B, L, N], a [N, D], d [D], h0 [B, N, D] or zero.
    Returns (out [B, L, D] float32, h_L [B, N, D])."""
    f32 = lambda t: t.astype(jnp.float32)
    x, dt, b, c, z = map(f32, (x, dt, b, c, z))
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], a.shape[0], x.shape[2]), jnp.float32)

    def token(h, xs):
        xt, dtt, bt, ct = xs  # [B, D], [B, D], [B, N], [B, N]
        h = (jnp.exp(dtt[:, None, :] * a) * h
             + (dtt * xt)[:, None, :] * bt[:, :, None])
        return h, jnp.sum(h * ct[:, :, None], axis=1)

    time_major = lambda t: jnp.swapaxes(t, 0, 1)
    h, y = jax.lax.scan(token, h0, tuple(map(time_major, (x, dt, b, c))))
    y = time_major(y) + d * x
    return y * jax.nn.silu(z), h


def _ssm_scan_kernel(lens_ref, x_ref, dt_ref, z_ref, bt_ref, ct_ref, a_ref,
                     d_ref, out_ref, h_ref, h_scr, dx_scr, y_scr, *,
                     chunk: int):
    """Grid (rows, channel blocks, chunks of positions), the chunks innermost
    and in order: the state block [N, bd] stays in VMEM from a row's first
    chunk to its last. A chunk past the row's length is skipped (its inputs
    are not fetched again: the index maps stop at the last chunk in use).
    Inside a chunk the positions are a static loop: position j reads row j
    of dt and dt*x (broadcast over the N sublanes) and column j of B^T and
    C^T [N, chunk] (broadcast over the lanes)."""
    r, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _zero():
        h_scr[...] = jnp.zeros_like(h_scr)

    @pl.when(t * chunk >= lens_ref[r])
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t * chunk < lens_ref[r])
    def _walk():
        a = a_ref[...]                                   # [N, bd]
        dx_scr[...] = dt_ref[0] * x_ref[0].astype(jnp.float32)
        h = h_scr[...]
        for j in range(chunk):
            h = (jnp.exp(dt_ref[0, j:j + 1, :] * a) * h
                 + dx_scr[j:j + 1, :] * bt_ref[0, 0, :, j:j + 1])
            y_scr[j:j + 1, :] = jnp.sum(h * ct_ref[0, 0, :, j:j + 1],
                                        axis=0, keepdims=True)
        h_scr[...] = h
        x = x_ref[0].astype(jnp.float32)                 # [chunk, bd]
        z = z_ref[0].astype(jnp.float32)
        out_ref[0] = ((y_scr[...] + d_ref[...] * x)
                      * (z * jax.nn.sigmoid(z))).astype(out_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _last():
        h_ref[0] = h_scr[...]


def ssm_scan_kernel(x, dt, b, c, z, a, d, lens, chunk: int = SCAN_CHUNK,
                    channels: int = SCAN_CHANNELS,
                    interpret: Optional[bool] = None):
    """Shapes as `ssm_scan`. Each of x, dt, z is read once and out written
    once; B and C (N floats a position) once a channel block."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows, length, width = x.shape
    n = a.shape[0]
    chunk = min(chunk, length)
    bd = min(channels, width)
    if length % chunk or width % bd:
        raise ValueError(f"ssm_scan: {length} positions in chunks of {chunk},"
                         f" {width} channels in blocks of {bd}")
    chunks = length // chunk
    # [rows, chunks, N, chunk]: a chunk's B^T, states on the sublanes.
    by_chunk = lambda m: m.astype(jnp.float32).reshape(
        rows, chunks, chunk, n).transpose(0, 1, 3, 2)

    def at(r, t, lens):  # chunk t, or the last one the row uses if past it
        last = jnp.maximum(jax.lax.div(lens[r] + chunk - 1, chunk) - 1, 0)
        return jnp.minimum(t, last)

    tile = pl.BlockSpec((1, chunk, bd),
                        lambda r, ci, t, lens: (r, at(r, t, lens), ci))
    cols = pl.BlockSpec((1, 1, n, chunk),
                        lambda r, ci, t, lens: (r, at(r, t, lens), 0, 0))
    out, h = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, width // bd, chunks),
            in_specs=[
                tile, tile, tile, cols, cols,
                pl.BlockSpec((n, bd), lambda r, ci, t, lens: (0, ci)),
                pl.BlockSpec((1, bd), lambda r, ci, t, lens: (0, ci)),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, bd),
                             lambda r, ci, t, lens: (r, t, ci)),
                pl.BlockSpec((1, n, bd), lambda r, ci, t, lens: (r, 0, ci)),
            ],
            scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32),
                            pltpu.VMEM((chunk, bd), jnp.float32),
                            pltpu.VMEM((chunk, bd), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((rows, n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(lens.astype(jnp.int32), x, dt.astype(jnp.float32), z, by_chunk(b),
      by_chunk(c), a.astype(jnp.float32),
      d.astype(jnp.float32).reshape(1, width))
    return out, h


def ssm_scan(x, dt, b, c, z, a, d, lens,
             use_kernel: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """A prefill's positions from a zero state: x, z [B, L, D] (the compute
    dtype), dt [B, L, D] float32, b, c [B, L, N], a [N, D] = -exp(A_log) and
    d [D] float32, lens [B] the rows' true lengths. A position at or past
    its row's length must come with dt = 0 and a finite x: it then changes
    nothing, and the kernel does not walk a chunk that holds only such.
    Returns (out [B, L, D] in x's dtype, the state after the last true
    position [B, N, D] float32). The Pallas kernel on a TPU, the plain form
    elsewhere (as `paged_attention`'s `use_kernel`)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return ssm_scan_kernel(x, dt, b, c, z, a, d, lens)
    out, h = ssm_scan_plain(x, dt, b, c, z, a, d)
    return out.astype(x.dtype), h


def ssm_step(x, dt, b, c, z, a, d, h, active):
    """One token a row on the state pool: x, dt, z [B, D], b, c [B, N],
    h [B, N, D] float32 (a row per engine slot), active [B] bool. Returns
    (out [B, D] float32, h): an inactive row's state is left as it was.
    Plain `jax.numpy`: on the donated pool XLA updates it in place
    (tests/test_tpu_compile.py counts the pool-sized copies: none)."""
    f32 = lambda t: t.astype(jnp.float32)
    x, dt, b, c, z = map(f32, (x, dt, b, c, z))
    new = (jnp.exp(dt[:, None, :] * a) * h
           + (dt * x)[:, None, :] * b[:, :, None])
    y = jnp.sum(new * c[:, :, None], axis=1) + d * x
    return (y * jax.nn.silu(z),
            jnp.where(active[:, None, None], new, h))


def _ssd_token(x, dt, b, c, a, s):
    """One position of the recurrence, float32: x [B, H, P], dt [B, H], b, c
    [B, G, N], a [H], s [B, H, P, N]. Returns (S_t [B, H, P, N], S_t C_t
    [B, H, P]). The heads that share a group lie side by side, so a group is
    a split of the heads' axis (a major one: no data moves)."""
    groups = b.shape[1]
    by_group = lambda t: t.reshape(t.shape[0], groups, -1, *t.shape[2:])
    over_heads = lambda m: m[:, :, None, None, :]            # [B, G, 1, 1, N]
    new = (by_group(jnp.exp(dt * a)[:, :, None, None] * s)
           + by_group(dt[:, :, None] * x)[..., None] * over_heads(b))
    y = jnp.sum(new * over_heads(c), axis=-1)
    return new.reshape(s.shape), y.reshape(x.shape)


def ssd_scan_plain(x, dt, b, c, a, d, s0=None):
    """Mamba-2's recurrence token by token (`lax.scan`), float32: x
    [B, L, H, P], dt [B, L, H] (after softplus; 0 at a position that must
    change nothing), b, c [B, L, G, N] (G divides H), a [H] = -exp(A_log),
    d [H], s0 [B, H, P, N] or zero. Returns (y [B, L, H, P] float32, S_L
    [B, H, P, N]): y before the gate and its norm, which are the model's."""
    f32 = lambda t: t.astype(jnp.float32)
    x, dt, b, c = map(f32, (x, dt, b, c))
    if s0 is None:
        s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)

    def token(s, xs):
        xt, dtt, bt, ct = xs  # [B, H, P], [B, H], [B, G, N], [B, G, N]
        return _ssd_token(xt, dtt, bt, ct, a, s)

    time_major = lambda t: jnp.swapaxes(t, 0, 1)
    s, y = jax.lax.scan(token, s0, tuple(map(time_major, (x, dt, b, c))))
    return time_major(y) + d[:, None] * x, s


def ssd_step(x, dt, b, c, a, d, s, active):
    """One token a row on the state pool: x [B, H, P], dt [B, H], b, c
    [B, G, N], s [B, H, P, N] float32 (a row per engine slot), active [B]
    bool. Returns (y [B, H, P] float32, s): an inactive row's state is left
    as it was. Plain `jax.numpy`, in place on the donated pool (as
    `ssm_step`)."""
    f32 = lambda t: t.astype(jnp.float32)
    x, dt, b, c = map(f32, (x, dt, b, c))
    new, y = _ssd_token(x, dt, b, c, a, s)
    return (y + d[:, None] * x,
            jnp.where(active[:, None, None, None], new, s))


def _ssd_scan_kernel(lens_ref, x_ref, cols_ref, rows_ref, b_ref, c_ref,
                     d_ref, y_ref, s_ref, g_scr, dx_scr, *, chunk: int,
                     heads: int, width: int, per_group: int):
    """Grid (rows, chunks, head blocks), the head blocks innermost: the
    row's whole state [H*P, N] is the output block, which stays in VMEM from
    the row's first chunk to its last, and C B^T of a chunk (lower triangle)
    is made once a group, at the first of its `per_group` head blocks (b and
    c are the block's group's: the index maps pick it). cols [chunk,
    2*heads]: per head the running sum c of dt A inside the chunk, then dt,
    down the sublanes; rows [heads, chunk]: c along the lanes. A chunk past
    the row's length is skipped (index maps stop at the last chunk in
    use)."""
    r, t, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block = heads * width

    @pl.when((t == 0) & (g == 0))
    def _zero():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(t * chunk >= lens_ref[r])
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t * chunk < lens_ref[r])
    def _chunk():
        mm = x_ref.dtype
        bm, cm = b_ref[0].astype(mm), c_ref[0].astype(mm)    # [chunk, N]

        @pl.when(g % per_group == 0)
        def _cb():
            cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            i = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 0)
            j = jax.lax.broadcasted_iota(jnp.int32, cb.shape, 1)
            g_scr[...] = jnp.where(j <= i, cb, 0.0)

        at = pl.ds(pl.multiple_of(g * block, block), block)
        s = s_ref[0, at, :]                                  # [block, N]
        carried = jax.lax.dot_general(                       # C S^T
            cm, s.astype(mm), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [chunk, block]
        ends = []
        for h in range(heads):  # static: every slice is a constant
            ch = slice(h * width, (h + 1) * width)
            cum = cols_ref[0, 0, :, h:h + 1]                 # [chunk, 1]
            dt = cols_ref[0, 0, :, heads + h:heads + h + 1]
            along = rows_ref[0, 0, h:h + 1, :]               # [1, chunk]
            # The chunk's last (dt A <= 0: its least), as a reduction: a
            # slice [1, 1] is not broadcast over sublanes and lanes at once.
            end = jnp.min(cum, axis=0, keepdims=True)        # [1, 1]
            # j <= i: c_i - c_j <= 0 (A < 0); above the diagonal C B^T is 0.
            decay = jnp.exp(jnp.minimum(cum - along, 0.0))
            xh = x_ref[0, :, ch].astype(jnp.float32)         # [chunk, P]
            dx = dt * xh
            inside = jnp.dot((g_scr[...] * decay).astype(mm), dx.astype(mm),
                             preferred_element_type=jnp.float32)
            y_ref[0, :, ch] = (inside + jnp.exp(cum) * carried[:, ch]
                               + d_ref[:, ch] * xh).astype(y_ref.dtype)
            dx_scr[:, ch] = (dx * jnp.exp(end - cum)).astype(mm)
            ends.append(jnp.exp(end))
        gain = jax.lax.dot_general(                          # (w dt x)^T B
            dx_scr[...], bm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [block, N]
        for h in range(heads):
            ch = slice(h * width, (h + 1) * width)
            s_ref[0, pl.ds(pl.multiple_of(g * block + h * width, width),
                           width), :] = ends[h] * s[ch] + gain[ch]


def ssd_scan_kernel(x, dt, b, c, a, d, lens, chunk: int = SSD_CHUNK,
                    heads: int = SSD_HEADS,
                    interpret: Optional[bool] = None):
    """Shapes as `ssd_scan`. x is read once and y written once, a group's B
    and C once a chunk, the state written once a row; the matrix products
    take x's dtype (float32 sums), the decays and the state are float32."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows, length, h, p = x.shape
    groups, n = b.shape[-2:]
    chunk = min(chunk, length)
    heads = min(heads, h // groups)     # a block's heads lie in one group
    if length % chunk or h % (groups * heads):
        raise ValueError(f"ssd_scan: {length} positions in chunks of {chunk},"
                         f" {h} heads of {groups} groups in blocks of "
                         f"{heads}")
    chunks, blocks, block = length // chunk, h // heads, heads * p
    per_group = blocks // groups
    dt = dt.astype(jnp.float32)
    # The running sum of dt A from each chunk's first position on.
    cum = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(
        rows, chunks, chunk, h), axis=2).reshape(rows, length, blocks, heads)
    by_block = lambda m: m.reshape(rows, length, blocks, heads)
    cols = jnp.concatenate([cum, by_block(dt)], axis=-1).transpose(0, 2, 1, 3)
    along = cum.transpose(0, 2, 3, 1)       # [rows, blocks, heads, length]

    def at(r, t, lens):  # chunk t, or the last one the row uses if past it
        last = jnp.maximum(jax.lax.div(lens[r] + chunk - 1, chunk) - 1, 0)
        return jnp.minimum(t, last)

    tile = lambda r, t, g, lens: (r, at(r, t, lens), g)
    # b, c [rows, length, G * N]: a head block's group is g // per_group.
    shared = pl.BlockSpec(
        (1, chunk, n),
        lambda r, t, g, lens: (r, at(r, t, lens), g // per_group))
    y, s = pl.pallas_call(
        functools.partial(_ssd_scan_kernel, chunk=chunk, heads=heads,
                          width=p, per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, chunks, blocks),
            in_specs=[
                pl.BlockSpec((1, chunk, block), tile),
                pl.BlockSpec((1, 1, chunk, 2 * heads),
                             lambda r, t, g, lens: (r, g, at(r, t, lens), 0)),
                pl.BlockSpec((1, 1, heads, chunk),
                             lambda r, t, g, lens: (r, g, 0, at(r, t, lens))),
                shared, shared,
                pl.BlockSpec((1, block), lambda r, t, g, lens: (0, g)),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, block),
                             lambda r, t, g, lens: (r, t, g)),
                pl.BlockSpec((1, h * p, n), lambda r, t, g, lens: (r, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32),
                            pltpu.VMEM((chunk, block), x.dtype)],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, length, h * p), x.dtype),
                   jax.ShapeDtypeStruct((rows, h * p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret,
        name="ssd_scan",
    )(lens.astype(jnp.int32), x.reshape(rows, length, h * p), cols, along,
      b.reshape(rows, length, groups * n), c.reshape(rows, length, groups * n),
      jnp.repeat(d.astype(jnp.float32), p).reshape(1, h * p))
    return y.reshape(x.shape), s.reshape(rows, h, p, n)


def ssd_scan(x, dt, b, c, a, d, lens, chunk: int = SSD_CHUNK,
             use_kernel: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """A prefill's positions from a zero state: x [B, L, H, P] and b, c
    [B, L, G, N] (the compute dtype; head h reads group h // (H / G)), dt
    [B, L, H] float32, a [H] = -exp(A_log) and d [H] float32, lens [B] the
    rows' true lengths. A position at or past
    its row's length must come with dt = 0 and a finite x: it then changes
    nothing, and the kernel does not walk a chunk (of `chunk` positions)
    that holds only such. Returns (y [B, L, H, P] in x's dtype, the state
    after the last true position [B, H, P, N] float32). The Pallas kernel on
    a TPU, the recurrence token by token elsewhere (as `ssm_scan`)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return ssd_scan_kernel(x, dt, b, c, a, d, lens, chunk)
    y, s = ssd_scan_plain(x, dt, b, c, a, d)
    return y.astype(x.dtype), s
