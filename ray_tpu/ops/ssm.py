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
