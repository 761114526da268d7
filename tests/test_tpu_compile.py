"""What the chip's compiler says, without the chip, and what a worker's
environment says about which device it may touch.

The TPU compiler is part of the installation and compiles for a chip that is
described, not attached (see the on-chip-measurement guide): interpret-mode
tests cannot see a misaligned slice, too much VMEM, or a Mosaic kernel that
GSPMD is asked to partition. These compiles keep the main path's two kernels
honest at Llama-3-8B head shapes, alone on one chip and inside their
shard_map wrappers on the four-chip host's 2x2 mesh. A compile that passes is
not a chip run."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from engine_sharing import (cell_at_depth, cell_model, decode_call,
                            prefill_call)
from ray_tpu._private import accelerators
from ray_tpu.llm._internal.paged import PagedCacheConfig
from ray_tpu.ops.paged_attention import (
    init_kv_pages,
    paged_attention_decode_kernel,
    pages_spec,
)
from ray_tpu.ops.attention import flash_attention
from ray_tpu.parallel.mesh import AXIS_ORDER

H, HKV, D = 32, 8, 128  # Llama-3-8B attention heads


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _mesh(topo, **sizes):
    dims = tuple(sizes.get(ax, 1) for ax in AXIS_ORDER)
    return Mesh(np.array(topo.devices).reshape(dims), AXIS_ORDER)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(text):
    """Which of the kernels' names the Mosaic custom-calls' own
    instruction names hold (`%jvp_flash_fwd_.1 = ... custom-call(...)`:
    the name comes from the innermost scope, the Pallas call's `name=`)."""
    held = set()
    for line in text.splitlines():
        if "tpu_custom_call" in line and " = " in line:
            instruction = line.split(" = ", 1)[0]
            held |= {k for k in ("paged_decode", "flash_fwd", "flash_bwd_dq",
                                 "flash_bwd_dkv", "gdn_decode", "ssm_scan",
                                 "ssd_scan",
                                 "moe_gmm", "swa_decode", "swa_flash",
                                 "mla_decode", "mla_flash", "sparse_decode",
                                 "sparse_flash")
                     if k in instruction}
    return held


def _flash_loss(q, k, v, mesh):
    out = flash_attention(q, k, v, causal=True, interpret=False, mesh=mesh)
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("batch,seq,heads,kv_heads", [
    (4, 4096, 16, 4),    # the flash bench's shape
    (1, 2048, H, HKV),   # chip_smoke's train step, per sequence
    (8, 128, H, HKV),    # short sequences: blocks shrink to the sequence
])
def test_flash_fwd_bwd_one_chip(topology, batch, seq, heads, kv_heads):
    one = SingleDeviceSharding(topology.devices[0])
    q = jax.ShapeDtypeStruct((batch, seq, heads, D), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((batch, seq, kv_heads, D), jnp.bfloat16,
                              sharding=one)
    grad = jax.grad(functools.partial(_flash_loss, mesh=None),
                    argnums=(0, 1, 2))
    # forward + dq + dk/dv kernels, each under its own instruction name
    # (what a trace's `XLA Ops` events are called)
    text = _compiled_text(grad, q, kv, kv)
    assert text.count("tpu_custom_call") >= 3
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= \
        _kernel_names(text)


def test_flash_fwd_bwd_sharded_2x2(topology):
    """Batch over fsdp, heads over tensor: what the sharded train step asks
    of the kernel. Bare, GSPMD refuses it ("Mosaic kernels cannot be
    automatically partitioned")."""
    mesh = _mesh(topology, fsdp=2, tensor=2)
    sh = NamedSharding(mesh, P("fsdp", None, "tensor"))
    q = jax.ShapeDtypeStruct((4, 2048, H, D), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((4, 2048, HKV, D), jnp.bfloat16, sharding=sh)
    grad = jax.grad(functools.partial(_flash_loss, mesh=mesh),
                    argnums=(0, 1, 2))
    assert _compiled_text(grad, q, kv, kv).count("tpu_custom_call") >= 3
    with pytest.raises(Exception, match="shard_map"):
        _compiled_text(jax.grad(functools.partial(_flash_loss, mesh=None),
                                argnums=(0, 1, 2)), q, kv, kv)


def _decode_args(sharding_for, batch, page_size, pages_per_seq):
    s = jax.ShapeDtypeStruct
    k_pages, v_pages = jax.eval_shape(lambda: init_kv_pages(
        PagedCacheConfig(num_pages=batch * pages_per_seq + 1,
                         page_size=page_size, max_seqs=batch,
                         max_pages_per_seq=pages_per_seq), HKV, D))
    return (
        s((batch, 1, H, D), jnp.bfloat16, sharding=sharding_for("q")),
        s(k_pages.shape, k_pages.dtype, sharding=sharding_for("pages")),
        s(v_pages.shape, v_pages.dtype, sharding=sharding_for("pages")),
        s((batch, pages_per_seq), jnp.int32, sharding=sharding_for(None)),
        s((batch,), jnp.int32, sharding=sharding_for(None)),
    )


@pytest.mark.parametrize("batch,page_size,pages_per_seq", [
    (8, 64, 16),    # chip_smoke's engine config
    (32, 16, 64),   # the engine's default page shape
    (8, 64, 64),    # several chunks of pages
])
def test_paged_decode_one_chip(topology, batch, page_size, pages_per_seq):
    one = SingleDeviceSharding(topology.devices[0])
    fn = functools.partial(paged_attention_decode_kernel, interpret=False)
    text = _compiled_text(
        fn, *_decode_args(lambda _: one, batch, page_size, pages_per_seq))
    assert "tpu_custom_call" in text
    assert "paged_decode" in _kernel_names(text)


def test_paged_decode_sharded_tensor4(topology):
    """KV pages split over kv heads, as the tensor-parallel engine holds
    them; page table and lengths replicated."""
    mesh = _mesh(topology, tensor=4)
    specs = {"q": P(None, None, "tensor"), "pages": pages_spec(HKV, mesh),
             None: P()}
    assert specs["pages"] == P(None, None, "tensor")
    args = _decode_args(lambda k: NamedSharding(mesh, specs[k]), 8, 64, 16)
    fn = functools.partial(paged_attention_decode_kernel, interpret=False)
    text = _compiled_text(functools.partial(fn, mesh=mesh), *args)
    assert "tpu_custom_call" in text
    with pytest.raises(Exception, match="shard_map"):
        _compiled_text(fn, *args)


# ---------------------------------------------------------------------------
# The K/V pool is never copied: scatter, gather and kernel take one layout
# ---------------------------------------------------------------------------
_LAYOUT_CHANGE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (.+?) (copy|transpose)\(")


def _pool_layout_changes(text, pool_elements, dtype=None):
    """The `copy` and `transpose` instructions of a compiled program (inside
    fusions too) whose result has as many elements as one layer's K or V
    pool (and, where given, its HLO `dtype`: a weight of another dtype can
    have a pool's element count): a pool changing its physical layout
    between two of its users. (`copy-start` is not one: at the cut depth the
    compiler may move a pool to another memory space, in the layout it
    has.)"""
    found = []
    for line in text.splitlines():
        m = _LAYOUT_CHANGE.match(line)
        if m and any(
                math.prod(int(n) for n in dims.split(",")) == pool_elements
                and dtype in (None, kind)
                for kind, dims in re.findall(r"(\w+)\[([\d,]+)\]",
                                             m.group(1))):
            found.append(line.strip()[:160])
    return found


def _lower_decode(model, ec, sharding):
    fn, args = decode_call(model, ec, sharding)
    return fn.lower(*args)


def _lower_prefill(model, ec, bucket, nb, sharding):
    fn, args = prefill_call(model, ec, bucket, nb, sharding)
    return fn.lower(*args)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("cell", ["decode-heavy", "hybrid-decode-heavy",
                                  "sdar-decode-heavy"])
def test_no_program_copies_a_kv_pool(topology, monkeypatch, cell, program):
    """The chip compiler's HLO of a decode window and of a prefill, at the
    serving widths and engine shapes of Mistral's and of the hybrid's cell
    (depth cut to two attention layers and to one of each kind): no
    instruction rewrites a whole pool, with the window's length the loop's
    traced trip count; nor do the repeated in-place writes
    of block diffusion's denoising passes, the first of a block scattering
    two blocks a row (SDAR's cell, two layers). With kv-head-major pages the scatter
    took the pool token major and the kernel as written, so every token
    step copied each layer's K and V from the one layout to the other (two
    such copies a layer in the loop, four more at its edges)."""
    from benchmark import sizing
    from benchmark.manifest import Manifest

    # the engine takes the Mosaic kernel where the default backend is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    manifest = Manifest(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    made = manifest.cell(cell)
    cfg = manifest.config(made["config"])
    family = manifest.family(cfg["family"])
    kw = family.model_kwargs(cfg)
    if "layer_types" in kw:
        kw["layer_types"] = kw["layer_types"][:4]
        assert "full_attention" in kw["layer_types"]
    else:
        kw["num_layers"] = 2
    model = family.model(kw)
    ec = manifest.traffic(made["traffic"])["engine_config"]
    one = SingleDeviceSharding(topology.devices[0])
    if program == "prefill":
        lowered = _lower_prefill(model, ec, 128, ec["max_seqs"], one)
        # (behind the tokens, caches, keys, logprobs and the expert load)
        carried = [x and x.shape for x in lowered.out_info[5:]]
        assert carried == ([None] * 2 if getattr(model, "block_length", 1) > 1
                           else [(ec["max_seqs"],)] * 2)
    else:
        lowered = _lower_decode(model, ec, one)
    text = lowered.compile().as_text()

    # a layer's K (or V) pool is the largest array of the engine's cache
    pool = max(jax.tree.leaves(sizing.cache_shapes(model, ec, None)),
               key=lambda x: math.prod(x.shape))
    assert pool.shape[:2] == (ec["max_seqs"] * ec["max_pages_per_seq"] + 1,
                              ec["page_size"])
    assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    if program == "decode":
        assert "paged_decode" in _kernel_names(text)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_state_pool_fills_the_lanes_and_is_never_copied(
        topology, monkeypatch, program):
    """The chip compiler's HLO of `hybrid-decode-heavy`'s decode window and
    of a prefill at the published widths (one period: three gated-delta
    layers and the full layer after them): a layer's state pool is
    `f32[16,15,96,384]`, two heads of [96, 192] side by side along the
    lanes (`ops/linear_attention.py` `state_shape`), and no instruction
    copies or transposes an array of its size: `gdn_decode` updates it in
    place and a prefill scatters its rows into it. (The prefill is of half
    the slots: what it packs for the scatter is the wave's states, `nb` of
    them, and a wave of all sixteen is as large as the pool without being
    it.) The program's arguments are their logical bytes: with 192 values
    in 256 lanes a layer's pool held 16 x 30 x 96 x 64 floats of padding,
    35.4 MB over these three layers and 141.6 MB over the cell's twelve."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("hybrid-decode-heavy", 4)
    assert model.state_layer_ids == (0, 1, 2)
    one = SingleDeviceSharding(topology.devices[0])
    if program == "decode":
        fn, args = decode_call(model, ec, one)
    else:
        fn, args = prefill_call(model, ec, 128, ec["max_seqs"] // 2, one)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    state = sizing.cache_shapes(model, ec, None)[0][1]
    assert (state.shape, state.dtype) == ((16, 15, 96, 384), jnp.float32)
    assert "f32[16,15,96,384]" in text and "f32[16,30,96,192]" not in text
    assert _pool_layout_changes(text, math.prod(state.shape)) == []
    if program == "decode":
        assert {"gdn_decode", "paged_decode"} <= _kernel_names(text)
    logical = sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
                  for x in jax.tree.leaves(args))
    held = compiled.memory_analysis().argument_size_in_bytes
    padding = 16 * 30 * 96 * 64 * 4     # a layer's, at 192 values in 256
    assert 0 <= held - logical < padding // 8, (held, logical)


def _jamba_at_depth(layers=None):
    return cell_at_depth("jamba-prompt-heavy", layers)


def _program_bytes(model, ec):
    from benchmark import sizing

    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for tree in (sizing.param_shapes(model, None),
                            sizing.cache_shapes(model, ec, None))
               for x in jax.tree.leaves(tree))


def test_jamba_decode_updates_the_state_pool_in_place(topology, monkeypatch):
    """The chip compiler's HLO of `jamba-prompt-heavy`'s decode window at the
    published widths (eight layers: seven Mamba layers and the attention
    layer after them): the one-token step is plain `jax.numpy` on the donated
    pool, and no instruction rewrites a layer's float32 state [8, 16, 5120]
    or a K/V pool; the attention layer's 20 query heads share one K/V head in
    the paged kernel."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = _jamba_at_depth(8)
    one = SingleDeviceSharding(topology.devices[0])
    text = _lower_decode(model, ec, one).compile().as_text()
    caches = sizing.cache_shapes(model, ec, None)
    state, pages = caches[0][1], caches[7][0]
    assert (state.shape, state.dtype) == ((8, 16, 5120), jnp.float32)
    assert pages.shape == (8 * 36 + 1, 64, 128)
    assert f"f32[{','.join(map(str, state.shape))}]" in text
    for pool in (state, pages):
        assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    assert "paged_decode" in _kernel_names(text)


def test_jamba_prefill_of_a_full_wave_fits_the_chip(topology, monkeypatch):
    """Prefill of 8 prompts in the 2,048 bucket, the largest program of
    `jamba-prompt-heavy`: compiled at eight layers (the temporaries are a
    layer's: the attention layer's float32 scores [8, 20, 2048, 2304], 3.0
    GB, are the largest) with the other twenty layers' weights and cache
    added from their shapes, it peaks under 14.75 GiB of a v5e's 15.75; the
    scan is the Pallas kernel, and no logits of every position are made."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = _jamba_at_depth(8)
    whole, _ = _jamba_at_depth()
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_prefill(model, ec, 2048, 8, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    rest = (_program_bytes(whole, ec) - _program_bytes(model, ec)) / sizing.GIB
    assert 3.5 < rest < 4.2            # 20 of 28 layers' weights and state
    assert parts["temp"] > 2.8         # the scores of a wave are there
    assert peak + rest < 14.75
    text = compiled.as_text()
    assert "ssm_scan" in _kernel_names(text)
    assert "[8,2048,65536]" not in text and "f32[8,65536]" in text


def test_granite_decode_streams_its_share_and_updates_the_pool_in_place(
        topology, monkeypatch):
    """The chip compiler's HLO of `granite-prompt-heavy`'s decode window at
    the published widths (six layers: five Mamba-2 layers and the attention
    layer after them, 36 of 72 experts held): the one-token step is plain
    `jax.numpy` on the donated pool, and no instruction rewrites a layer's
    float32 state [8, 128, 64, 128] or a K/V pool; the experts are the
    grouped-matmul kernel over stacks of 36, the attention layer the paged
    kernel."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("granite-prompt-heavy", 6)
    one = SingleDeviceSharding(topology.devices[0])
    text = _lower_decode(model, ec, one).compile().as_text()
    caches = sizing.cache_shapes(model, ec, None)
    state, pages = caches[0][1], caches[5][0]
    assert (state.shape, state.dtype) == ((8, 128, 64, 128), jnp.float32)
    assert pages.shape == (8 * 36 + 1, 64, 8 * 128)
    assert f"f32[{','.join(map(str, state.shape))}]" in text
    for pool in (state, pages):
        assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    assert {"paged_decode", "moe_gmm"} <= _kernel_names(text)
    assert "bf16[36,4096,1536]" in text and "bf16[72," not in text


@pytest.mark.parametrize("nb", [1, 8])
def test_granite_prefill_of_a_full_wave_fits_the_chip(topology, monkeypatch,
                                                      nb):
    """Prefill of one prompt (what the closed loop admits after its first
    wave) and of 8 in the 2,048 bucket, the largest program of
    `granite-prompt-heavy`: compiled at six layers (the temporaries are a
    layer's: the expert layer's rows laid out by expert are the largest, and
    attention keeps one row's scores) with the other four layers' weights and
    state added from their shapes, it peaks at 11.555 GiB of a v5e's 15.75,
    the whole vocabulary held (compile, PR 49: 11.855 while the gate-and-up
    product `[M, 2I]` and its float32 copy were arrays of the program; the
    gate-and-up call writes the activation); the scan is the Pallas kernel
    `ssd_scan`, the experts `moe_gmm` inside its `vmem_limit_bytes` (the
    chip's compiler refuses a kernel that is not), and no logits of every
    position are made."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("granite-prompt-heavy", 6)
    whole, _ = cell_at_depth("granite-prompt-heavy")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_prefill(model, ec, 2048, nb, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    rest = (_program_bytes(whole, ec) - _program_bytes(model, ec)) / sizing.GIB
    assert 3.4 < rest < 3.6            # 4 of 10 layers' weights and state
    # not the 4.5 GiB of a wave's scores
    assert parts["temp"] < (2.0 if nb == 8 else 0.75)
    assert peak + rest <= (11.555 if nb == 8 else 10.309) + 0.005
    text = compiled.as_text()
    assert {"ssd_scan", "moe_gmm"} <= _kernel_names(text)
    assert f"[{nb},2048,100352]" not in text and f"f32[{nb},100352]" in text
    assert f"f32[{nb},32,2048,2304]" not in text


def test_mellum_decode_walks_rings_and_pages_in_place(topology, monkeypatch):
    """The chip compiler's HLO of `mellum-code-context`'s decode window at the
    published widths and all eight layers: the six sliding layers' step is
    the windowed kernel over rings of 17 pages a slot, the two full layers'
    the paged kernel over the allocator's pool, the experts `moe_gmm` over
    all 64; no instruction rewrites a ring or a pool; it peaks at 7.47 GiB
    of a v5e's 15.75 (47%)."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("mellum-code-context")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_decode(model, ec, one).compile()
    peak, _ = sizing.peak_gib(compiled)
    assert 0.25 * sizing.USABLE_GIB < peak <= 7.466 + 0.05
    text = compiled.as_text()
    caches = sizing.cache_shapes(model, ec, None)
    ring, pages = caches[0][0], caches[3][0]
    assert ring.shape == (8 * 17, 64, 4 * 128)
    assert pages.shape == (8 * 72 + 1, 64, 4 * 128)
    for pool in (ring, pages):
        assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    assert {"swa_decode", "paged_decode", "moe_gmm"} <= _kernel_names(text)
    assert "bf16[64,2304,1792]" in text


@pytest.mark.parametrize("nb", [1, 8])
def test_mellum_prefill_of_a_full_wave_fits_the_chip(topology, monkeypatch,
                                                     nb):
    """Prefill of one prompt and of 8 in the 4,096 bucket, the largest
    program of `mellum-code-context`, at all eight layers: both kinds of
    layer attend over the call's own keys through a flash forward
    (`flash_fwd` causal, `swa_flash` banded), so no scores of 4,096 queries
    are made; the head runs on one position a row; the wave peaks at 10.127
    GiB of a v5e's 15.75 and one prompt at 7.885 (compile, PR 49: 10.953 and
    8.05 while the expert layers' gate-and-up product and its float32 copy
    were arrays of the program)."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("mellum-code-context")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_prefill(model, ec, 4096, nb, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    assert peak <= (10.127 if nb == 8 else 7.885) + 0.005 < sizing.USABLE_GIB
    assert parts["temp"] < (2.9 if nb == 8 else 0.6)
    text = compiled.as_text()
    assert {"flash_fwd", "swa_flash", "moe_gmm"} <= _kernel_names(text)
    assert not {"swa_decode", "paged_decode"} & _kernel_names(text)
    assert f"[{nb},4096,98304]" not in text and f"f32[{nb},98304]" in text
    for keys in (4096, 4608):                   # no [rows, heads, q, keys]
        assert f"[{nb},32,4096,{keys}]" not in text
        assert f"[32,4096,{keys}]" not in text


def test_sarvam_decode_walks_the_latent_pool_in_place(topology, monkeypatch):
    """The chip compiler's HLO of `sarvam-long-decode`'s decode window at the
    published widths and all six layers (32 of 128 experts held, a quarter of
    the vocabulary): every layer's step is `mla_decode` over one pool of
    [1281, 64, 640] (576 values a token on whole lanes), the experts `moe_gmm`
    over the 32 held; no instruction rewrites a pool, and nothing has the
    shape of a cache up-projected to its 64 heads' keys and values; it peaks
    at 11.43 GiB of a v5e's 15.75 (compile, PR 48)."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("sarvam-long-decode")
    assert model.cfg.num_layers == 6 and model.cfg.experts_held == (0, 32)
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_decode(model, ec, one).compile()
    peak, _ = sizing.peak_gib(compiled)
    assert 0.25 * sizing.USABLE_GIB < peak <= 11.432 + 0.05
    text = compiled.as_text()
    caches = sizing.cache_shapes(model, ec, None)
    assert [c.shape for c in caches] == [(16 * 80 + 1, 64, 640)] * 6
    assert _pool_layout_changes(text, math.prod(caches[0].shape)) == []
    assert {"mla_decode", "moe_gmm"} <= _kernel_names(text)
    assert not {"paged_decode", "mla_flash", "flash_fwd"} & _kernel_names(text)
    assert "bf16[32,4096,4096]" in text            # the held experts' stacks
    # per head, a cached token's keys and values are 256 values: no array of
    # every page's or every slot's tokens times 64 heads of them
    for tokens in (1281 * 64, 16 * 5120, 5120):
        assert f"[{tokens},16384]" not in text
        assert f"[{tokens},64,256]" not in text and \
            f"[{tokens},64,128]" not in text


@pytest.mark.parametrize("nb", [1, 16])
def test_sarvam_prefill_of_a_full_wave_fits_the_chip(topology, monkeypatch,
                                                     nb):
    """Prefill of one prompt and of 16 in the 4,096 bucket, the largest
    program of `sarvam-long-decode`, at all six layers: attention in the
    published form through the flash forward at keys of 192 and values of 128
    (`mla_flash`), a row of the wave at a time, so neither the scores nor a
    wave's up-projected keys and values are made; the pool is written in
    place; the head runs on one position a row; the wave peaks at 13.649 GiB
    of a v5e's 15.75 and one prompt at 11.797 (compile, PR 49: 14.492 and
    12.261 while a row's gate-and-up product and its float32 copy were
    arrays of the program), under the issue's 15.0."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("sarvam-long-decode")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_prefill(model, ec, 4096, nb, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    assert peak <= (13.649 if nb == 16 else 11.797) + 0.005 < 15.0
    assert parts["temp"] < (2.95 if nb == 16 else 1.08)
    text = compiled.as_text()
    assert {"mla_flash", "moe_gmm"} <= _kernel_names(text)
    assert not {"mla_decode", "paged_decode", "flash_fwd"} \
        & _kernel_names(text)
    pool = sizing.cache_shapes(model, ec, None)[0]
    assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    assert f"[{nb},4096,65536]" not in text and f"f32[{nb},65536]" in text
    assert "[64,4096,4096]" not in text            # no [heads, q, keys]
    if nb > 1:                                     # no wave's q, k or v
        assert f"[{nb},4096,64,192]" not in text
        assert f"[{nb},4096,12288]" not in text


def _sala_pools(model, ec):
    """(a sparse layer's K pool, its index pool, a lightning layer's state
    pool) of `sala-long-context`'s cache, as shapes."""
    from benchmark import sizing

    caches = sizing.cache_shapes(model, ec, None)
    k_pages, _, m_pages = caches[model.index_layer_ids[0]]
    return k_pages, m_pages, caches[model.state_layer_ids[0]]


def _outside_fusions(text):
    """A compiled program's text without its fusions' bodies: what is left
    names the arrays the program holds and the instructions it launches."""
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", text))
    kept, body = [], False
    for line in text.splitlines():
        if line.endswith("{") and line.split(" ", 1)[0] in fused:
            body = True
        if not body:
            kept.append(line)
        if line == "}":
            body = False
    return "\n".join(kept)


def _selection_sorts_nothing(text, pairs):
    """The selection counts (`ops.attention.chosen_mask`): the program sorts
    no scores and no list (the one `sort` a wave's prefill holds is the
    compiler's own, over the 131,072 indices of a pool's scatter, one
    dimension), and the comparisons of every block with every other, `pairs`,
    live inside a fusion and are never an array."""
    for line in text.splitlines():
        if " sort(" in line:
            assert all("," not in dims for dims in re.findall(
                r"\w+\[([\d,]*)\]", line.split(" sort(")[0])), line[:200]
    assert pairs in text and pairs not in _outside_fusions(text)


def test_sala_decode_walks_chosen_pages_and_updates_its_pools_in_place(
        topology, monkeypatch):
    """The chip compiler's HLO of `sala-long-context`'s decode window at the
    published widths and all sixteen layers: the four sparse layers' steps
    are `sparse_decode` over lists of 128 entries a row and KV head (a KV
    head's lanes of a page one DMA), the twelve lightning layers' plain XLA
    on the state pool; no instruction rewrites a K/V pool, an index pool or
    a state pool, and nothing has the shape of a row's whole context
    gathered; the 64 pages are found by counting, so the program holds no
    `sort` and the comparisons `[8,2,1,272,272]` only inside a fusion; it
    peaks at 11.435 GiB of a v5e's 15.75 (compile, PR 52; 11.437 with the
    sorts, PR 51)."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("sala-long-context")
    assert len(model.index_layer_ids) == 4 and len(
        model.state_layer_ids) == 12
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_decode(model, ec, one).compile()
    peak, _ = sizing.peak_gib(compiled)
    assert 0.25 * sizing.USABLE_GIB < peak <= 11.437 + 0.05
    text = compiled.as_text()
    k_pages, m_pages, state = _sala_pools(model, ec)
    assert k_pages.shape == (8 * 272 + 1, 64, 256)
    assert m_pages.shape == (8 * 272 + 1, 4, 256) and state.shape == (
        8, 32, 128, 128)
    for pool in (k_pages, m_pages, state):
        assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    assert _kernel_names(text) == {"sparse_decode"}
    # the selection gathers a row's segment means (a sixteenth of its keys,
    # float32), never its keys or values
    assert "f32[8,272,4,256]" in text
    for gathered in ("[8,272,64,256]", "[8,17408,256]", "[8,17408,2,128]"):
        assert gathered not in text
    _selection_sorts_nothing(text, "[8,2,1,272,272]")


@pytest.mark.parametrize("nb", [1, 8])
def test_sala_prefill_of_a_full_wave_fits_the_chip(topology, monkeypatch, nb):
    """Prefill of one prompt and of 8 in the 16,384 bucket, the largest
    program of `sala-long-context`, at all sixteen layers: a row of the wave
    at a time through every layer (the embedding inside the loop: a wave's
    hidden states are never an array), the sparse layers through
    `sparse_flash` under the mask `select_blocks` made, no scores tensor,
    and the mask by counting: no `sort`, a tile's comparisons
    `[1,2,512,256,256]` only inside a fusion; the pools are written in place
    after the loop; the head runs on one position a row; the wave peaks at
    14.478 GiB of a v5e's 15.75 and one prompt at 11.674 (compile, PR 52:
    14.4776 and 11.6743; with the sorts 14.4783 and 11.6749, PR 51), under
    the issue's 15.0."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("sala-long-context")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_prefill(model, ec, 16384, nb, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    assert peak <= (14.478 if nb == 8 else 11.675) + 0.005 < 15.0
    text = compiled.as_text()
    assert _kernel_names(text) == {"sparse_flash"}
    k_pages, m_pages, _ = _sala_pools(model, ec)
    for pool in (k_pages, m_pages):
        assert _pool_layout_changes(text, math.prod(pool.shape)) == []
    assert f"[{nb},16384,73448]" not in text and f"f32[{nb},73448]" in text
    assert "[32,16384,16384]" not in text          # no [heads, q, keys]
    assert "[32,16384,1024]" not in text           # nor every query's scores
    assert "f32[2,16,512,1024]" in text            # a tile of 512 queries'
    _selection_sorts_nothing(text, "[1,2,512,256,256]")
    if nb > 1:                                     # no wave's hidden states
        assert f"[{nb},16384,4096]" not in text


@pytest.mark.parametrize("tm,tiles,experts,k,n,act", [
    (128, 196, 36, 4096, 1536, False),   # Granite, one prompt of 2,048: gate
                                         # and up as a plain product
    (128, 1316, 36, 768, 4096, False),   # a wave of 8: down
    (16, 39, 36, 4096, 1536, False),     # its decode step of 8 rows
    (16, 152, 128, 2048, 1536, False),   # SDAR's forward over 64 tokens
    (128, 64, 8, 4096, 28672, False),    # a stack too wide for one block: 14
    # the gate-and-up call as the layer makes it, the activation written:
    (128, 196, 36, 4096, 1536, True),    # Granite, one prompt of 2,048
    (16, 39, 36, 4096, 1536, True),      # its decode step
    (128, 320, 64, 2304, 1792, True),    # Mellum, one prompt of 4,096
    (16, 152, 128, 2048, 1536, True),    # SDAR's forward over 64 tokens
    (128, 288, 32, 4096, 4096, True),    # Sarvam, one prompt: two blocks of
                                         # 1,024 gate and 1,024 up columns
    (16, 38, 32, 4096, 4096, True),      # its decode step of 16 rows
    (128, 64, 8, 4096, 28672, True),     # too wide for one block: 14
])
def test_moe_gmm_one_chip_holds_its_blocks_in_vmem(topology, tm, tiles,
                                                   experts, k, n, act):
    """`moe_gmm` alone at the cells' shapes, as the plain product and as the
    call that writes the activation: the Mosaic compiler takes the weight
    blocks `_rhs_columns` chose (every column of an expert at the widths
    served but Sarvam's, 12 MiB double-buffered at Granite's gate and up, as
    one block or as the activating call's two) within `VMEM_LIMIT_BYTES`;
    the activating call returns `[M, I]`."""
    from ray_tpu.ops import moe

    one = SingleDeviceSharding(topology.devices[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def run(lhs, rhs, tile_expert, tiles_used):
        p = moe.Plan(tm, None, None, tile_expert, tiles_used, None)
        return moe.gmm(lhs, rhs, p, use_kernel=True, interpret=False, act=act)

    text = _compiled_text(run, s((tiles * tm, k), jnp.bfloat16),
                          s((experts, k, n), jnp.bfloat16),
                          s((tiles,), jnp.int32), s((1,), jnp.int32))
    assert _kernel_names(text) == {"moe_gmm"}
    assert f"bf16[{tiles * tm},{n // 2 if act else n}]" in text
    if act and n != k:                # (at Sarvam's widths the rows' shape)
        assert f"[{tiles * tm},{n}]" not in text


@pytest.mark.parametrize("tokens,k,columns,held", [
    (8, 8, 64, None),              # Mellum's decode step
    (64, 8, 128, None),            # SDAR's forward over 64 tokens
    (8, 10, 72, (0, 36)),          # Granite's decode step on its share
    (4096, 8, 64, None),           # Mellum's one-prompt prefill
])
def test_moe_plan_compiles_to_no_loop_gather_of_scalars_or_scatter(
        topology, tokens, k, columns, held):
    """What the chip's compiler makes of `ops/moe.py` `plan`, which the
    jaxpr cannot show: it turns a gather of windows into a `while` of a step
    a window, pads a gather of scalars to 1,024 indices (20 us, whatever
    their number) and runs a scatter an index at a time (builder's chip runs,
    PR 45). The layout has none of the three at the shapes served; the one
    lookup left takes a block of `2 * tm` tokens a tile."""
    from ray_tpu.ops import moe

    one = SingleDeviceSharding(topology.devices[0])
    experts = jax.ShapeDtypeStruct((tokens, k), jnp.int32, sharding=one)
    text = _compiled_text(
        lambda e: tuple(moe.plan(e, columns, held=held))[1:], experts)
    assert " while(" not in text
    assert " scatter(" not in text
    gathers = re.findall(r" gather\(.*slice_sizes=\{([\d,]+)\}", text)
    tm = moe.tile_rows(tokens * k, columns)
    assert gathers == [f"1,{2 * tm}"]


@pytest.mark.parametrize("tokens,k,columns,held,h,inter", [
    (2048, 10, 72, (0, 36), 4096, 768),   # Granite's one-prompt prefill
    (8, 8, 64, None, 2304, 896),          # Mellum's decode step
    (4096, 8, 64, None, 2304, 896),       # Mellum's one-prompt prefill
    (4096, 8, 128, (0, 32), 4096, 2048),  # Sarvam's one-prompt prefill
    (64, 8, 128, None, 2048, 768),        # SDAR's forward over 64 tokens
])
def test_moe_layer_compiles_to_rows_moved_once_each_way(
        topology, tokens, k, columns, held, h, inter):
    """What the chip's compiler makes of `moe_layer`'s glue around its two
    grouped matmuls: a gather in `fill` mode brings a `broadcast_select`
    pass over all `[M, H]` rows behind it (626 us of 940 at Granite's
    prefill), rows gathered token-major a relayout `[tokens, k, H]` (0.2 ms
    a 1,024 tokens; builder's chip runs, PR 45 and 46). Neither is left; the
    kernels are the two `moe_gmm` calls and no other, and the first of them
    writes the activation `[M, I]`: no instruction's result is the
    gate-and-up product `[M, 2I]`, stored or converted to float32 (two
    passes over every padded row, 443 us of 3,589 at Granite's prefill and
    2,531 of 9,642 at Sarvam's; builder's chip runs, PR 49)."""
    from ray_tpu.ops import moe

    one = SingleDeviceSharding(topology.devices[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    count = columns if held is None else held[1]
    text = _compiled_text(
        lambda *a: moe.moe_layer(*a, k, use_kernel=True, interpret=False,
                                 held=held)[0],
        s((tokens, h), jnp.bfloat16), s((h, columns), jnp.float32),
        s((count, h, 2 * inter), jnp.bfloat16),
        s((count, inter, h), jnp.bfloat16))
    rows = jax.eval_shape(
        lambda e: moe.plan(e, columns, held=held).row_token,
        jax.ShapeDtypeStruct((tokens, k), jnp.int32)).shape[0]
    block = min(tokens, moe.COMBINE_TOKENS)
    # (instruction name, what it is) of every instruction; its result's type
    defs = [line.split(" = ", 1) for line in text.splitlines()
            if " = " in line]
    results = [d.split("{", 1)[0].split("(", 1)[0] for _, d in defs]
    assert f"bf16[{rows},{h}]" in results             # (the rows are there)
    assert not [n for n, d in defs if "broadcast_select_fusion" in n
                and d.startswith(f"bf16[{rows},{h}]")]
    for b in {block, min(tokens, 1024)}:    # (the blocks were of 1,024)
        assert f"bf16[{b},{k},{h}]" not in results
        assert f"f32[{b},{k},{h}]" not in results
    assert f"bf16[{rows},{inter}]" in results         # (the activation is)
    # (at Sarvam's widths 2I is H: the rows' shape is not the product's)
    product = {f"{dt}[{rows},{2 * inter}]" for dt in ("f32", "bf16")}
    assert not (product - {f"bf16[{rows},{h}]"}) & set(results)
    calls = [d for n, d in defs
             if "tpu_custom_call" in d and re.match(r"\s*%moe_gmm[.\d]*$", n)]
    assert [d.split("{", 1)[0] for d in calls] == [
        f"bf16[{rows},{inter}]", f"bf16[{rows},{h}]"]
    assert _kernel_names(text) == {"moe_gmm"}


def test_ssd_scan_one_chip_at_the_published_head_shapes(topology):
    """`ssd_scan` alone for a wave of 8 x 2,048 positions at Granite's 128
    heads of 64 with 128 states: the Mosaic compiler takes its blocks, its
    dynamic slices of the resident state and its 48 MiB of VMEM."""
    from ray_tpu.ops.ssm import ssd_scan_kernel

    one = SingleDeviceSharding(topology.devices[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    b, length, h, p, n = 8, 2048, 128, 64, 128
    text = _compiled_text(
        functools.partial(ssd_scan_kernel, interpret=False),
        s((b, length, h, p), jnp.bfloat16), s((b, length, h), jnp.float32),
        s((b, length, 1, n), jnp.bfloat16),
        s((b, length, 1, n), jnp.bfloat16),
        s((h,), jnp.float32), s((h,), jnp.float32), s((b,), jnp.int32))
    assert "ssd_scan" in _kernel_names(text)
    assert f"f32[{b},{h * p},{n}]" in text     # the state, N on the lanes


def test_train_step_recomputes_the_wide_ffn_products_and_nothing_else(
        topology, monkeypatch):
    """The chip compiler's HLO of `train-2k`'s whole step (8 x 2,048, depth
    8, adafactor): a remat'd layer keeps q, k, v, the flash kernel's output
    and row sums and the attention projection, so `flash_fwd` runs once a
    layer, the k and v projections' matmuls are the forward's and the weight
    gradients' and no third, the only matmuls under `rematted_computation`
    are `gate_proj` and `up_proj`, and the step fits the chip with a GiB to
    spare."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, traffic = cell_model("train-2k")
    depth = model.cfg.num_layers
    step = sizing.lower_train_step(
        model, traffic["batch"], traffic["seq"], traffic["learning_rate"],
        SingleDeviceSharding(topology.devices[0])).compile()
    text = step.as_text()
    calls = [line.split(" = ", 1)[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert sum("flash_fwd" in name for name in calls) == depth
    matmuls = [line for line in text.splitlines() if " convolution(" in line]
    results = lambda shape: sum(f" = {shape}{{" in line for line in matmuls)
    assert results("bf16[8,2048,8,128]") == 2 * depth       # k, v: forward
    assert results("bf16[8,2048,14336]") == 5 * depth
    again = [re.search(r"/(\w+)/dot_general", line).group(1)
             for line in matmuls if "rematted_computation" in line]
    assert sorted(again) == sorted(["gate_proj", "up_proj"] * depth)
    peak, parts = sizing.peak_gib(step)
    assert 13.0 <= peak <= sizing.USABLE_GIB - 1.0, (peak, parts)


# ---------------------------------------------------------------------------
# The environment the nodelet gives a worker (no cluster needed)
# ---------------------------------------------------------------------------
_HOST = {
    # what the chip machine's own environment carries
    "JAX_PLATFORMS": "tpu,cpu",
    "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1",
    "PATH": "/usr/bin",
}


@pytest.mark.parametrize("parent_platform", ["tpu,cpu", "cpu", None])
def test_worker_without_lease_is_a_cpu_process(parent_platform):
    base = dict(_HOST)
    if parent_platform is None:
        del base["JAX_PLATFORMS"]
    else:
        base["JAX_PLATFORMS"] = parent_platform
    before = dict(base)
    env = accelerators.process_environ(base)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env
    assert env["PATH"] == "/usr/bin"
    assert base == before


@pytest.mark.parametrize("chips,bounds", [
    ([2], "1,1,1"),            # 1 of 4
    ([0, 1], "1,2,1"),
    ([0, 1, 2, 3], "2,2,1"),   # 4 of 4
])
def test_leased_worker_gets_the_tpu_even_under_a_cpu_parent(chips, bounds):
    env = accelerators.process_environ(
        dict(_HOST, JAX_PLATFORMS="cpu"), chips)
    assert env["JAX_PLATFORMS"].split(",")[0] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_lease_of_unknown_shape_is_refused():
    with pytest.raises(ValueError, match="3 chips"):
        accelerators.process_environ(_HOST, [0, 1, 2])


def test_compile_cache_dir_reaches_leased_workers():
    env = accelerators.process_environ(
        dict(_HOST, JAX_COMPILATION_CACHE_DIR="/somewhere/cache"), [0])
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/cache"
    env = accelerators.process_environ(_HOST, [0])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert env["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        repo, ".jax_cache")
    # A CPU process keeps whatever its parent had, and gets no default.
    assert "JAX_COMPILATION_CACHE_DIR" not in accelerators.process_environ(
        _HOST)


@pytest.mark.parametrize("vfio,dev,want", [
    (["0", "1", "2", "3", "vfio"], ["vfio", "null"], 4),   # four-chip host
    (["3", "vfio"], ["vfio", "null"], 1),                  # one chip of it
    (None, ["accel0", "accel1", "null"], 2),               # /dev/accel*
    (None, ["null"], 0),
])
def test_chips_are_counted_from_device_files(monkeypatch, vfio, dev, want):
    def listdir(path):
        if path == "/dev/vfio":
            if vfio is None:
                raise FileNotFoundError(path)
            return vfio
        assert path == "/dev"
        return dev

    monkeypatch.setattr(accelerators.os, "listdir", listdir)
    # The host's bounds describe its type, not what this VM was given.
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    assert accelerators._count_tpu_chips() == want


def test_nemotron_decode_holds_five_pools_one_layers_pages_and_no_more(
        topology, monkeypatch):
    """The chip compiler's HLO of `nemotron-decode-heavy`'s decode window at
    the published widths and all eleven blocks (5 Mamba-2 with eight groups
    of B and C, 1 attention, 5 expert blocks holding 128 of 512 experts): the
    grouped one-token step is plain `jax.numpy` on the donated pool, and no
    instruction rewrites a block's float32 state [16, 128, 64, 128] or the
    one layer's K/V pool; an expert block has no cache entry at all; the
    experts are the grouped-matmul kernel over stacks of 128 in the 1,024-wide
    latent, the up call's body the squared ReLU (`bf16[rows, 2688]` out of
    `bf16[128, 1024, 2688]`), the attention block the paged kernel; it peaks
    at 10.571 GiB of a v5e's 15.75, 67% (the configuration's
    `memory_analysis`; compile, PR 56)."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("nemotron-decode-heavy")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_decode(model, ec, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    assert 0.6 * sizing.USABLE_GIB < peak <= 10.571 + 0.01
    text = compiled.as_text()
    caches = sizing.cache_shapes(model, ec, None)
    assert [len(jax.tree.leaves(c)) for c in caches] == [
        2, 0, 2, 0, 2, 0, 2, 2, 0, 2, 0]
    state, pages = caches[0][1], caches[7][0]
    assert (state.shape, state.dtype) == ((16, 128, 64, 128), jnp.float32)
    assert pages.shape == (16 * 20 + 1, 64, 2 * 128)
    assert f"f32[{','.join(map(str, state.shape))}]" in text
    # (by dtype too: the attention block's bf16 q and o kernels [4096, 4096]
    # have as many elements as a float32 state pool)
    for pool, kind in ((state, "f32"), (pages, "bf16")):
        assert _pool_layout_changes(text, math.prod(pool.shape), kind) == []
    assert {"paged_decode", "moe_gmm"} <= _kernel_names(text)
    assert "bf16[128,1024,2688]" in text and "bf16[512," not in text
    # ten `moe_gmm` calls a token step: an up and a down call a block
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and re.match(r"\s*%moe_gmm", line)]
    assert len(calls) == 10
    assert sum("2688]" in c.split(" = ", 1)[1].split("{", 1)[0]
               for c in calls) == 5


@pytest.mark.parametrize("nb", [1, 16])
def test_nemotron_prefill_of_a_full_wave_fits_the_chip(topology, monkeypatch,
                                                       nb):
    """Prefill of one prompt and of 16 in the 128 bucket, the largest program
    of `nemotron-decode-heavy`, at all eleven blocks: the scan is the Pallas
    kernel `ssd_scan` with B and C of eight groups, the experts `moe_gmm`,
    the head on one position a row; the wave peaks at 11.027 GiB of a v5e's
    15.75 and one prompt at 10.575 (compile, PR 56)."""
    from benchmark import sizing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, ec = cell_at_depth("nemotron-decode-heavy")
    one = SingleDeviceSharding(topology.devices[0])
    compiled = _lower_prefill(model, ec, 128, nb, one).compile()
    peak, parts = sizing.peak_gib(compiled)
    assert peak <= (11.027 if nb == 16 else 10.575) + 0.01
    assert parts["temp"] < (0.6 if nb == 16 else 0.1)
    text = compiled.as_text()
    assert {"ssd_scan", "moe_gmm"} <= _kernel_names(text)
    assert f"[{nb},128,131072]" not in text and f"f32[{nb},131072]" in text
