"""Continuous-batching LLM engine (reference:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180 — the
reference wraps vLLM's CUDA engine; on TPU we are the engine, SURVEY §7.3).

TPU-first design:
- one jitted decode step over a FIXED batch of slots (static shapes; idle
  slots masked) — XLA compiles it once and the MXU stays busy regardless of
  request churn;
- prefill jitted per power-of-two length bucket, one sequence at a time,
  writing straight into the paged KV cache;
- paged KV cache: host-side page allocator (llm/_internal/paged.py) +
  device-side scatter/gather/kernel (ops/paged_attention.py, which the
  model calls), donated through the step so pages update in place;
- greedy/temperature sampling inside the jitted step.

The engine is synchronous and single-model; LLMServer (serve deployment)
runs it on a background thread and streams tokens per request.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import flight_recorder as _fr
from ray_tpu.llm._internal.paged import (
    PageAllocator,
    PagedCacheConfig,
    PrefixCache,
)
from ray_tpu.util import metrics as _um
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# Records of built and retraced programs kept for `programs_report()`.
_PROGRAM_RECORDS = 32
_LATENCY_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                    2.5, 10.0)


@dataclasses.dataclass
class EngineConfig:
    max_seqs: int = 8
    page_size: int = 16
    max_pages_per_seq: int = 64
    num_pages: Optional[int] = None  # default: enough for all slots full
    prefill_buckets: Tuple[int, ...] = (32, 128, 512, 2048)
    # Decode iterations per jitted dispatch (multi-step scheduling, like
    # vLLM's num_scheduler_steps): amortizes host dispatch over K tokens at
    # the cost of up to K-1 wasted tokens past a stop condition. The longest
    # a window runs: `_window_steps` halves it while a request could be
    # admitted at the window's end.
    decode_steps: int = 8
    # Static width of the per-token top-logprob report (requests may ask
    # for fewer; more than this raises at add_request).
    max_logprobs: int = 5
    # Full prompt pages are indexed by content hash and shared across
    # requests (the engine-side cache the prefix-aware router assumes).
    enable_prefix_cache: bool = True
    # Batched multi-LoRA (reference: ray.llm multiplex/LoRA deployments →
    # vLLM punica; here gathered-einsum banks in the jitted steps).
    # lora_rank 0 disables; max_loras counts ADAPTERS (slot 0 = none).
    lora_rank: int = 0
    max_loras: int = 4
    lora_targets: Tuple[str, ...] = ("q_proj", "k_proj", "v_proj",
                                     "o_proj")
    # Overlap host scheduling with device compute: dispatch decode window
    # N+1 from window N's DEVICE outputs before N's tokens reach the host.
    pipeline_dispatch: bool = True

    def resolved_num_pages(self) -> int:
        return self.num_pages or self.max_seqs * self.max_pages_per_seq


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_ids: List[int]
    max_tokens: int = 64
    temperature: float = 0.0
    stop_token: Optional[int] = None
    lora_id: str = ""  # adapter name ("" = base model)
    # OpenAI sampling parity (reference:
    # llm/_internal/serve/configs/openai_api_models.py:236): nucleus /
    # top-k truncation run INSIDE the jitted sample step; `seed` pins this
    # request's own PRNG chain (its stream depends only on its own
    # sampling events, not on batch-mates).
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    seed: Optional[int] = None
    # Number of top-alternative logprobs to return per token (0 = off;
    # the chosen token's logprob is returned whenever > -1).
    logprobs: int = 0
    # runtime state
    slot: int = -1
    generated: int = 0
    done: bool = False
    # Block generation: prompt tokens at the head of the request's first
    # block; they come back with its first window and are not emitted.
    skip: int = 0
    # (cached prompt tokens, prefill batch) of its admission, for the
    # first-token mark, which block generation makes a window later.
    prefilled: Tuple[int, int] = (0, 0)
    # Monotonic stamps. Enqueued when the object is made (whoever queues
    # it); admitted and first token by the engine.
    t_enqueued: float = dataclasses.field(default_factory=time.monotonic)
    t_admitted: float = 0.0
    t_first_token: float = 0.0


@dataclasses.dataclass
class StepOutput:
    request_id: str
    token: int
    finished: bool
    # log p(token) under the UNSCALED model distribution, plus the top-N
    # (id, logprob) alternatives — populated when the request asked.
    logprob: Optional[float] = None
    top_logprobs: Optional[List[Tuple[int, float]]] = None


class _Window(NamedTuple):
    """A dispatched decode window: its device results (tokens [K, B] of
    which the first `steps` rows are filled, final last_tokens and seq_lens,
    logprobs or None), the request each of its rows was dispatched for, by
    slot (a slot may hold another by the time the tokens are read), and its
    token steps."""
    toks: Any
    last: Any
    lens: Any
    lp: Any
    slots: Dict[int, "Request"]
    steps: int


# What a layer of experts sows as `expert_load` in a forward (`ops.moe.Load`),
# under the names the spans carry it by.
_EXPERT_LOAD = ("experts_touched", "expert_load_max", "expert_rows_held",
                "expert_rows_routed", "expert_tiles")
# What a layer with an index pool sows as `page_load` in a decode step
# (`ops.paged_attention.select_pages`): the pages its rows' KV heads walked
# and the pages those rows hold.
_PAGE_LOAD = ("pages_selected", "pages_visible")
_SOWN = ["expert_load", "page_load"]


def _sown_load(sown):
    """[layers, n] int32: the `expert_load` (n = 5) or `page_load` (n = 2)
    collection of one forward, a row a layer that sows; [0, 5] of a model
    that sows none."""
    load = jax.tree.leaves(sown)
    return (jnp.stack(load) if load
            else jnp.zeros((0, len(_EXPERT_LOAD)), jnp.int32))


def _leaf_bytes(x) -> int:
    """Bytes of an array (or a tracer, or a shape) from its shape alone."""
    return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize


def _laid_out_bytes(x) -> int:
    """Bytes of an array on the device, whose tiles are 128 lanes wide: the
    minor axis is padded to a multiple of 128."""
    lanes = -(-x.shape[-1] // 128) * 128
    return _leaf_bytes(x) // x.shape[-1] * lanes


def _signature(args) -> Dict[str, tuple]:
    """What a jitted function's cache tells calls apart by, per argument
    leaf: shape, dtype, weak type, sharding, committed to it or not."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(args)
    return {jax.tree_util.keystr(path): (
        tuple(np.shape(x)), str(getattr(x, "dtype", type(x).__name__)),
        bool(getattr(x, "weak_type", False)),
        str(getattr(x, "sharding", None)),
        bool(getattr(x, "committed", False))) for path, x in leaves}


def _signature_diff(old: Dict[str, tuple], new: Dict[str, tuple]
                    ) -> Dict[str, Any]:
    """Leaves whose signature changed, as {path: [before, after]} (at most
    eight; a leaf only one side has reads None on the other)."""
    out: Dict[str, Any] = {}
    for path in sorted(set(old) | set(new)):
        if old.get(path) != new.get(path) and len(out) < 8:
            out[path] = [old.get(path), new.get(path)]
    return out


class LLMEngine:
    """add_request() + step() — the scheduler half of continuous batching.

    Tensor parallel: pass `mesh` (any jax.sharding.Mesh with a "tensor"
    axis). Params shard per the family's rules (heads/mlp/vocab over
    tensor), the paged KV cache shards over its kv-head axis, and the jitted
    prefill/decode steps run SPMD — XLA inserts the all-reduces over ICI
    (reference passes tensor_parallel_size into vLLM,
    serve/deployments/llm/vllm/vllm_models.py:125; here TP is native).

    What the engine reads off a model is stated once, with the value of a
    model that does not say otherwise, on `models/layers.py` `Decoder`.

    The cache is what the model says each layer holds
    (`model.init_cache`): K/V pages per token, or for the layers in
    `model.state_layer_ids` a fixed state per slot, which prefill overwrites
    from zero and decode updates in place. A model with state layers runs
    without LoRA banks or prefix sharing (each is built for K/V layers only)
    and, having no sharding rules, without a mesh.

    The layers in `model.ring_layer_ids` (sliding-window attention) hold a
    ring of `sliding_window / page_size + 1` pages a slot, owned by the slot
    as a state is and addressed as pages are: the allocator, the page table
    and `max_pages_per_seq` are the other layers' alone, and releasing a slot
    needs nothing for a ring (the next prefill overwrites what its decode
    steps read). Such a model runs under the same limits as one with state
    layers: a shared page run has no ring to restore.

    The layers in `model.latent_layer_ids` (latent attention) hold one pool
    of the allocator's pages, a token's row `latent_width` values for all
    heads. A prefill of such a model attends over the call's own keys and
    reads no cached page, so it too runs under those limits: prefix sharing
    over latent pages is not built.

    The layers in `model.cacheless_layer_ids` (an expert layer alone) keep
    nothing between steps: their entry is empty, passes through both programs
    as it is, and is counted apart (`cacheless_layers`), not as a K/V layer.

    A model that says `num_logits_to_keep = 1` gets its prefill's final norm
    and head on each row's last prompt position only (`logits_at`): logits
    [nb, 1, V] where the other families compute [nb, bucket, V] and keep a
    row.

    A model whose layers in `model.expert_layer_ids` sow an `expert_load`
    (`ops.moe.Load`, one a layer and forward) has it summed over a decode
    window's steps and carried behind the window's tokens, and returned by
    its prefills: the `emit` and `prefill_dispatch` spans report it.

    The layers in `model.index_layer_ids` (learned sparse attention) hold an
    index pool beside their K/V pages and choose, a decode step and KV head,
    the pages they walk; what they sow as `page_load` (pages selected, pages
    visible) rides behind a window's tokens as an `expert_load` does and is
    reported by the `emit` span.

    A model with `block_length` B > 1 generates by diffusion over aligned
    blocks of B positions (`_block_decode`): prefill only fills the cache
    with the prompt's whole blocks, a decode window is `decode_steps / B`
    blocks of `denoising_steps` forwards each, and what is carried between
    windows is two blocks' ids, not one last token: the block a row is on
    and the one before it, whose K/V the next window's first forward stores
    under its revealed ids. It runs under the same limits as a model
    with state layers (prefix sharing would hold: whole pages are whole
    blocks; it is off because an admission that finds all its blocks cached
    is not built).
    """

    def __init__(self, model, params, cfg: EngineConfig, mesh=None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self._state_layers = len(model.state_layer_ids)
        # The window of a model with ring layers (0: none); `init_cache`
        # refuses one that is not whole pages.
        self._ring_window = (int(model.sliding_window)
                             if model.ring_layer_ids else 0)
        self._latent_layers = len(model.latent_layer_ids)
        special = ("has state layers" if self._state_layers else
                   "generates by blocks" if self._block > 1 else
                   "has ring layers" if self._ring_window else
                   "has latent layers" if self._latent_layers else "")
        if special and cfg.lora_rank > 0:
            raise NotImplementedError(
                f"{type(model).__name__} {special} and cannot run with "
                "lora_rank > 0: LoRA banks are built for attention "
                "projections of every layer")
        if self._block > 1 and (max(1, cfg.decode_steps) % self._block
                                or cfg.page_size % self._block):
            raise ValueError(
                f"decode_steps {cfg.decode_steps} and page_size "
                f"{cfg.page_size} must be multiples of "
                f"{type(model).__name__}'s block_length {self._block}")
        self.cache_cfg = PagedCacheConfig(
            num_pages=cfg.resolved_num_pages() + 1,  # +1: OOB drop page
            page_size=cfg.page_size, max_seqs=cfg.max_seqs,
            max_pages_per_seq=cfg.max_pages_per_seq)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ray_tpu.models import sharding_rules
            from ray_tpu.parallel.sharding import shard_tree

            rules = sharding_rules(model)
            if rules is None:
                raise NotImplementedError(
                    f"{type(model).__name__} has no parameter sharding "
                    "rules: it cannot run under a mesh")

            # The attention kernels need the mesh to run under shard_map.
            self.model = model = model.clone(mesh=mesh)
            # No-op for parameters that were initialized into these
            # shardings (LLMServer); places a host or one-device tree.
            params = shard_tree(params, rules.tree_shardings(mesh, params))
            self._replicated = NamedSharding(mesh, PartitionSpec())
        self.params = params
        # What the replica holds at rest, whole-tree bytes by dtype (the
        # loader rounds to the compute dtype what the model would convert).
        self.params_report = self._describe_params()
        _fr.mark("ray_tpu.engine.params_placed",
                 **{f"{k}_bytes": v for k, v in self.params_report.items()})
        # Per layer what the model keeps between steps; donated argument 1
        # of both programs.
        self.caches = model.init_cache(self.cache_cfg, mesh)
        self.cache_report = self._describe_cache()
        _fr.mark("ray_tpu.engine.cache_built", **self.cache_report)
        self.allocator = PageAllocator(self.cache_cfg)
        # reserve nothing: allocator hands out real pages; the scatter's
        # drop-page is index num_pages (out of bounds by construction).
        self.waiting: deque = deque()
        self.running: Dict[int, Request] = {}
        # host mirrors of device state
        self.page_table = np.zeros(
            (cfg.max_seqs, cfg.max_pages_per_seq), np.int32)
        self.seq_lens = np.zeros((cfg.max_seqs,), np.int32)
        # What a row's next decode step is fed: its last token, or (block
        # generation) the ids of the block awaiting its commit, -1 where a
        # row has none, then of the block it is on, MASK where unrevealed.
        self.last_tokens = (
            np.zeros((cfg.max_seqs,), np.int32) if self._block == 1 else
            np.tile(np.repeat(np.int32([-1, model.mask_token_id]),
                              self._block), (cfg.max_seqs, 1)))
        self.temps = np.zeros((cfg.max_seqs,), np.float32)
        self.top_ps = np.ones((cfg.max_seqs,), np.float32)
        self.top_ks = np.zeros((cfg.max_seqs,), np.int32)
        # Per-slot PRNG chains (seedable per request). Live on device and
        # advance functionally inside the jitted steps — only for slots
        # that actually sampled, so a request's stream is a pure function
        # of its seed and its own token count.
        self._keys_dev = jnp.asarray(
            jax.random.split(jax.random.PRNGKey(0), cfg.max_seqs))
        self._seed_counter = 0
        # Jitted decode variants keyed by (rich_sampling, want_logprobs):
        # the common greedy path pays for neither the top-p/top-k sort
        # machinery nor the logprob softmax.
        self._decode_fns: Dict[Tuple[bool, bool], Callable] = {}
        self._prefill_fns: Dict[Tuple[int, int, bool, bool],
                                Callable] = {}
        self._free_slots = list(range(cfg.max_seqs))
        self.prefix_cache = (PrefixCache(self.allocator)
                             if cfg.enable_prefix_cache else None)
        if special and self.prefix_cache is not None:
            # A shared page carries K/V and no state (and no ring): a
            # sharer's state layers would start from zero in the middle of
            # its prompt. (A latent layer's prefill reads no cached page.)
            logger.info("%s %s: prefix sharing is off",
                        type(model).__name__, special)
            self.prefix_cache = None
        # LoRA banks (slot 0 = zero adapter = base model).
        self.lora_banks: Optional[Dict[str, Any]] = None
        self._lora_slots: Dict[str, int] = {}
        self.lora_idx = np.zeros((cfg.max_seqs,), np.int32)
        if cfg.lora_rank > 0:
            self.lora_banks = self._init_lora_banks()
        # Pipelined dispatch state: the window in flight, whether a slot
        # was freed since the last dispatch, and the dispatches counted.
        self._inflight: Optional[_Window] = None
        self._freed = False
        self._windows = {"unchained": 0, "none": 0, "finish": 0,
                         "admission": 0}
        # What the prefills and decode windows read so far have routed.
        self._expert_load = dict.fromkeys(self._load_names, 0)
        # Compile record: (kind, key) -> [jit cache size after the last
        # call, argument signature it was last traced for], and the last
        # records of programs built and retraced.
        self._programs: Dict[Tuple[str, tuple], list] = {}
        self._program_records: List[Dict[str, Any]] = []
        self._programs_built = 0
        self._programs_retraced = 0
        self._m_queue_wait = _um.get_histogram(
            "ray_tpu_llm_queue_wait_seconds",
            "Request made to admitted into a slot",
            boundaries=_LATENCY_BUCKETS)
        self._m_prefill = _um.get_histogram(
            "ray_tpu_llm_prefill_seconds",
            "Admission to the first token on the host",
            boundaries=_LATENCY_BUCKETS)
        self._m_programs = _um.get_counter(
            "ray_tpu_llm_programs_built_total",
            "Prefill/decode programs built or retraced by the engine",
            tag_keys=("kind",))

    @property
    def _block(self) -> int:
        """Positions a decode step makes for a row: 1, or the block length
        of a model that generates by diffusion over blocks."""
        return int(self.model.block_length)

    @property
    def _load_names(self) -> Tuple[str, ...]:
        """What a row of the load behind a window's tokens counts."""
        return _PAGE_LOAD if self.model.index_layer_ids else _EXPERT_LOAD

    @property
    def _head_last(self) -> bool:
        """Whether a prefill runs the head on each row's last prompt
        position only: the model says so (`num_logits_to_keep`), as it says
        `state_layer_ids`, and takes `logits_at`."""
        return self.model.num_logits_to_keep == 1

    def _describe_params(self) -> Dict[str, int]:
        """Bytes of the parameter tree by dtype, from shapes alone."""
        held: Dict[str, int] = {}
        for x in jax.tree.leaves(self.params):
            name = np.dtype(x.dtype).name
            held[name] = held.get(name, 0) + _leaf_bytes(x)
        return held

    def _describe_cache(self) -> Dict[str, int]:
        """Layers and bytes of the cache by kind, from shapes alone and as
        the device lays them out: a minor axis fills whole lanes. A model
        with ring layers reports them apart from the allocator's pages, one
        with latent layers those apart from K/V, and one with index layers
        says how many of its K/V layers hold an index pool and its bytes;
        one with state layers says what share of their bytes is padding of
        the lanes; one with layers that hold nothing counts them."""
        state = set(self.model.state_layer_ids)
        ring = set(self.model.ring_layer_ids)
        latent = set(self.model.latent_layer_ids)
        cacheless = set(self.model.cacheless_layer_ids)
        size = lambda layer: sum(map(_laid_out_bytes,
                                     jax.tree.leaves(layer)))
        other = state | ring | latent | cacheless
        index = self.model.index_layer_ids
        report = {
            "kv_layers": len(self.caches) - len(other),
            "state_layers": len(state),
            # (an index layer's third array is counted apart, below)
            "kv_bytes": sum(size(c[:2]) for i, c in enumerate(self.caches)
                            if i not in other),
            "state_bytes": sum(size(self.caches[i]) for i in state)}
        if state:
            logical = sum(_leaf_bytes(x) for i in state
                          for x in jax.tree.leaves(self.caches[i]))
            report["state_padding_pct"] = round(
                100.0 * (1 - logical / report["state_bytes"]), 2)
        for kind, layers in (("ring", ring), ("latent", latent)):
            if layers:
                report.update({f"{kind}_layers": len(layers),
                               f"{kind}_bytes": sum(
                                   size(self.caches[i]) for i in layers)})
        if index:   # those of the K/V layers that hold an index pool
            report.update(index_layers=len(index), index_bytes=sum(
                size(self.caches[i][2]) for i in index))
        if cacheless:
            report["cacheless_layers"] = len(cacheless)
        return report

    # ------------------------------------------------------------------
    # LoRA multiplexing
    # ------------------------------------------------------------------
    def _init_lora_banks(self) -> Dict[str, Any]:
        cfg, mcfg = self.cfg, self.model.cfg
        K = cfg.max_loras + 1  # + the zero adapter
        r = cfg.lora_rank
        out_dims = {
            "q_proj": mcfg.num_heads * mcfg.head_dim,
            "k_proj": mcfg.num_kv_heads * mcfg.head_dim,
            "v_proj": mcfg.num_kv_heads * mcfg.head_dim,
            "o_proj": mcfg.hidden_size,
        }
        in_dims = {"q_proj": mcfg.hidden_size, "k_proj": mcfg.hidden_size,
                   "v_proj": mcfg.hidden_size,
                   "o_proj": mcfg.num_heads * mcfg.head_dim}
        banks: Dict[str, Any] = {}
        for i in range(mcfg.num_layers):
            banks[f"layers_{i}"] = {
                t: {"a": jnp.zeros((K, r, in_dims[t]), jnp.float32),
                    "b": jnp.zeros((K, out_dims[t], r), jnp.float32),
                    # per-SLOT scale: adapters share the bank, so a
                    # scalar here would let the last load rescale every
                    # other adapter's delta
                    "scale": jnp.ones((K,), jnp.float32)}
                for t in cfg.lora_targets}
        return banks

    def load_lora(self, name: str, adapter: Dict[str, Any],
                  scale: float = 1.0) -> int:
        """Install adapter weights into a bank slot. `adapter` maps
        "layers_<i>" → {proj: (A [r, Din], B [Dout, r])}. Returns the
        slot. Re-loading a name overwrites its slot; bank VALUES update
        without recompiling the jitted steps (they are traced args)."""
        if self.lora_banks is None:
            raise ValueError("engine built with lora_rank=0")
        slot = self._lora_slots.get(name)
        if slot is None:
            if len(self._lora_slots) >= self.cfg.max_loras:
                raise ValueError(
                    f"all {self.cfg.max_loras} LoRA slots in use")
            slot = len(self._lora_slots) + 1  # 0 = zero adapter
            self._lora_slots[name] = slot
        for layer, projs in adapter.items():
            bank_layer = self.lora_banks.get(layer)
            if bank_layer is None:
                continue
            for proj, (a, b) in projs.items():
                if proj not in bank_layer:
                    continue
                bank = bank_layer[proj]
                bank["a"] = bank["a"].at[slot].set(
                    jnp.asarray(a, jnp.float32))
                bank["b"] = bank["b"].at[slot].set(
                    jnp.asarray(b, jnp.float32))
                bank["scale"] = bank["scale"].at[slot].set(float(scale))
        return slot

    def lora_slot(self, name: str) -> int:
        if not name:
            return 0
        slot = self._lora_slots.get(name)
        if slot is None:
            raise KeyError(f"LoRA adapter {name!r} not loaded")
        return slot

    # ------------------------------------------------------------------
    # Jitted steps
    # ------------------------------------------------------------------
    def _sampler(self, rich: bool, want_lp: bool, L: int):
        """Shared sample step for the prefill/decode variants.

        Takes keys [n,2], logits [n,V], temps/top_ps [n], top_ks [n].
        Returns (toks [n], new_keys [n,2], lp) where lp is None or
        (chosen_logp [n], top_vals [n,L], top_ids [n,L]).

        rich=True compiles nucleus + top-k truncation (a [n,V] sort per
        step); rich=False is plain temperature/greedy. Both advance each
        row's PRNG chain exactly once per call, so a seeded request's
        stream is a pure function of its seed and its own token count."""

        def sample(keys, logits, temps, top_ps, top_ks):
            split = jax.vmap(lambda k: jax.random.split(k))(keys)
            use, nxt = split[:, 0], split[:, 1]
            scaled = logits / jnp.maximum(temps, 1e-3)[:, None]
            if rich:
                V = logits.shape[-1]
                # top-k: drop strictly below the k-th largest (k=0 off)
                desc = jnp.sort(scaled, axis=-1)[:, ::-1]
                kth = jnp.take_along_axis(
                    desc, jnp.clip(top_ks - 1, 0, V - 1)[:, None],
                    axis=-1)
                scaled = jnp.where(
                    (top_ks[:, None] > 0) & (scaled < kth),
                    -jnp.inf, scaled)
                # top-p over the surviving mass: keep a token iff the
                # cumulative prob of STRICTLY higher-ranked tokens is
                # still < p (the argmax token always survives)
                desc = jnp.sort(scaled, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = (cum - probs) < top_ps[:, None]
                cutoff = jnp.min(
                    jnp.where(keep, desc, jnp.inf), axis=-1,
                    keepdims=True)
                scaled = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
            sampled = jax.vmap(jax.random.categorical)(use, scaled)
            toks = jnp.where(temps > 0, sampled,
                             jnp.argmax(logits, axis=-1)).astype(jnp.int32)
            lp = None
            if want_lp:
                # OpenAI logprobs report the UNSCALED model distribution
                logp = jax.nn.log_softmax(logits, axis=-1)
                chosen = jnp.take_along_axis(
                    logp, toks[:, None], axis=-1)[:, 0]
                top_vals, top_ids = jax.lax.top_k(logp, L)
                lp = (chosen, top_vals, top_ids)
            return toks, nxt, lp

        return sample

    def _decode_fn(self, rich: bool, want_lp: bool):
        fn = self._decode_fns.get((rich, want_lp))
        if fn is not None:
            return fn
        if self._block > 1:
            fn = jax.jit(self._block_decode(rich, want_lp),
                         donate_argnums=(1,))
            self._decode_fns[(rich, want_lp)] = fn
            return fn
        model = self.model
        K = max(1, self.cfg.decode_steps)
        L = max(1, self.cfg.max_logprobs)
        sample = self._sampler(rich, want_lp, L)

        def one(params, caches, last_tokens, page_table, seq_lens, active,
                temps, top_ps, top_ks, keys, lora, lora_idx):
            # positions of the NEW token = current length (before write).
            positions = seq_lens[:, None]
            (logits, new_caches), sown = model.apply(
                {"params": params}, last_tokens[:, None],
                positions=positions, paged_kv=caches,
                page_table=page_table, write_mask=active[:, None],
                seq_lens=seq_lens + 1, lora=lora, lora_idx=lora_idx,
                mutable=_SOWN)
            logits = logits[:, 0].astype(jnp.float32)  # [B, V]
            toks, nxt, lp = sample(keys, logits, temps, top_ps, top_ks)
            # inactive slots keep their chain position
            nxt = jnp.where(active[:, None], nxt, keys)
            return toks, new_caches, nxt, lp, _sown_load(sown)

        def decode(params, caches, last_tokens, page_table, seq_lens,
                   active, temps, top_ps, top_ks, keys, lora, lora_idx,
                   steps=None):
            """`steps` (traced, at most K) token steps for every row; the
            results keep K rows, of which the first `steps` are filled. Left
            out (whoever lowers the program from shapes alone), K. Where the
            model sows an `expert_load` or a `page_load`, the tokens come flat
            with its sums over the window's steps [layers, 5 or 2] behind
            them, in the one int32 result (as `_block_decode`'s)."""
            B = last_tokens.shape[0]
            # A free slot's row is stale while windows chain on the device
            # (the host's mirror says 0, the device's what its last request
            # left): it attends over nothing, whoever dispatched the window.
            seq_lens = jnp.where(active, seq_lens, 0)
            out = jnp.zeros((K, B), jnp.int32)
            out_lp = jnp.zeros((K, B), jnp.float32)
            out_tv = jnp.zeros((K, B, L), jnp.float32)
            out_ti = jnp.zeros((K, B, L), jnp.int32)
            # (the model says which layers sow: a trace of the forward to
            # find out would cost every family seconds a program)
            load = jnp.zeros((len(model.index_layer_ids
                                  or model.expert_layer_ids),
                              len(self._load_names)), jnp.int32)

            def body(j, carry):
                (caches, toks, lens, keys, out, out_lp, out_tv,
                 out_ti, load) = carry
                toks, caches, keys, lp, seen = one(
                    params, caches, toks, page_table, lens, active,
                    temps, top_ps, top_ks, keys, lora, lora_idx)
                out = out.at[j].set(toks)
                if lp is not None:
                    out_lp = out_lp.at[j].set(lp[0])
                    out_tv = out_tv.at[j].set(lp[1])
                    out_ti = out_ti.at[j].set(lp[2])
                return (caches, toks, lens + 1, keys, out, out_lp,
                        out_tv, out_ti, load + seen)

            (caches, last, lens, keys, out, out_lp, out_tv, out_ti,
             load) = jax.lax.fori_loop(
                    0, K if steps is None else steps, body,
                    (caches, last_tokens, seq_lens, keys, out, out_lp,
                     out_tv, out_ti, load))
            if load.size:
                out = jnp.concatenate([out.reshape(-1), load.reshape(-1)])
            # Final last_tokens/seq_lens feed the NEXT window's dispatch
            # without a host round trip (pipeline_dispatch).
            lp_out = (out_lp, out_tv, out_ti) if want_lp else None
            return out, last, lens, caches, keys, lp_out

        fn = jax.jit(decode, donate_argnums=(1,))
        self._decode_fns[(rich, want_lp)] = fn
        return fn

    def _block_decode(self, rich: bool, want_lp: bool):
        """The decode program of a model that generates by diffusion over
        blocks of B positions: a window is `decode_steps / B` blocks. A
        block is `denoising_steps` passes, each one forward over the block's
        ids [rows, B] (its K/V written in place at lens .. lens+B-1, so all
        B queries see the earlier blocks and this one), every position
        sampled, and B / steps of the masked ones revealed by the model's
        `remasking`; then lens += B. The K/V its last pass left are those of
        ids still partly MASK: the block is committed (its K/V stored under
        its revealed ids) inside the first pass of the block that follows
        it, which runs 2 * rows rows under the same page-table rows: row
        (r, 0) the block before, at lens-B .. lens-1 and seeing keys below
        lens, row (r, 1) the block the sequence is on. Every row's K/V is
        written before any row attends, so (r, 1) sees what (r, 0) has just
        stored, the weights are streamed once for both, and only the rows
        (r, 1) are sampled. A request's last block is never committed:
        nothing reads it.

        Same arguments and results as `decode`, with `last_tokens`
        [rows, 2B]: the block awaiting its commit (-1 where there is none: a
        row just admitted, whose whole prompt blocks prefill has stored) and
        the block a row is on (a prompt's remainder, then MASK); the token
        of a position is reported with the logprobs of the pass that
        revealed it, and behind the tokens [K, rows], in the one int32
        result, [layers, 5] sums over the window's forwards of what the model
        sows as `expert_load` (`ops.moe.Load`)."""
        model = self.model
        B, T = self._block, model.denoising_steps
        blocks = max(1, self.cfg.decode_steps) // B
        L = max(1, self.cfg.max_logprobs)
        mask_id = model.mask_token_id
        by_confidence = model.remasking == "low_confidence_static"
        sample = self._sampler(rich, want_lp, L)

        def decode(params, caches, last_tokens, page_table, seq_lens,
                   active, temps, top_ps, top_ks, keys, lora, lora_idx):
            rows = last_tokens.shape[0]
            seq_lens = jnp.where(active, seq_lens, 0)  # as in `decode`
            per_pos = lambda a: jnp.repeat(a, B, axis=0)

            def forward(caches, ids, starts, table, write, logits_from=0):
                """ids [n, B] at positions starts .. starts+B-1 of the
                page-table rows `table`, stored where `write` [n]; logits
                of the rows from `logits_from` on."""
                (logits, caches), sown = model.apply(
                    {"params": params}, ids,
                    positions=starts[:, None] + jnp.arange(B)[None, :],
                    paged_kv=caches, page_table=table,
                    write_mask=jnp.broadcast_to(write[:, None], ids.shape),
                    seq_lens=starts + B, logits_from=logits_from,
                    mutable=["expert_load"])
                return logits, caches, _sown_load(sown)

            def reveal(logits, ids, keys, rec):
                """A pass's sampling: B / steps more of `ids` revealed."""
                logits = logits.astype(jnp.float32)  # [rows, B, V]
                # every position samples from its row's chain, which moves
                # on once a pass
                pos_keys = jax.vmap(lambda k: jax.vmap(
                    lambda j: jax.random.fold_in(k, j))(jnp.arange(B)))(keys)
                toks, _, lp = sample(
                    pos_keys.reshape(rows * B, -1),
                    logits.reshape(rows * B, -1), per_pos(temps),
                    per_pos(top_ps), per_pos(top_ks))
                toks = toks.reshape(rows, B)
                masked = ids == mask_id
                if by_confidence:
                    conf = jnp.where(masked, jnp.max(
                        jax.nn.softmax(logits, axis=-1), axis=-1), -1.0)
                    left = jnp.tril(jnp.ones((B, B), bool), -1)
                    ahead = jnp.sum(
                        (conf[:, None, :] > conf[:, :, None])
                        | ((conf[:, None, :] == conf[:, :, None]) & left),
                        axis=-1)
                else:
                    ahead = jnp.cumsum(masked, axis=-1) - masked
                show = masked & (ahead < B // T)
                ids = jnp.where(show, toks, ids)
                if lp is not None:
                    rec = tuple(
                        jnp.where(show.reshape((rows, B) + (1,) * (
                            new.ndim - 1)), new.reshape((rows, B)
                                                        + new.shape[1:]), old)
                        for old, new in zip(rec, lp))
                nxt = jax.vmap(lambda k: jax.random.split(k)[1])(keys)
                return ids, jnp.where(active[:, None], nxt, keys), rec

            def fused(caches, before, ids, lens):
                """A block's first pass with the commit of the block
                `before` it riding along: rows (r, 0) then rows (r, 1). A
                row with none (or under B tokens) runs its first half at
                clamped positions, stored nowhere, for nothing."""
                return forward(
                    caches, jnp.concatenate([jnp.maximum(before, 0), ids]),
                    jnp.concatenate([jnp.maximum(lens - B, 0), lens]),
                    jnp.concatenate([page_table, page_table]),
                    jnp.concatenate([active & (before[:, 0] >= 0), active]),
                    logits_from=rows)

            def denoise(_, carry):
                caches, ids, lens, keys, rec, load = carry
                logits, caches, seen = forward(caches, ids, lens, page_table,
                                               active)
                ids, keys, rec = reveal(logits, ids, keys, rec)
                return caches, ids, lens, keys, rec, load + seen

            def block(i, carry):
                caches, pair, lens, keys, out, out_lp, load = carry
                rec = (jnp.zeros((rows, B), jnp.float32),
                       jnp.zeros((rows, B, L), jnp.float32),
                       jnp.zeros((rows, B, L), jnp.int32))
                before, ids = pair[:, :B], pair[:, B:]
                logits, caches, seen = fused(caches, before, ids, lens)
                ids, keys, rec = reveal(logits, ids, keys, rec)
                caches, ids, lens, keys, rec, load = jax.lax.fori_loop(
                    1, T, denoise, (caches, ids, lens, keys, rec,
                                    load + seen))
                at = lambda new, old: jax.lax.dynamic_update_slice(
                    old, jnp.moveaxis(new, 1, 0),
                    (i * B,) + (0,) * (old.ndim - 1))
                out = at(ids, out)
                if want_lp:
                    out_lp = tuple(map(at, rec, out_lp))
                pair = jnp.concatenate([ids, jnp.full_like(ids, mask_id)], 1)
                return caches, pair, lens + B, keys, out, out_lp, load

            K = blocks * B
            out_lp = (jnp.zeros((K, rows), jnp.float32),
                      jnp.zeros((K, rows, L), jnp.float32),
                      jnp.zeros((K, rows, L), jnp.int32))
            probe = jax.eval_shape(fused, caches, last_tokens[:, :B],
                                   last_tokens[:, B:], seq_lens)[2]
            caches, last, lens, keys, out, out_lp, load = jax.lax.fori_loop(
                0, blocks, block,
                (caches, last_tokens, seq_lens, keys,
                 jnp.zeros((K, rows), jnp.int32), out_lp,
                 jnp.zeros(probe.shape, jnp.int32)))
            packed = jnp.concatenate([out.reshape(-1), load.reshape(-1)])
            return (packed, last, lens, caches, keys,
                    out_lp if want_lp else None)

        return decode

    def _prefill_fn(self, bucket: int, nb: int = 1, rich: bool = False,
                    want_lp: bool = False):
        """Batched prefill: `nb` sequences in ONE pass over the weights —
        a wave of admissions streams the parameters once
        instead of once per request, the dominant term in TTFT for
        HBM-bound models."""
        fn = self._prefill_fns.get((bucket, nb, rich, want_lp))
        if fn is not None:
            return fn
        model = self.model
        L = max(1, self.cfg.max_logprobs)
        sample = self._sampler(rich, want_lp, L)

        def prefill(params, caches, ids, rows, starts, true_lens,
                    temps, top_ps, top_ks, all_keys, slots, lora,
                    lora_idx, last_tokens=None, seq_lens=None):
            """`last_tokens`/`seq_lens` [max_seqs]: what the decode window
            behind this prefill is fed, returned with the wave's rows
            scattered in (each first token and prompt length), as
            `all_keys` is. Left out (whoever lowers the program from shapes
            alone, block generation), None comes back."""
            # ids [nb, bucket] = each prompt's SUFFIX from absolute
            # position starts[i] (>0 when a cached prefix run was shared
            # into its page-table row); causal within each sequence.
            positions = starts[:, None] + jnp.arange(bucket)[None, :]
            mask = jnp.arange(bucket)[None, :] < true_lens[:, None]
            # The head on one position a row where the model takes it so:
            # logits [nb, 1, V], not [nb, bucket, V] of which one row is kept.
            at = {"logits_at": true_lens - 1} if self._head_last else {}
            (logits, new_caches), sown = model.apply(
                {"params": params}, ids, positions=positions,
                paged_kv=caches, page_table=rows,
                write_mask=mask, seq_lens=starts + true_lens,
                lora=lora, lora_idx=lora_idx, slots=slots,
                mutable=_SOWN, **at)
            if self._block > 1:
                # Cache fill only: the first block's passes sample its
                # tokens, and with the logits unused no head is compiled
                # (nor the load counted: the host reads nothing of this
                # program).
                return None, new_caches, all_keys, None, None, None, None
            # [layers, 5] of this forward, or None of a model without experts
            load = _sown_load(sown) if sown else None
            last = (logits[:, 0] if at else
                    logits[jnp.arange(nb), true_lens - 1]).astype(
                jnp.float32)  # [nb, V]
            keys = all_keys[slots]
            toks, nxt, lp = sample(keys, last, temps, top_ps, top_ks)
            # write the advanced chains back into the [B,2] key table
            all_keys = all_keys.at[slots].set(nxt)
            if last_tokens is not None:
                last_tokens = last_tokens.at[slots].set(toks)
                seq_lens = seq_lens.at[slots].set(starts + true_lens)
            return (toks, new_caches, all_keys, lp, load, last_tokens,
                    seq_lens)

        fn = jax.jit(prefill, donate_argnums=(1,))
        self._prefill_fns[(bucket, nb, rich, want_lp)] = fn
        return fn

    # ------------------------------------------------------------------
    # Compile record
    # ------------------------------------------------------------------
    def _run_program(self, kind: str, key: tuple, fn, args: tuple):
        """Call a jitted program and keep the compile record: the first
        call of a program builds it (trace, compile or cache load), and a
        later call that grows the function's jit cache is a retrace: the
        same key met an argument signature it had not seen."""
        entry = self._programs.get((kind, key))
        t0 = time.monotonic()
        out = fn(*args)
        size = fn._cache_size()
        if entry is not None and size <= entry[0]:
            return out
        seconds = time.monotonic() - t0
        signature = _signature(args)
        record = {"kind": kind, "key": list(key), "t": time.time(),
                  "seconds": seconds}
        if entry is None:
            record["event"] = "built"
            self._programs_built += 1
        else:
            record["event"] = "retraced"
            record["differs"] = _signature_diff(entry[1], signature)
            self._programs_retraced += 1
        self._programs[(kind, key)] = [size, signature]
        # Rebound, not appended to: `programs_report` reads it from other
        # threads.
        self._program_records = (self._program_records
                                 + [record])[-_PROGRAM_RECORDS:]
        self._m_programs.inc(tags={"kind": kind})
        _fr.mark("ray_tpu.program." + record["event"], kind=kind,
                 key=str(key), seconds=seconds)
        logger.info("engine %s program %s %s in %.3fs%s", record["event"],
                    kind, key, seconds,
                    f": {record['differs']}" if entry is not None else "")
        return out

    def programs_report(self) -> Dict[str, Any]:
        """Programs built (first call of a new key) and retraced (a key
        that is in the table, traced again for other arguments), with the
        last records of both."""
        return {"built": self._programs_built,
                "retraced": self._programs_retraced,
                "records": list(self._program_records)}

    def _sampling_flags(self, reqs) -> Tuple[bool, bool]:
        rich = any(r.temperature > 0 and (r.top_p < 1.0 or r.top_k > 0)
                   for r in reqs)
        want_lp = any(r.logprobs > 0 for r in reqs)
        return rich, want_lp

    def _dev(self, x):
        """Host → device, replicated across the mesh when TP is on (scalar
        control state rides along every shard)."""
        arr = jnp.asarray(x)
        if self.mesh is not None:
            return jax.device_put(arr, self._replicated)
        return arr

    # ------------------------------------------------------------------
    # Scheduler
    # ------------------------------------------------------------------
    def add_request(self, req: Request) -> None:
        # Multi-step decode may overshoot by up to decode_steps-1 writes.
        need = (len(req.prompt_ids) + req.max_tokens
                + max(1, self.cfg.decode_steps) - 1)
        if need > self.cache_cfg.max_context:
            raise ValueError(
                f"request needs up to {need} cache slots; max context is "
                f"{self.cache_cfg.max_context}")
        if not (0.0 < req.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {req.top_p}")
        if req.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {req.top_k}")
        if req.logprobs < 0 or req.logprobs > self.cfg.max_logprobs:
            raise ValueError(
                f"logprobs must be in [0, {self.cfg.max_logprobs}], got "
                f"{req.logprobs}")
        if req.lora_id:
            if self.lora_banks is None:
                raise KeyError(
                    f"LoRA adapter {req.lora_id!r} requested but the "
                    "engine was built with lora_rank=0")
            self.lora_slot(req.lora_id)  # validate HERE, before any
            # admission-time state mutation — a typo'd adapter must fail
            # this one request, not poison the running batch
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def num_running(self) -> int:
        return len(self.running)

    def step(self) -> List[StepOutput]:
        """Admit + prefill waiting requests, then one decode window.

        With pipeline_dispatch, the next window is dispatched from the
        in-flight window's DEVICE outputs before its tokens reach the
        host, so host-side stop/stream handling overlaps device compute
        (the "enqueue N+1 before N returns" scheme; reference analog:
        vLLM async scheduling). The chain outlives a change of the slot
        set: after a finish the next window takes `active` and the rest
        from the host mirrors as every dispatch does, and the freed row,
        stale on the device, is inactive; at an admission the window behind
        the wave's prefills is queued before the host reads a first token,
        with the new rows merged in on the device (`_admit`). A window is
        read with none queued behind it only when nothing runs, when every
        request ends inside it, and at a block-generating model's
        admissions."""
        out: List[StepOutput] = []
        with _fr.span("ray_tpu.engine.step", running=len(self.running),
                      waiting=len(self.waiting),
                      inflight=self._inflight is not None):
            self._step(out)
        return out

    def _step(self, out: List[StepOutput]) -> None:
        inflight = self._inflight
        admitted = self._admit(out)
        if self._inflight is not inflight:
            # `_admit` queued the next window behind its prefills: the one
            # that was in flight is this step's.
            self._process_window(inflight, out, why="chained")
        else:
            if admitted and inflight is not None:
                # Block generation: the first block's ids are in the host
                # mirror alone, so the window in flight is drained and the
                # next dispatched from the host.
                self._process_window(inflight, out, why="admitted")
                self._inflight = None
            if self.running:
                self._decode(out)
        if not self.running and self._inflight is not None:
            # Nothing runs: every row of the window in flight is stale.
            self._process_window(self._inflight, out, why="idle")
            self._inflight = None

    def _decode(self, out: List[StepOutput]) -> None:
        """One decode window for the running requests: dispatch the next
        and, pipelined, read the one before it."""
        K = max(1, self.cfg.decode_steps)
        if self._inflight is None:
            self._ensure_decode_pages(K)
            self._inflight = self._dispatch_window()
            if not self.cfg.pipeline_dispatch:
                self._process_window(self._inflight, out)
                self._inflight = None
            return
        # Pipelined: cover the NEXT window's writes too (both at their
        # longest), then chain the dispatch off the in-flight window's
        # device state. Skip the chain when every request ends inside the
        # in-flight window — the chained window would be pure waste.
        if all(r.generated + self._inflight.steps - r.skip >= r.max_tokens
               for r in self.running.values()):
            self._process_window(self._inflight, out, why="all_finishing")
            self._inflight = None
            return
        self._ensure_decode_pages(2 * K)
        nxt = self._dispatch_window(self._inflight.last, self._inflight.lens)
        # A request that ends inside the window read here is a row of `nxt`
        # too. Its tokens there are passed over (`_emit_window`), and its
        # stale page and state writes are harmless: released pages and the
        # slot's state row get re-prefilled by strictly later programs on
        # the ordered device stream. The surviving rows' device last/lens
        # are right, so the chain goes on.
        self._process_window(self._inflight, out, why="chained")
        self._inflight = nxt

    def _window_steps(self) -> int:
        """Token steps of the window about to be dispatched: `decode_steps`
        when nobody can be admitted at its end (no slot is free, or the
        head of the queue finds too few pages: `_admit` has just left it
        there), half of that while a slot is free, since a request that
        arrives meanwhile waits for all that is on the device: this window
        and the rest of the one before it. Block generation keeps
        `decode_steps`: its program's two blocks a window are a static count
        (the carry and the fused commit were measured at that chain), and
        `experts_touched_pct` reads every window as the same forwards."""
        full = max(1, self.cfg.decode_steps)
        if self._block > 1 or not self._free_slots:
            return full
        if self.waiting and not self.allocator.can_allocate(
                len(self.waiting[0].prompt_ids) + 1):
            return full
        return max(1, full // 2)

    def _decode_args(self, last=None, lens=None, steps=None) -> tuple:
        """Arguments of the decode program: control state from the host
        mirrors, except `last`/`lens` when chaining off a window that is
        still on the device, then the window's token steps (the whole
        `decode_steps` if not given)."""
        active = np.zeros((self.cfg.max_seqs,), bool)
        for slot in self.running:
            active[slot] = True
        args = (
            self.params, self.caches,
            self._dev(self.last_tokens) if last is None else last,
            self._dev(self.page_table),
            self._dev(self.seq_lens) if lens is None else lens,
            self._dev(active), self._dev(self.temps),
            self._dev(self.top_ps), self._dev(self.top_ks),
            self._keys_dev, self.lora_banks, self._dev(self.lora_idx))
        if self._block > 1:  # the block program's count of blocks is static
            return args
        return args + (self._dev(
            np.int32(steps or max(1, self.cfg.decode_steps))),)

    def _dispatch_window(self, last=None, lens=None,
                         admission: bool = False) -> _Window:
        """Dispatch a window for the running requests, chained off `last`
        and `lens` on the device where given. `across` says what a chained
        dispatch's chain outlived since the dispatch before it: an
        `admission` (`last`/`lens` hold the wave's rows), a `finish`, or
        `none`."""
        across = ("none" if last is None else "admission" if admission
                  else "finish" if self._freed else "none")
        self._freed = False
        self._windows[across if last is not None else "unchained"] += 1
        rich, want_lp = self._sampling_flags(self.running.values())
        key = (rich, want_lp)
        K, B = self._window_steps(), self._block
        # Forwards this dispatch runs for every row: one a token, or for
        # each block its denoising passes, the first of which commits the
        # block before it. A row fresh from admission has none to commit in
        # its first block (a chained window's rows all have one).
        denoise = K if B == 1 else K // B * self.model.denoising_steps
        fresh = 0 if B == 1 or last is not None else sum(
            int(self.last_tokens[slot, 0] < 0) for slot in self.running)
        # What the window's first step attends over, from the host mirrors
        # (a chained window's rows are up to a window further on): every
        # active row's length, and what of it lies inside the window.
        reach = {}
        if (self._ring_window or self._latent_layers
                or self.model.index_layer_ids):
            held = self.seq_lens[list(self.running)]
            reach = {"context_tokens": int(held.sum())}
        if self._ring_window:
            reach["window_tokens"] = int(
                np.minimum(held, self._ring_window).sum())
        with _fr.span("ray_tpu.engine.dispatch_decode", **reach,
                      active=len(self.running), max_seqs=self.cfg.max_seqs,
                      steps=K, free_slots=len(self._free_slots),
                      chained=last is not None, across=across,
                      new_program=key not in self._decode_fns,
                      state_rows=len(self.running) * self._state_layers,
                      block_length=B, denoise_passes=denoise,
                      commit_passes=0,
                      fused_commits=0 if B == 1 else K // B,
                      fresh_rows=fresh):
            toks, last, lens, self.caches, self._keys_dev, lp = \
                self._run_program("decode", key, self._decode_fn(*key),
                                  self._decode_args(last, lens, K))
        return _Window(toks, last, lens, lp, dict(self.running), K)

    def _count_load(self, load) -> Dict[str, int]:
        """A program's expert load, or the pages its index layers walked
        (sums over its forwards, whatever its shape) as its span's arguments,
        sums over the layers; added to the sums `expert_load_report`
        gives."""
        names = self._load_names
        sums = np.asarray(load).reshape(-1, len(names)).sum(axis=0)
        args = dict(zip(names, map(int, sums)))
        for name, n in args.items():
            self._expert_load[name] += n
        return args

    def expert_load_report(self) -> Dict[str, int]:
        """`ops.moe.Load` summed over every prefill and decode window read so
        far, layers and forwards (all 0 for a model without experts):
        `expert_tiles` over `experts_touched` is how many tiles shared one
        read of an expert's weights. Of a model with index layers, the pages
        its decode steps walked and those their rows held."""
        return dict(self._expert_load)

    def windows_report(self) -> Dict[str, int]:
        """Decode windows dispatched: `unchained` from the host mirrors, and
        chained off the window before by what the chain outlived (`none`,
        `finish`, `admission`)."""
        return dict(self._windows)

    def lowered_decode_text(self) -> str:
        """StableHLO of the greedy decode program as this process lowers
        it, for checking which attention path it holds (the Mosaic kernel
        shows as `tpu_custom_call`). Traces from shapes only: nothing runs,
        and buffers the engine thread may donate meanwhile are not read."""
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self._decode_args())
        return self._decode_fn(False, False).lower(*shapes).as_text()

    def _process_window(self, window, out: Optional[List[StepOutput]],
                        why: str = "unpipelined") -> bool:
        """Block on a window's tokens; update host mirrors and emit
        outputs. out=None discards (pipeline drain). `why` names what made
        the caller wait for this window (the span's argument). Returns True
        if any slot finished."""
        toks, _, _, lp, slots, steps = window
        with _fr.span("ray_tpu.engine.wait_tokens", why=why):
            toks = np.asarray(toks)  # [K, B] (blocks here)
            if lp is not None:
                lp = tuple(np.asarray(a) for a in lp)
        load = {}
        if toks.ndim == 1:
            # Behind the tokens: the expert load (a model that routes) or
            # the pages walked (one with index layers), summed over the
            # window's forwards.
            cut = max(1, self.cfg.decode_steps) * self.cfg.max_seqs
            load = self._count_load(toks[cut:])
            if self.model.index_layer_ids:
                # the selections (a layer and token step) those pages are of
                load["select_calls"] = steps * len(self.model.index_layer_ids)
            toks = toks[:cut].reshape(-1, self.cfg.max_seqs)
        if out is None:
            return False
        toks = toks[:steps]  # the rows the window filled
        with _fr.span("ray_tpu.engine.emit") as sp:
            tokens, running = len(out), len(self.running)
            skipped = self._emit_window(toks, lp, slots, out)
            finished = running - len(self.running)  # one release each
            sp.set(tokens=len(out) - tokens, finished=finished,
                   skipped=skipped, **load)
        return finished > 0

    def _emit_window(self, toks, lp, slots: Dict[int, Request],
                     out: List[StepOutput]) -> int:
        """The host loop over a window's tokens, now on the host. Returns
        the positions it passed over as a prompt's remainder (block
        generation)."""
        K = toks.shape[0]
        block = self._block
        skipped = 0
        for slot, req in slots.items():
            if self.running.get(slot) is not req:
                # It ended before this window was read (inside the one
                # before, or on its first token); the slot may hold the next
                # request by now, whose tokens these are not.
                continue
            if req.done:  # aborted externally (e.g. stop-string match)
                self._release(slot)
                continue
            if block > 1:
                # The window's last block awaits its commit; the next starts
                # as MASK.
                self.last_tokens[slot, :block] = toks[K - block:, slot]
                self.last_tokens[slot, block:] = self.model.mask_token_id
            for j in range(K):
                tok = int(toks[j, slot])
                self.seq_lens[slot] += 1
                if req.skip:
                    req.skip -= 1
                    skipped += 1
                    continue
                if block == 1:
                    self.last_tokens[slot] = tok
                elif not req.generated:
                    self._mark_first_token(req, slot, *req.prefilled)
                req.generated += 1
                finished = (req.generated >= req.max_tokens
                            or (req.stop_token is not None
                                and tok == req.stop_token))
                so = StepOutput(req.request_id, tok, finished)
                if lp is not None and req.logprobs > 0:
                    so.logprob = float(lp[0][j, slot])
                    n = req.logprobs
                    so.top_logprobs = [
                        (int(lp[2][j, slot, i]), float(lp[1][j, slot, i]))
                        for i in range(n)]
                out.append(so)
                if finished:
                    # Tokens past the stop within this window are wasted
                    # compute (multi-step tradeoff); drop them.
                    self._release(slot)
                    break
        return skipped

    def finish_request(self, request_id: str) -> bool:
        """Finish a request early (serving layer stop-string match /
        client disconnect). Safe from the engine-loop thread; the slot is
        released at the next window boundary (an in-flight window's
        remaining tokens for it are dropped)."""
        for req in self.running.values():
            if req.request_id == request_id:
                req.done = True
                return True
        for req in list(self.waiting):
            if req.request_id == request_id:
                self.waiting.remove(req)
                return True
        return False

    def _admit(self, out: List[StepOutput]) -> bool:
        """Admit as many waiting requests as fit. The wave's prefills run
        BATCHED per bucket — one pass over the weights for
        the whole admission wave, not one per request — and the first
        tokens stay on device until every batch is in flight, so TTFT for
        N admissions is ~one weight stream + one host sync.

        With a window in flight, the next is queued behind the prefills
        before the host reads a first token, chained off the in-flight
        window's last tokens and lengths with the wave's rows scattered in by
        the prefill programs: it becomes `_inflight`, and the caller reads
        the one that was. Block generation samples nothing in its prefill
        and leaves the window in flight to its caller's drain."""
        if not (self.waiting and self._free_slots):
            return False
        with _fr.span("ray_tpu.engine.admit",
                      free_slots=len(self._free_slots),
                      free_pages=self.allocator.num_free) as sp:
            entries = self._place_waiting()
            if self._block > 1:
                # nothing was sampled: the first window brings the tokens
                for _, req, _, _, _, nb, cached in self._dispatch_prefills(
                        entries)[0]:
                    req.prefilled = (cached, nb)
            else:
                pending: List[tuple] = []
                if entries:
                    # The programs take last tokens and lengths whether a
                    # window waits for them or not: one signature, so one
                    # program a shape.
                    behind = self._inflight
                    carry = ((self._dev(self.last_tokens),
                              self._dev(self.seq_lens)) if behind is None
                             else (behind.last, behind.lens))
                    pending, carry = self._dispatch_prefills(entries, carry)
                    if behind is not None:
                        self._ensure_decode_pages(
                            2 * max(1, self.cfg.decode_steps))
                        self._inflight = self._dispatch_window(
                            *carry, admission=True)
                self._sync_first_tokens(pending, out)
            sp.set(admitted=len(entries), waiting_left=len(self.waiting))
        return bool(entries)

    def _place_waiting(self) -> List[tuple]:
        """Move waiting requests into free slots while pages last: page
        bookkeeping, prefix sharing and sampling state, nothing on the
        device but each slot's PRNG key."""
        # Flat admission-order list of (slot, req, suffix_ids, cached_len,
        # S, bucket, deps). deps = admission indices of SAME-WAVE requests
        # whose prefill must be dispatched first: a sharer attends over
        # pages its owner's prefill writes, and the write only becomes
        # visible through the self.caches chain once the owner's batch has
        # been dispatched. Owner and sharer in one batched prefill would
        # race (the sharer reads the pre-wave input cache), so dispatch
        # below splits buckets into dependency-respecting sub-batches.
        entries: List[Tuple[int, Request, Any, int, int, int, set]] = []
        # page id -> admission index of the request whose prefill writes it
        wave_page_owner: Dict[int, int] = {}
        ps = self.cache_cfg.page_size
        while self.waiting and self._free_slots:
            req: Request = self.waiting[0]
            T = len(req.prompt_ids)
            # Prefix reuse: share the longest cached run of FULL prompt
            # pages into this slot; prefill then runs only on the suffix.
            # At least one real token must go through prefill (it produces
            # the first sampled token), so a whole-prompt hit backs off by
            # one page.
            digests: List[Any] = []
            shared: List[int] = []
            if self.prefix_cache is not None:
                digests = self.prefix_cache.page_digests(req.prompt_ids, ps)
                shared = self.prefix_cache.match(digests)
                if len(shared) * ps >= T:
                    shared = shared[:(T - 1) // ps]
                # PIN the matched pages before any eviction below can see
                # them as cache-only (ref==1) and hand them to the free
                # list — a page must never be shared and free at once.
                for p in shared:
                    self.allocator.retain(p)
            cached_len = len(shared) * ps
            fresh_tokens = T + 1 - cached_len  # suffix + first decode room
            if not self.allocator.can_allocate(fresh_tokens):
                deficit = (self.allocator.pages_needed(fresh_tokens)
                           - self.allocator.num_free)
                if self.prefix_cache is not None and deficit > 0:
                    self.prefix_cache.evict(deficit)
                if not self.allocator.can_allocate(fresh_tokens):
                    for p in shared:  # unpin: not admitting
                        self.allocator.unref(p)
                    break  # wait for running requests to free pages
            self.waiting.popleft()
            req.t_admitted = time.monotonic()
            slot = self._free_slots.pop()
            req.slot = slot
            self.running[slot] = req
            if shared:
                # transfer the admission pins to the slot
                self.allocator.adopt(slot, shared)
            pages = self.allocator.ensure(slot, T + 1)
            row = np.zeros((self.cfg.max_pages_per_seq,), np.int32)
            row[:len(pages)] = pages
            self.page_table[slot] = row
            # Block generation prefills the prompt's whole blocks alone;
            # the remainder (none: all MASK) is the first block's start.
            whole = T - T % self._block
            suffix = req.prompt_ids[cached_len:whole]
            S = len(suffix)
            bucket = next((b for b in self.cfg.prefill_buckets if b >= S),
                          self.cache_cfg.max_context)
            self.temps[slot] = req.temperature
            self.top_ps[slot] = req.top_p
            self.top_ks[slot] = req.top_k
            # Seed this slot's PRNG chain: explicit seed for reproducible
            # requests, else a fresh engine-global counter.
            if req.seed is not None:
                seed = int(req.seed)
            else:
                self._seed_counter += 1
                seed = (0x5eed << 20) + self._seed_counter
            self._keys_dev = self._keys_dev.at[slot].set(
                jax.random.PRNGKey(seed))
            self.lora_idx[slot] = self.lora_slot(req.lora_id) \
                if self.lora_banks is not None else 0
            idx = len(entries)
            deps = {wave_page_owner[p] for p in shared
                    if p in wave_page_owner}
            if self.prefix_cache is not None and digests:
                # Index this prompt's full pages for future requests;
                # no-op for runs already cached. Pages past the shared
                # prefix are written by THIS request's prefill — record
                # ownership so later same-wave sharers order after us.
                n_full = len(digests)
                slot_pages = self.allocator.slot_pages[slot]
                self.prefix_cache.insert(digests, slot_pages[:n_full])
                for p in slot_pages[len(shared):n_full]:
                    wave_page_owner[p] = idx
            self.seq_lens[slot] = whole
            if self._block == 1:
                req.generated = 1  # prefill samples the first token
            else:
                # (nothing awaits a commit: `_release` left the slot so)
                req.skip = T - whole
                on = self.last_tokens[slot, self._block:]
                on[:req.skip] = req.prompt_ids[whole:]
                on[req.skip:] = self.model.mask_token_id
            entries.append((slot, req, suffix, cached_len, S, bucket, deps))
        return entries

    def _dispatch_prefills(self, entries: List[tuple], carry=(None, None)
                           ) -> Tuple[List[tuple], tuple]:
        """Dispatch the wave's prefills; the first tokens stay on the
        device. Returns (slot, req, tokens on device, logprobs, row, batch
        size, cached prompt tokens) per admission, and `carry`: every slot's
        last token and length on the device, threaded through the
        sub-batches as `self.caches` is, each scattering its rows in."""
        pending: List[tuple] = []
        # Dispatch in dependency-respecting sub-batches: repeatedly take
        # the earliest undispatched admission, batch it with every other
        # undispatched same-bucket entry whose deps are all dispatched.
        # deps always point to earlier admissions, so the earliest
        # remaining entry is always dispatchable (no deadlock).
        done: set = set()
        # (a prompt shorter than one block has nothing to prefill)
        remaining = [j for j, e in enumerate(entries) if e[4] > 0]
        while remaining:
            bucket = entries[remaining[0]][5]
            batch = [j for j in remaining
                     if entries[j][5] == bucket and entries[j][6] <= done]
            wave = [entries[j][:5] for j in batch]
            nb = len(wave)
            rich, want_lp = self._sampling_flags(
                [entries[j][1] for j in batch])
            key = (bucket, nb, rich, want_lp)
            with _fr.span("ray_tpu.engine.prefill_dispatch", bucket=bucket,
                          nb=nb, tokens=sum(w[4] for w in wave),
                          cached_tokens=sum(w[3] for w in wave), rich=rich,
                          want_lp=want_lp,
                          new_program=key not in self._prefill_fns,
                          state_rows=nb * self._state_layers,
                          scan_positions=nb * bucket * self._state_layers,
                          head_rows=(0 if self._block > 1 else nb
                                     if self._head_last else nb * bucket)
                          ) as sp:
                dev_toks, lp, load, carry = self._prefill_wave(key, wave,
                                                               carry)
                if load is not None:
                    # A model that routes: the span waits for its prefill's
                    # counts (the device then idles for the host's next
                    # dispatch, once a wave).
                    sp.set(**self._count_load(load))
            for i, (slot, req, _, cached_len, _) in enumerate(wave):
                pending.append((slot, req, dev_toks, lp, i, nb, cached_len))
            done.update(batch)
            remaining = [j for j in remaining if j not in done]
        return pending, carry

    def _prefill_wave(self, key: Tuple[int, int, bool, bool],
                      wave: List[tuple], carry: tuple):
        """One batched prefill: the host arrays, their transfers and the
        program call."""
        bucket, nb = key[:2]
        ids = np.zeros((nb, bucket), np.int32)
        rows = np.zeros((nb, self.cfg.max_pages_per_seq), np.int32)
        starts = np.zeros((nb,), np.int32)
        lens = np.zeros((nb,), np.int32)
        temps = np.zeros((nb,), np.float32)
        tps = np.ones((nb,), np.float32)
        tks = np.zeros((nb,), np.int32)
        slot_ids = np.zeros((nb,), np.int32)
        lidx = np.zeros((nb,), np.int32)
        for i, (slot, req, suffix, cached_len, S) in enumerate(wave):
            ids[i, :S] = suffix
            rows[i] = self.page_table[slot]
            starts[i] = cached_len
            lens[i] = S
            temps[i] = req.temperature
            tps[i] = req.top_p
            tks[i] = req.top_k
            slot_ids[i] = slot
            lidx[i] = self.lora_idx[slot]
        dev_toks, self.caches, self._keys_dev, lp, load, *carry = \
            self._run_program(
                "prefill", key, self._prefill_fn(*key), (
                    self.params, self.caches, self._dev(ids),
                    self._dev(rows), self._dev(starts), self._dev(lens),
                    self._dev(temps), self._dev(tps), self._dev(tks),
                    self._keys_dev, self._dev(slot_ids), self.lora_banks,
                    self._dev(lidx), *carry))
        return dev_toks, lp, load, tuple(carry)

    def _sync_first_tokens(self, pending: List[tuple],
                           out: List[StepOutput]) -> None:
        """Block on the first tokens (every wave is in flight by now), emit
        them, and stamp each request's queue wait and prefill time."""
        with _fr.span("ray_tpu.engine.prefill_sync", requests=len(pending)):
            for slot, req, dev_toks, lp, i, nb, cached_len in pending:
                tok = int(np.asarray(dev_toks)[i])  # blocks on its wave
                self._mark_first_token(req, slot, cached_len, nb)
                self.last_tokens[slot] = tok
                finished = (req.generated >= req.max_tokens
                            or (req.stop_token is not None
                                and tok == req.stop_token))
                so = StepOutput(req.request_id, tok, finished)
                if lp is not None and req.logprobs > 0:
                    so.logprob = float(np.asarray(lp[0])[i])
                    so.top_logprobs = [
                        (int(np.asarray(lp[2])[i, k]),
                         float(np.asarray(lp[1])[i, k]))
                        for k in range(req.logprobs)]
                out.append(so)
                if finished:
                    self._release(slot)

    def _mark_first_token(self, req: Request, slot: int, cached_len: int,
                          nb: int) -> None:
        """A request's first token has reached the host: stamp it, its queue
        wait and its prefill time (admission to now)."""
        req.t_first_token = now = time.monotonic()
        queue_s = req.t_admitted - req.t_enqueued
        prefill_s = now - req.t_admitted
        self._m_queue_wait.observe(queue_s)
        self._m_prefill.observe(prefill_s)
        _fr.mark("ray_tpu.request.first_token", rid=req.request_id,
                 slot=slot, queue_ms=queue_s * 1e3,
                 prefill_ms=prefill_s * 1e3, prompt=len(req.prompt_ids),
                 cached=cached_len, nb=nb)

    def _ensure_decode_pages(self, k: int = 1) -> None:
        """Each running slot is about to append up to k tokens starting at
        seq_lens[slot]; grow its page list to cover them. Cache-held prefix
        pages are evictable fuel here too — decode growth must not die on
        MemoryError while reclaimable pages exist."""
        for slot in list(self.running):
            need = int(self.seq_lens[slot]) + k
            try:
                pages = self.allocator.ensure(slot, need)
            except MemoryError:
                if self.prefix_cache is None:
                    raise
                deficit = (self.allocator.pages_needed(need)
                           - len(self.allocator.slot_pages[slot])
                           - self.allocator.num_free)
                self.prefix_cache.evict(max(1, deficit))
                pages = self.allocator.ensure(slot, need)
            row = self.page_table[slot]
            row[:len(pages)] = pages

    def _release(self, slot: int) -> None:
        req = self.running.pop(slot, None)
        if req is not None:
            _fr.mark("ray_tpu.request.finished", rid=req.request_id,
                     slot=slot, tokens=req.generated, decode_ms=(
                         time.monotonic() - req.t_first_token) * 1e3)
        self.allocator.release(slot)
        self._free_slots.append(slot)
        self._freed = True
        self.seq_lens[slot] = 0
        if self._block > 1:  # its last block is never committed
            self.last_tokens[slot, :self._block] = -1
        self.lora_idx[slot] = 0
        self.top_ps[slot] = 1.0
        self.top_ks[slot] = 0
