"""LLMServer: the serve deployment hosting one engine replica.

Reference: llm/_internal/serve/deployments/llm/llm_server.py + vllm_engine.py
(there the engine is vLLM's; here it's ray_tpu.llm._internal.engine). The
engine runs on a dedicated thread; request handlers enqueue work and stream
tokens back through per-request queues, one item per engine step: a decode
window's tokens for a request cross to its handler together."""

from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional

from ray_tpu._private import flight_recorder as _fr
from ray_tpu.llm._internal.engine import (
    EngineConfig,
    LLMEngine,
    Request,
    StepOutput,
)
from ray_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# How long a request may wait for its next token. The first request of each
# shape compiles its prefill and decode programs, which at real widths takes
# longer than serve's default request timeout, so the LLM deployments carry
# this one.
GENERATE_TIMEOUT_S = 600.0


def load_model_and_params(llm_config: Dict[str, Any], mesh=None):
    """Resolve an llm_config dict to (model, params). Shared by the serve
    path (LLMServer) and the batch path (_internal/batch.py).
    `llm_config["family"]` picks the model family from `ray_tpu.models`
    (default "llama"; a name it does not have raises). The tree is the
    one serving computes with (`models.serving_params`): what the model
    would convert to its compute dtype at every use is rounded to it here,
    once. With a mesh, seeded parameters are initialized straight into
    their tensor-parallel shardings, so no device ever holds the whole
    tree."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import models

    name = llm_config.get("family", "llama")
    if name not in models.FAMILIES and llm_config.get("bench_root"):
        # The benchmark's harness names families that are files of its own
        # (`<bench_root>/benchmark/families/<name>.py`); one the program
        # lacks is the Llama block there. From anyone else it is a typo.
        logger.warning("llm_config family %r is none of %s: building llama",
                       name, sorted(models.FAMILIES))
        name = "llama"
    fam = models.family(name)
    config_cls = fam.load("config")
    model_cfg = llm_config.get("model_config") or {}
    preset = llm_config.get("model", "tiny")
    if preset == "tiny":
        cfg = config_cls.tiny(**model_cfg)
    elif preset == "llama3-8b":
        cfg = config_cls.llama3_8b()
    else:
        cfg = config_cls(**model_cfg)
    if mesh is not None and fam.sharding is None:
        raise NotImplementedError(
            f"model family {name!r} has no parameter sharding rules: it "
            "runs on one device, without a mesh")
    model = fam.load("model")(cfg, **({} if mesh is None else {"mesh": mesh}))
    sharding = None if mesh is None else fam.load("sharding")
    params_path = llm_config.get("params_path")
    if params_path:
        import pickle

        with open(params_path, "rb") as f:
            params = pickle.load(f)
        # On the host: what crosses to the device is the rounded tree.
        params = models.serving_params(model, params)
        if mesh is None:
            # Onto the device once, not with every step. With a mesh the
            # engine places each shard from the host instead.
            params = jax.tree.map(jnp.asarray, params)
    else:
        seed = int(llm_config.get("seed", 0))
        if sharding is None and hasattr(model, "init_params"):
            # The model's own seeded initializer (one small program per
            # kind of layer: see models/olmo_hybrid.py).
            return model, models.serving_params(
                model, model.init_params(jax.random.PRNGKey(seed)))
        sample = jnp.zeros((1, 8), jnp.int32)

        def init(rng):
            # Rounded inside the program that draws them: the float32 tree
            # is never whole in HBM, and no second program runs.
            return models.serving_params(
                model, model.init(rng, sample)["params"])

        shardings = None
        if sharding is not None:
            shardings = sharding.tree_shardings(
                mesh, jax.eval_shape(init, jax.random.PRNGKey(seed)))
        # One compiled program: eager init would hold each initializer's
        # temporaries next to the tree it is building.
        params = jax.jit(init, out_shardings=shardings)(
            jax.random.PRNGKey(seed))
    return model, params


@dataclasses.dataclass
class Burst:
    """What one engine step returned for one request: the one item it is on
    the request's queue, however many tokens the step made."""
    outputs: List[StepOutput]
    # time.perf_counter() when the engine loop queued it.
    delivered_s: float

    # benchmark/replica.py's warm-up waits on these queues itself and reads
    # `.token` and `.finished` off every item, as off a StepOutput.
    @property
    def finished(self) -> bool:
        return self.outputs[-1].finished

    @property
    def token(self) -> int:
        return self.outputs[-1].token


class LLMServer:
    def __init__(self, llm_config: Dict[str, Any]):
        mesh = llm_config.get("mesh")
        tp = int(llm_config.get("tensor_parallel_size") or 1)
        if mesh is None and tp > 1:
            # TP over the first tp local devices (reference forwards
            # tensor_parallel_size into vLLM, vllm_models.py:125-139; here
            # the engine itself shards over the mesh).
            import jax

            from ray_tpu.parallel.mesh import create_mesh

            mesh = create_mesh({"tensor": tp},
                               devices=jax.devices()[:tp])
        self.model, self.params = load_model_and_params(llm_config, mesh)
        eng_cfg = EngineConfig(**(llm_config.get("engine_config") or {}))
        self.engine = LLMEngine(self.model, self.params, eng_cfg, mesh=mesh)
        self._queues: Dict[str, "queue.Queue"] = {}
        self._lock = threading.Lock()
        self._pending: "queue.Queue" = queue.Queue()
        self._aborts: "queue.Queue" = queue.Queue()
        self._tokens_out = 0
        self._running = True
        threading.Thread(target=self._engine_loop, daemon=True,
                         name="llm-engine").start()

    # ------------------------------------------------------------------
    def _engine_loop(self) -> None:
        while self._running:
            moved = False
            while True:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                self.engine.add_request(req)
                moved = True
            while True:
                try:
                    rid = self._aborts.get_nowait()
                except queue.Empty:
                    break
                self.engine.finish_request(rid)
            if not self.engine.has_work():
                # One span per idle stretch, not per sleep: an idle replica
                # must not fill the recorder's ring.
                with _fr.span("ray_tpu.server.idle"):
                    time.sleep(0.005 if moved else 0.01)
                    while (self._running and self._pending.empty()
                           and self._aborts.empty()):
                        time.sleep(0.01)
                continue
            try:
                outputs = self.engine.step()
            except Exception as e:
                logger.exception("engine step failed")
                with self._lock:
                    for q in self._queues.values():
                        q.put(("error", str(e)))
                    self._queues.clear()
                continue
            self._tokens_out += len(outputs)
            with _fr.span("ray_tpu.server.deliver", outputs=len(outputs)):
                by_request: Dict[str, List[StepOutput]] = {}
                for so in outputs:
                    by_request.setdefault(so.request_id, []).append(so)
                now = time.perf_counter()
                with self._lock:
                    for rid, outs in by_request.items():
                        q = self._queues.get(rid)
                        if q is not None:
                            q.put(("burst", Burst(outs, now)))

    # ------------------------------------------------------------------
    def generate(self, prompt_ids: List[int], max_tokens: int = 64,
                 temperature: float = 0.0,
                 stop_token: Optional[int] = None,
                 lora_id: str = "", top_p: float = 1.0, top_k: int = 0,
                 seed: Optional[int] = None,
                 logprobs: int = 0) -> Iterator[Dict[str, Any]]:
        """Streaming generation — one dict per token. lora_id selects a
        loaded adapter (reference: the model-id multiplex surface of
        ray.llm's LoRA deployments). Closing the generator early (stop
        string matched, client gone) aborts the request in the engine so
        its slot stops burning decode steps.

        An engine step's tokens arrive together, and `more` on each dict
        says how many of them are still behind it: a consumer that writes
        somewhere sends what it has when `more` is 0. The first dict
        carries the engine's id of the request (`rid`), the last one
        `delivered_s`, the time.perf_counter() at which the engine loop
        handed over the step that finished the request.

        The engine's id is the request's own where this thread serves one
        (the serve replica names it: the id the proxy made, the `rid` of
        every mark on the request's path), else one made here; a second
        request under an id still running gets a suffix."""
        rid = _fr.request_id() or uuid.uuid4().hex[:12]
        q: "queue.Queue" = queue.Queue()
        with self._lock:
            if rid in self._queues:
                rid = f"{rid}-{uuid.uuid4().hex[:6]}"
            self._queues[rid] = q
        self._pending.put(Request(rid, list(prompt_ids),
                                  max_tokens=max_tokens,
                                  temperature=temperature,
                                  stop_token=stop_token,
                                  lora_id=lora_id, top_p=top_p,
                                  top_k=top_k, seed=seed,
                                  logprobs=logprobs))
        first = True
        finished = False
        try:
            while True:
                kind, burst = q.get(timeout=GENERATE_TIMEOUT_S)
                if kind == "error":
                    raise RuntimeError(f"engine failed: {burst}")
                more = len(burst.outputs)
                for so in burst.outputs:
                    more -= 1
                    out = {"token": int(so.token), "more": more}
                    if so.logprob is not None:
                        out["logprob"] = so.logprob
                        out["top_logprobs"] = so.top_logprobs
                    if first:
                        out["rid"] = rid
                        first = False
                    finished = so.finished
                    if finished:
                        out["delivered_s"] = burst.delivered_s
                    yield out
                    if finished:
                        return
        finally:
            if not finished:
                self._aborts.put(rid)
            with self._lock:
                self._queues.pop(rid, None)

    def generate_all(self, prompt_ids: List[int], max_tokens: int = 64,
                     temperature: float = 0.0,
                     stop_token: Optional[int] = None,
                     lora_id: str = "", top_p: float = 1.0,
                     top_k: int = 0, seed: Optional[int] = None,
                     logprobs: int = 0) -> Dict[str, Any]:
        """Unary variant: returns all tokens at once."""
        toks = []
        lps: List[Any] = []
        tops: List[Any] = []
        for item in self.generate(prompt_ids, max_tokens, temperature,
                                  stop_token, lora_id, top_p, top_k,
                                  seed, logprobs):
            toks.append(item["token"])
            if "logprob" in item:
                lps.append(item["logprob"])
                tops.append(item["top_logprobs"])
        out: Dict[str, Any] = {"tokens": toks}
        if lps:
            out["logprobs"] = lps
            out["top_logprobs"] = tops
        return out

    def load_lora(self, name: str, adapter: Dict[str, Any],
                  scale: float = 1.0) -> int:
        """Install a LoRA adapter into the engine's banks (reference:
        LoRA multiplex deployments' model loading)."""
        return self.engine.load_lora(name, adapter, scale)

    def stats(self) -> Dict[str, Any]:
        import os

        import jax

        devices = (list(self.engine.mesh.devices.flat)
                   if self.engine.mesh is not None else jax.devices()[:1])

        def bytes_per_device(tree) -> List[int]:
            # From the shardings, not the buffers: the engine thread may
            # be donating the cache to a step right now.
            held = {d.id: 0 for d in devices}
            for leaf in jax.tree.leaves(tree):
                shard = leaf.sharding.shard_shape(leaf.shape)
                for d in leaf.sharding.device_set:
                    held[d.id] += math.prod(shard) * leaf.dtype.itemsize
            return list(held.values())

        return {
            "running": self.engine.num_running(),
            "waiting": len(self.engine.waiting),
            "free_pages": self.engine.allocator.num_free,
            "tokens_out": self._tokens_out,
            # Compile record: programs built and retraced, last records.
            "programs": self.engine.programs_report(),
            # Decode windows dispatched: `unchained` from the host mirrors
            # (the chip waited for that dispatch), and chained off the window
            # before by what the chain outlived: none, finish, admission.
            "decode_windows": self.engine.windows_report(),
            # What the experts' layers routed, summed since the engine began
            # (all 0 for a model without experts).
            "expert_load": self.engine.expert_load_report(),
            # Which device answers: the devices this engine computes on, as
            # JAX reports them in this process, and the chips it was leased.
            "pid": os.getpid(),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
            "param_bytes_per_device": bytes_per_device(self.params),
            # Of the whole tree, by the dtype it is held in.
            "param_bytes_by_dtype": dict(self.engine.params_report),
            "kv_bytes_per_device": bytes_per_device(self.engine.caches),
            # What the cache holds by kind of layer: K/V pages per token,
            # or a fixed state per slot.
            "cache": dict(self.engine.cache_report),
        }

    def self_check(self, prompt_ids: List[int], steps: int = 2
                   ) -> Dict[str, Any]:
        """Compare the engine with the plain model where the weights live.

        Generates `steps` greedy tokens for `prompt_ids` through the engine
        (paged prefill, then the decode program) asking for logprobs, and
        recomputes the same positions with one dense `model.apply` without
        a cache on the same parameters (with attention_impl="reference"
        where the model's config has such a choice). Returns the
        largest logprob gap over the engine's reported top tokens, whether
        each engine token is the reference's argmax, and whether the
        lowered decode program holds the Mosaic paged-attention kernel."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        top = self.engine.cfg.max_logprobs
        got = self.generate_all(prompt_ids, max_tokens=steps, logprobs=top)
        tokens = got["tokens"]
        plain = {"attention_impl": "reference", "remat": False}
        fields = {f.name for f in dataclasses.fields(self.model.cfg)}
        ref_model = type(self.model)(dataclasses.replace(
            self.model.cfg,
            **{k: v for k, v in plain.items() if k in fields}))
        ids = jnp.asarray([list(prompt_ids) + tokens[:-1]], jnp.int32)
        logits = jax.jit(ref_model.apply)({"params": self.params}, ids)
        ref = np.asarray(jax.nn.log_softmax(
            logits[0, len(prompt_ids) - 1:].astype(jnp.float32), axis=-1))
        gap = 0.0
        for i, alts in enumerate(got["top_logprobs"]):
            for tok, lp in alts:
                gap = max(gap, abs(float(ref[i, tok]) - lp))
        return {
            "tokens": tokens,
            "top_logprobs": got["top_logprobs"],
            "max_logprob_gap": gap,
            "argmax_agrees": [int(ref[i].argmax()) == t
                              for i, t in enumerate(tokens)],
            "decode_has_mosaic_kernel":
                "tpu_custom_call" in self.engine.lowered_decode_text(),
        }

    def check_health(self) -> bool:
        return True
