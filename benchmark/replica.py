"""The replica class the benchmark deploys: `OpenAIServer`, unchanged in
what it serves, plus what only the chip's holder can do for a measurement —
a deterministic warm-up of the cell's shapes, the float32 reference check,
compile counts, a profiler slice, memory. The replica class is the user's
code in this system; nothing under `ray_tpu/` is edited."""

from __future__ import annotations

import queue
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from benchmark import holder, tokenizer_gen
from ray_tpu.llm._internal.openai import OpenAIServer


class BenchServer(OpenAIServer):
    def __init__(self, llm_config: Dict[str, Any]):
        holder.cache_everything()
        self._compiles = holder.CompileCounter()
        self._t_init = time.monotonic()
        super().__init__(llm_config)
        self._init_s = time.monotonic() - self._t_init
        self._model_kwargs = dict(llm_config["model_config"])
        self._vocab = int(self._model_kwargs["vocab_size"])
        self._tokens = 0
        self._silent = 0
        self._stopped = 0
        self._tracer: Optional[holder.SliceTracer] = None
        self._mark: Dict[str, Any] = {}
        self._count_generated()

    # -- counters --------------------------------------------------------
    def _count_generated(self) -> None:
        """Count, where the tokens leave the engine, those a client cannot
        see as an event of their own (tokenizer_gen.py) and the requests
        that ended on the stop id."""
        inner = self.server.generate
        vocab, stop = self._vocab, self.tokenizer.eot_id

        def generate(*args: Any, **kwargs: Any):
            gen = inner(*args, **kwargs)
            try:
                for item in gen:
                    tok = item["token"]
                    self._tokens += 1
                    if tokenizer_gen.is_silent(tok, vocab):
                        self._silent += 1
                        if tok == stop:
                            self._stopped += 1
                    yield item
            finally:
                gen.close()

        self.server.generate = generate

    def _counters(self) -> Dict[str, Any]:
        return {"tokens_out": self.server._tokens_out,
                "generated": self._tokens, "silent": self._silent,
                "stopped_on_eot": self._stopped,
                "compiles": self._compiles.snapshot(),
                "cache_entries": holder.cache_entries()}

    # -- warm-up ---------------------------------------------------------
    def _wait_idle(self, deadline_s: float = 600.0) -> None:
        t0 = time.monotonic()
        eng = self.server.engine
        while (eng.has_work() or not self.server._pending.empty()
               or eng._inflight is not None):
            if time.monotonic() - t0 > deadline_s:
                raise RuntimeError("engine did not go idle")
            time.sleep(0.002)

    def _release_together(self, prompts: List[List[int]], max_tokens: int
                          ) -> List[List[int]]:
        """Hand the idle engine all of `prompts` in one piece, so that one
        admission batches exactly these: the engine thread drains its inbox
        under the queue's own mutex, and the requests go in under it. Waits
        for every request's tokens."""
        from ray_tpu.llm._internal.engine import Request

        self._wait_idle()
        srv = self.server
        rids = [uuid.uuid4().hex[:12] for _ in prompts]
        outs: Dict[str, "queue.Queue"] = {r: queue.Queue() for r in rids}
        with srv._lock:
            srv._queues.update(outs)
        reqs = [Request(r, list(p), max_tokens=max_tokens)
                for r, p in zip(rids, prompts)]
        inbox = srv._pending
        with inbox.mutex:
            inbox.queue.extend(reqs)
            inbox.unfinished_tasks += len(reqs)
            inbox.not_empty.notify()
        tokens: List[List[int]] = []
        try:
            for r in rids:
                got: List[int] = []
                while True:
                    kind, item = outs[r].get(timeout=1200)
                    if kind == "error":
                        raise RuntimeError(f"engine failed in warm-up: {item}")
                    got.append(int(item.token))
                    if item.finished:
                        break
                tokens.append(got)
        finally:
            with srv._lock:
                for r in rids:
                    srv._queues.pop(r, None)
        return tokens

    def _warm_prompt(self, length: int, salt: int) -> List[int]:
        import random

        from benchmark.loadgen import text_ids

        return text_ids(random.Random(0x5EED0000 + salt), length,
                        self._vocab)

    def bench_warm(self, prompt_lens: Dict[str, int], max_nb: int,
                   decode_tokens: int) -> Dict[str, Any]:
        """Compile (or load from the cache) and run once every program the
        cell's traffic can reach: the decode program, and a prefill for
        every bucket in `prompt_lens` ({bucket: a prompt length that falls
        into it}) at every admission size 1..max_nb. Coverage is checked
        against the engine's own table and missing shapes are repeated."""
        eng = self.server.engine
        t0 = time.monotonic()
        before = self._compiles.snapshot()
        salt = 0
        # Decode, through enough windows to take the pipelined dispatch too.
        first_len = next(iter(prompt_lens.values()))
        self._release_together([self._warm_prompt(first_len, salt)],
                               decode_tokens)
        decode_s = time.monotonic() - t0
        missing: List[Tuple[int, int]] = []
        for attempt in range(3):
            missing = [(int(b), nb) for b in prompt_lens
                       for nb in range(1, max_nb + 1)
                       if (int(b), nb, False, False) not in eng._prefill_fns]
            if not missing:
                break
            for bucket, nb in missing:
                prompts = []
                for _ in range(nb):
                    salt += 1
                    prompts.append(self._warm_prompt(
                        prompt_lens[str(bucket)], salt))
                self._release_together(prompts, 1)
        self._wait_idle()
        after = self._compiles.snapshot()
        return {"seconds": time.monotonic() - t0, "decode_s": decode_s,
                "programs": sorted(k[:2] for k in eng._prefill_fns
                                   if not k[2] and not k[3]),
                "missing": missing,
                "decode_variants": sorted(eng._decode_fns),
                "compiled": after["programs"] - before["programs"],
                "cache_hits": after["cache_hits"] - before["cache_hits"],
                "cache_misses": after["cache_misses"]
                - before["cache_misses"]}

    # -- correctness -----------------------------------------------------
    def bench_check(self, prompt: List[int], steps: int) -> Dict[str, Any]:
        """Greedy `steps` tokens through the engine (paged prefill, then
        the decode program) with their logprobs, against the float32
        reference's full forward over prompt + tokens on the same weights.
        Logprobs and not tokens: with seeded weights the largest logit
        changes on rounding."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmark import reference

        t0 = time.monotonic()
        before = self._compiles.snapshot()
        top = self.server.engine.cfg.max_logprobs
        got = self.server.generate_all(prompt, max_tokens=steps,
                                       logprobs=top)
        t_engine = time.monotonic()
        tokens = got["tokens"]
        ids = jnp.asarray(list(prompt) + tokens[:-1], jnp.int32)
        kw = self._model_kwargs
        ref = np.asarray(jax.jit(
            lambda p, x: reference.logprobs(p, x, kw)[len(prompt) - 1:])(
                self.server.params, ids))
        t_ref = time.monotonic()
        gap = 0.0
        for i, alts in enumerate(got["top_logprobs"]):
            for tok, lp in alts:
                gap = max(gap, abs(float(ref[i, tok]) - lp))
        self._wait_idle()
        after = self._compiles.snapshot()
        return {"seconds": time.monotonic() - t0, "tokens": tokens,
                # the engine's share includes waiting for the weights: the
                # jitted init is still running when the constructor returns
                "split_s": {"engine": t_engine - t0,
                            "reference": t_ref - t_engine},
                "compiles": {k: after[k] - before[k] for k in after},
                "max_logprob_gap": gap, "positions": len(tokens),
                "argmax_agrees": [int(ref[i].argmax()) == t
                                  for i, t in enumerate(tokens)]}

    # -- the window ------------------------------------------------------
    def bench_begin(self, trace: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
        self._mark = self._counters()
        if trace:
            eng = self.server.engine
            if not getattr(eng, "_bench_spans", False):
                holder.wrap_with_span(eng, "step", "engine.step")
                holder.wrap_with_span(eng, "_admit", "engine.admit")
                holder.wrap_with_span(eng, "_process_window",
                                      "engine.wait_tokens")
                holder.wrap_with_span(eng, "_dispatch_window",
                                      "engine.dispatch_decode")
                eng._bench_spans = True
            self._tracer = holder.SliceTracer(
                trace["dir"], trace["delay_s"], trace["length_s"])
            self._tracer.start()
        return {"init_s": self._init_s, **self._mark}

    def bench_end(self) -> Dict[str, Any]:
        now = self._counters()
        out: Dict[str, Any] = {
            k: now[k] - self._mark[k]
            for k in ("tokens_out", "generated", "silent", "stopped_on_eot")}
        out["compiles_in_window"] = (now["compiles"]["programs"]
                                     - self._mark["compiles"]["programs"])
        out["compiled_in_window"] = self._compiles.names[
            self._mark["compiles"]["programs"]:]
        out["cache_entries"] = now["cache_entries"]
        out["device"] = holder.device_report()
        out["decode_steps"] = self.server.engine.cfg.decode_steps
        if self._tracer is not None:
            out["trace"] = self._tracer.finish()
            self._tracer = None
        return out
