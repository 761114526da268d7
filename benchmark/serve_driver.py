"""Serving cells (`kind` serve_open and serve_closed): deploy the replica
class through `serve.run`, warm exactly the cell's shapes, check against the
float32 reference, offer the traffic over HTTP SSE for the window, compare
what the clients counted with what the replicas counted, shut down."""

from __future__ import annotations

import asyncio
import math
import os
import threading
import time
from typing import Any, Dict, List

from benchmark import client, holder, loadgen, metrics, tokenizer_gen

# Working limits of a chat product, for the attainment line (PERF.md §2).
TTFT_LIMIT_MS, TPOT_LIMIT_MS = 1000.0, 50.0
CHECK_PROMPT_LEN, CHECK_STEPS = 100, 4


def llm_config(ctx: Dict[str, Any]) -> Dict[str, Any]:
    traffic, kw = ctx["traffic"], ctx["model_kwargs"]
    path = tokenizer_gen.write(
        os.path.join(ctx["cache_dir"], f"tokenizer-{kw['vocab_size']}.json"),
        kw["vocab_size"])
    return {"model": ctx["cell"]["config"], "model_config": kw,
            "engine_config": traffic["engine_config"],
            # Weights come from the seed, on the device, in one jitted init.
            "seed": ctx["seed"],
            "tokenizer_path": path,
            "max_ongoing_requests": traffic.get("max_ongoing_requests", 32)}


def deploy(ctx: Dict[str, Any], cfg: Dict[str, Any]):
    """`build_openai_app`'s deployment, with the benchmark's subclass in
    the replica's place."""
    from ray_tpu import serve
    from ray_tpu.llm import GENERATE_TIMEOUT_S

    from benchmark.replica import BenchServer

    chips = ctx["cell"]["chips"]
    dep = serve.deployment(
        BenchServer, name=f"OpenAI:{cfg['model']}", num_replicas=chips,
        ray_actor_options={"num_cpus": 1.0, "num_tpus": 1.0},
        max_ongoing_requests=int(cfg["max_ongoing_requests"]),
        request_timeout_s=GENERATE_TIMEOUT_S)
    serve.run(dep.bind(cfg), route_prefix="/v1")
    return serve.http_port()


def _each(actors: List[Any], method: str, *args: Any) -> List[Any]:
    """The same call on every replica, at the same time."""
    import ray_tpu

    refs = [a.handle_request_unary.remote(method, args, {}) for a in actors]
    return ray_tpu.get(refs, timeout=1500)


def warm_spec(traffic: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.llm._internal.engine import EngineConfig

    ec = EngineConfig(**traffic["engine_config"])
    buckets = loadgen.buckets_used(traffic, list(ec.prefill_buckets))
    longest = int(round(max(loadgen.quantiles(traffic["prompt_len"], 101))))
    context = ec.page_size * ec.max_pages_per_seq
    return {"prompt_lens": {str(b): min(b, longest) for b in buckets},
            "max_nb": ec.max_seqs,
            "decode_tokens": min(1 + 3 * ec.decode_steps,
                                 context - min(buckets[0], longest)
                                 - ec.decode_steps)}


class StatsSampler:
    """`stats()["running"]` of every replica at 10 Hz (traced runs only)."""

    def __init__(self, actors: List[Any], clock):
        self.actors, self.clock = actors, clock
        self.samples: List[Dict[str, Any]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(0.1):
            try:
                stats = _each(self.actors, "stats")
            except Exception:  # a sample lost is not a run lost
                continue
            self.samples.append({
                "t": self.clock(),
                "running": sum(s["running"] for s in stats),
                "waiting": sum(s["waiting"] for s in stats)})

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(10)


async def _offer(ctx, port, reqs, clock, probe_out) -> List[metrics.Rec]:
    traffic, seconds = ctx["traffic"], ctx["seconds"]
    temp = float(traffic.get("sampling", {}).get("temperature", 0.0))
    drain = float(traffic.get("drain_s", 60.0))
    stop = asyncio.Event()
    probe = None
    if ctx["trace"]:
        probe = asyncio.ensure_future(
            client.probe_rtt(port, clock, stop, probe_out))
    try:
        if "clients" in traffic:
            return await client.closed_loop(
                port, reqs, int(traffic["clients"]), seconds, clock, temp,
                drain)
        return await client.open_loop(port, reqs, seconds, clock, temp,
                                      drain)
    finally:
        stop.set()
        if probe is not None:
            await probe


def bring_up(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Deploy, wait for the replicas, check them against the reference and
    warm the cell's shapes. Everything before the first measured request."""
    traffic, say, chips = ctx["traffic"], ctx["say"], ctx["cell"]["chips"]
    cfg = llm_config(ctx)
    t0 = time.monotonic()
    port = deploy(ctx, cfg)
    client.wait_ready(port)
    deadline = time.monotonic() + 300
    while len(actors := list(client.replica_actors().values())) < chips:
        if time.monotonic() > deadline:
            raise RuntimeError(f"only {len(actors)} of {chips} replicas")
        time.sleep(0.5)
    stats = _each(actors, "stats")
    for s in stats:
        ctx["check_device"](s["platform"], s["device_count"], 1)
    ready_s = time.monotonic() - t0

    vocab = cfg["model_config"]["vocab_size"]
    check_prompt = loadgen.requests(
        {"clients": 1, "rounds": 1,
         "prompt_len": {"dist": "fixed", "value": CHECK_PROMPT_LEN},
         "output_len": {"dist": "fixed", "value": CHECK_STEPS}},
        vocab, ctx["seed"], 0)[0].prompt
    checks = _each(actors, "bench_check", check_prompt, CHECK_STEPS)
    tol = float(ctx["config"]["check"]["logprob_tol"])
    gap = max(c["max_logprob_gap"] for c in checks)
    correct = gap <= tol
    spec = warm_spec(traffic)
    warms = _each(actors, "bench_warm", spec["prompt_lens"],
                  spec["max_nb"], spec["decode_tokens"])
    if any(w["missing"] for w in warms):
        raise RuntimeError(f"warm-up left shapes out: "
                           f"{[w['missing'] for w in warms]}")
    say(f"set-up: replicas ready {ready_s:.1f}s (cluster, worker, TPU "
        f"runtime, weights) | reference check "
        f"{max(c['seconds'] for c in checks):.1f}s "
        f"({ {k: round(v, 1) for k, v in checks[0]['split_s'].items()} }, "
        f"{checks[0]['compiles']}): max logprob gap "
        f"{gap:.4f} (tolerance {tol}) over {checks[0]['positions']} "
        f"positions, argmax agrees {checks[0]['argmax_agrees']} | "
        f"warm-up {max(w['seconds'] for w in warms):.1f}s: "
        f"{len(warms[0]['programs'])} prefill programs "
        f"{sorted(set(b for b, _ in warms[0]['programs']))} x nb 1.."
        f"{spec['max_nb']} + decode (first {warms[0]['decode_s']:.1f}s), "
        f"{warms[0]['compiled']} programs through the compiler, cache hits "
        f"{warms[0]['cache_hits']} misses {warms[0]['cache_misses']}")
    return {"port": port, "actors": actors, "vocab": vocab,
            "pids": [s["pid"] for s in stats], "ready_s": ready_s,
            "correct": correct}


def measure(ctx: Dict[str, Any], up: Dict[str, Any], traffic: Dict[str, Any],
            seconds: float) -> Dict[str, Any]:
    """One window of `traffic` against the replicas that are up."""
    actors, port = up["actors"], up["port"]
    reqs = loadgen.requests(traffic, up["vocab"], ctx["seed"], seconds)
    trace_opts = (holder.slice_options(ctx["cache_dir"], seconds)
                  if ctx["trace"] else None)
    begins = _each(actors, "bench_begin", trace_opts)
    t_window = time.monotonic()
    clock = lambda: time.monotonic() - t_window
    sampler = StatsSampler(actors, clock) if ctx["trace"] else None
    if sampler:
        sampler.start()
    probe_ms: List[float] = []
    recs = asyncio.run(_offer(dict(ctx, traffic=traffic, seconds=seconds),
                              port, reqs, clock, probe_ms))
    if sampler:
        sampler.stop()
    ends = _each(actors, "bench_end")
    for e in ends:
        ctx["check_device"](e["device"]["platform"], e["device"]["count"], 1)
    return {"recs": recs, "begins": begins, "ends": ends,
            "t_window": t_window, "probe_ms": probe_ms,
            "samples": sampler.samples if sampler else []}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu import serve

    traffic, seconds, say = ctx["traffic"], ctx["seconds"], ctx["say"]
    up = bring_up(ctx)
    m = measure(ctx, up, traffic, seconds)
    serve.shutdown()
    gone_s = client.wait_gone(up["pids"])
    recs, ends, begins = m["recs"], m["ends"], m["begins"]
    correct, ready_s, t_window = up["correct"], up["ready_s"], m["t_window"]
    probe_ms = m["probe_ms"]

    # -- after the window: what the clients saw against what was made ----
    summary = metrics.summarize(recs, seconds)
    made = sum(e["generated"] for e in ends)
    silent = sum(e["silent"] for e in ends)
    counted = sum(e["tokens_out"] for e in ends)
    seen = summary["token_events"]
    # Every token the engine made left the replica (made == counted), and
    # reached a client as an event of its own unless it is one of the ids
    # the tokenizer class keeps silent; two lone bytes can join into one
    # character, hence the upper margin.
    tokens_agree = (made == counted
                    and made - silent <= seen <= made - silent + silent // 2)
    finished = all(r.ok for r in recs)   # final chunk and [DONE] seen
    compiles = sum(e["compiles_in_window"] for e in ends)
    correct = bool(correct and tokens_agree and summary["failed"] == 0
                   and finished)
    ttft = metrics.ttft_ms(recs, seconds)
    tpot = metrics.tpot_ms(recs, until_s=seconds, min_tokens=8)
    e2e = {"setup_s": t_window - ctx["t_process"],
           "out_tok_per_s": metrics.out_tok_per_s(recs, seconds)}
    if tpot:
        e2e["tpot_p95_ms"] = metrics.percentile(tpot, 95)
    if "clients" not in traffic:
        e2e["ttft_p95_ms"] = metrics.percentile(ttft, 95)
    say(f"window {seconds}s: {summary} | replicas made {made} tokens "
        f"(tokens_out {counted}, silent ids {silent}, ended on the stop id "
        f"{sum(e['stopped_on_eot'] for e in ends)}), clients saw {seen} "
        f"token events: {'agree' if tokens_agree else 'DISAGREE'} | samples: "
        f"ttft {len(ttft)}, tpot {len(tpot)} | ttft p50 "
        f"{metrics.percentile(ttft, 50):.1f} ms, tpot p50 "
        f"{(metrics.percentile(tpot, 50) if tpot else math.nan):.2f} ms | "
        f"attainment of TTFT <= {TTFT_LIMIT_MS:.0f} ms and TPOT <= "
        f"{TPOT_LIMIT_MS:.0f} ms: "
        f"{metrics.attainment(recs, seconds, TTFT_LIMIT_MS, TPOT_LIMIT_MS):.3f}"
        f" | compiles in window {compiles} "
        f"{[n for e in ends for n in e['compiled_in_window']][:5]} | chip "
        f"released {gone_s:.1f}s after shutdown | errors "
        f"{sorted({r.error for r in recs if r.error})[:3]}")
    if begins:
        say(f"set-up split: replica constructor {begins[0]['init_s']:.1f}s "
            f"of ready {ready_s:.1f}s; total to window "
            f"{e2e['setup_s']:.1f}s; cache entries {ends[0]['cache_entries']}")
    traces = [e["trace"] for e in ends
              if e.get("trace", {}).get("window_s")]
    device = {"platform": ends[0]["device"]["platform"],
              "kind": ends[0]["device"]["kind"],
              "count": sum(e["device"]["count"] for e in ends),
              "memory_peak_bytes": max(e["device"]["memory_peak_bytes"]
                                       for e in ends)}
    obs = {"recs": recs, "seconds": seconds, "probe_ms": probe_ms,
           "stats_samples": m["samples"],
           "replicas": ends, "traces": traces, "e2e": e2e,
           "compiles_in_window": compiles}
    return {"correct": correct, "attempted": summary["attempted"],
            "failed": summary["failed"], "e2e": e2e, "device": device,
            "obs": obs}
