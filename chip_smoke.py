"""Proof that the serve and train main paths start on the chip.

    python chip_smoke.py             # one TPU chip; about 3 min, cold cache
    python chip_smoke.py --chips 4   # four-chip host; only what spans chips

Default mode, in this order and nothing else:

  serve  ray_tpu.init() -> serve.run(build_openai_app(cfg, num_tpus=1)) ->
         HTTP /v1/completions through proxy, router, replica and engine, at
         Llama-3-8B widths with depth cut to fit one 16 GB chip. Checks: every
         request returns the tokens it asked for; the compiled decode program
         holds the Mosaic paged-attention kernel; the engine's logprobs for the
         first two generated positions (paged prefill, then the kernel) agree
         with a dense model.apply under attention_impl="reference" in the same
         replica process.
  train  after serve.shutdown() released the chip: DataParallelTrainer with one
         use_tpu worker running train/step.py (bf16, remat, flash, adafactor)
         on a repeated batch. Checks: loss finite and falling.

This process never initializes a JAX backend: the chip is held by the replica
worker, then by the train worker, one at a time. The last line of stdout is
the device those workers reported. Anything else — a failed check, a phase
that raises, a worker on another platform, no TPU — exits non-zero without it.
There is no CPU mode; tests/test_chip_smoke.py rehearses the script on the CPU
by overriding the constants below from outside.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import statistics
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List

PLATFORM = "tpu"

# Llama-3-8B (LlamaConfig defaults: hidden 4096, FFN 14336, 32 query / 8 KV
# heads of 128, vocabulary 128256, untied head). Depth is the only cut; the
# figures behind it are compiled.memory_analysis() of each program for a
# described v5e chip (15.75 GiB usable), see CHANGES.md PR 21.
SERVE = {
    "model": "llama3-8b-depth4",
    "model_config": {"num_layers": 4, "max_seq_len": 2048},
    "engine_config": {"max_seqs": 8, "page_size": 64,
                      "max_pages_per_seq": 16},
    "reduced": "depth 4 of 32, sized when serving held float32 weights "
               "(1.92B params, 7.16 GiB) beside the decode program's bf16 "
               "copy (10.93 GiB peak of 15.75); it holds them in bf16 now",
    "prompt_len": 300,   # prefill bucket 512
    "max_tokens": 32,
    "concurrency": 4,    # per wave: half unary, half SSE
}
TRAIN = {
    "model_config": {"num_layers": 2, "max_seq_len": 2048},
    "reduced": "depth 2 of 32, batch 2 x 2048: float32 params and grads of "
               "1.49B params with adafactor (AdamW moments of the 1.05B-"
               "parameter vocabulary alone exceed the chip), 9.60 GiB peak "
               "of 15.75",
    "batch": 2,
    "seq": 2048,
    "steps": 4,
    "learning_rate": 1e-2,
}
# bf16 activations: logits of magnitude 4-8 are spaced 1/32-1/16 apart, and
# the two attention paths round differently; four spacings.
LOGPROB_TOL = 0.25
# Sharded vs one-device loss (--chips 4), relative: bf16 matmuls reduce in
# another order when sharded, and the difference compounds through the
# optimizer updates between the compared steps.
LOSS_RTOL = 0.02


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------
def llm_config(seed: int, **extra: Any) -> Dict[str, Any]:
    return {"model": SERVE["model"], "model_config": SERVE["model_config"],
            "engine_config": SERVE["engine_config"], "seed": seed, **extra}


def prompts(seed: int, n: int) -> List[List[int]]:
    """Seeded token ids (pre-tokenized prompts reach the engine as given)."""
    import random

    rng = random.Random(seed)
    return [[rng.randrange(1, 250) for _ in range(SERVE["prompt_len"])]
            for _ in range(n)]


def http(port: int, path: str, body: Any = None, timeout: float = 600.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.headers.get("content-type", ""), \
            resp.read().decode()


def wait_ready(port: int, deadline_s: float = 600.0) -> float:
    """Poll GET /v1/models until the replica (weights loaded) answers."""
    t0 = time.monotonic()
    last = None
    while time.monotonic() - t0 < deadline_s:
        try:
            status, _, _ = http(port, "/v1/models", timeout=120.0)
            if status == 200:
                return time.monotonic() - t0
            last = status
        except (urllib.error.URLError, OSError) as e:
            last = e
        time.sleep(1.0)
    raise SmokeFailure(f"replica not ready after {deadline_s:.0f}s: {last!r}")


def completion(port: int, prompt: List[int], stream: bool) -> None:
    """One /v1/completions request. A unary answer must count the tokens
    asked for; an SSE stream must be well-formed and complete (its tokens
    are counted through the replica's counter: ids outside the byte
    tokenizer's vocabulary decode to no text)."""
    want = SERVE["max_tokens"]
    status, ctype, text = http(port, "/v1/completions", {
        "prompt": prompt, "max_tokens": want, "temperature": 0.0,
        "stream": stream})
    check(status == 200, f"completions returned HTTP {status}: {text[:300]}")
    if not stream:
        out = json.loads(text)
        got = out["usage"]["completion_tokens"]
        check(got == want and out["usage"]["prompt_tokens"] == len(prompt),
              f"unary request returned usage {out['usage']}, wanted {want}")
        return
    check("text/event-stream" in ctype, f"SSE content-type {ctype!r}")
    events = [ln[len("data: "):] for ln in text.splitlines()
              if ln.startswith("data: ")]
    check(len(events) >= 2 and events[-1] == "[DONE]",
          f"SSE stream did not end with [DONE]: {events[-2:]}")
    chunks = [json.loads(e) for e in events[:-1]]
    check(chunks[-1]["choices"][0]["finish_reason"] == "stop",
          f"SSE stream has no final chunk: {chunks[-1]}")


def wave(port: int, batch: List[List[int]]) -> float:
    """Send `batch` concurrently, alternating unary and SSE. Seconds."""
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(batch)) as pool:
        futs = [pool.submit(completion, port, p, i % 2 == 1)
                for i, p in enumerate(batch)]
        for f in futs:
            f.result()
    return time.monotonic() - t0


def replica_actors() -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.serve._controller import REPLICA_NAME_PREFIX
    from ray_tpu.util import state

    return {a["name"]: ray_tpu.get_actor(a["name"])
            for a in state.list_actors(state="ALIVE")
            if (a.get("name") or "").startswith(REPLICA_NAME_PREFIX)}


def replica_call(actor: Any, method: str, *args: Any) -> Any:
    import ray_tpu

    return ray_tpu.get(
        actor.handle_request_unary.remote(method, args, {}), timeout=900)


def check_device(who: str, rep: Dict[str, Any], count: int) -> Dict[str, Any]:
    check(rep["platform"] == PLATFORM,
          f"{who} runs on {rep['platform']!r}, not {PLATFORM!r}")
    check(rep["device_count"] == count,
          f"{who} computes on {rep['device_count']} devices, wanted {count}")
    return {"platform": rep["platform"], "kind": rep["device_kind"],
            "count": rep["device_count"]}


def check_self(who: str, rep: Dict[str, Any]) -> str:
    check(rep["decode_has_mosaic_kernel"] or PLATFORM != "tpu",
          f"{who}: no Mosaic kernel (tpu_custom_call) in the decode program")
    check(rep["max_logprob_gap"] <= LOGPROB_TOL,
          f"{who}: engine logprobs differ from the reference path by "
          f"{rep['max_logprob_gap']:.4f} > {LOGPROB_TOL}")
    return (f"mosaic kernel in decode: "
            f"{'yes' if rep['decode_has_mosaic_kernel'] else 'no'}; "
            f"logprobs vs reference model.apply at {len(rep['tokens'])} "
            f"positions: max gap {rep['max_logprob_gap']:.4f} <= "
            f"{LOGPROB_TOL}, argmax agrees {rep['argmax_agrees']}")


def wait_gone(pids: List[int], deadline_s: float = 120.0) -> float:
    """The chip is free only when its holder's process is gone."""
    t0 = time.monotonic()
    while any(os.path.exists(f"/proc/{p}") for p in pids):
        check(time.monotonic() - t0 < deadline_s,
              f"replica process(es) {pids} still alive {deadline_s:.0f}s "
              "after serve.shutdown(): the chip is not released")
        time.sleep(0.2)
    return time.monotonic() - t0


def widths(model_config: Dict[str, Any]) -> str:
    from ray_tpu.models.llama import LlamaConfig

    c = LlamaConfig(**model_config)
    return (f"hidden {c.hidden_size}, ffn {c.intermediate_size}, heads "
            f"{c.num_heads}/{c.num_kv_heads}x{c.head_dim}, vocab "
            f"{c.vocab_size}, depth {c.num_layers}")


def serve_phase(seed: int) -> Dict[str, Any]:
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    t0 = time.monotonic()
    app = build_openai_app(llm_config(seed), num_tpus=1)
    serve.run(app, route_prefix="/v1")
    port = serve.http_port()
    ready_s = wait_ready(port)
    (actor,) = replica_actors().values()
    stats = replica_call(actor, "stats")
    device = check_device("serve replica", stats, 1)

    ps = prompts(seed, 1 + 2 * SERVE["concurrency"])
    t1 = time.monotonic()
    self_rep = replica_call(actor, "self_check", ps[0], 2)
    self_s = time.monotonic() - t1
    self_line = check_self("serve replica", self_rep)

    n = SERVE["concurrency"]
    before = replica_call(actor, "stats")["tokens_out"]
    first_s = wave(port, ps[1:1 + n])
    second_s = wave(port, ps[1 + n:1 + 2 * n])
    got = replica_call(actor, "stats")["tokens_out"] - before
    check(got == 2 * n * SERVE["max_tokens"],
          f"replica generated {got} tokens for {2 * n} requests of "
          f"{SERVE['max_tokens']}")

    serve.shutdown()
    gone_s = wait_gone([stats["pid"]])
    print(f"serve: device {device['platform']}/{device['kind']} x"
          f"{device['count']} (chips {stats['visible_chips'] or '-'}, pid "
          f"{stats['pid']}) | {widths(SERVE['model_config'])} | reduced: "
          f"{SERVE['reduced']} | "
          f"params {stats['param_bytes_per_device'][0] / 2**30:.2f} GiB + KV "
          f"{stats['kv_bytes_per_device'][0] / 2**30:.2f} GiB on device | "
          f"replica ready {ready_s:.1f}s (init compile + weights) | "
          f"self-check {self_s:.1f}s (compiles a prefill, a decode and the "
          f"reference program): {self_line} | "
          f"{2 * n} HTTP requests (unary + SSE, 2 waves of {n} concurrent, "
          f"{SERVE['prompt_len']}-token prompts, {SERVE['max_tokens']} tokens "
          f"each) all complete, {got} tokens counted in the replica | wave "
          f"1 {first_s:.1f}s (compiles prefill + decode), wave 2 "
          f"{second_s:.1f}s (same shapes; compiles again only if admission "
          f"batched the prompts differently) | chip released {gone_s:.1f}s "
          f"after shutdown | "
          f"phase {time.monotonic() - t0:.0f}s", flush=True)
    return device


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------
def train_loop(config: Dict[str, Any]) -> None:
    """Runs in the train worker, which holds the chip(s)."""
    import time

    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import (
        LLAMA_SHARDING,
        LlamaConfig,
        LlamaModel,
        count_params,
    )
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.train.step import init_train_state, make_train_step

    devices = jax.devices()
    cfg = LlamaConfig(**config["model_config"])
    ids = jax.random.randint(jax.random.PRNGKey(config["seed"]),
                             (config["batch"], config["seq"]), 0,
                             cfg.vocab_size)

    def run(mesh):
        model = LlamaModel(cfg, mesh=mesh)
        opt = optax.adafactor(config["learning_rate"])
        rules = LLAMA_SHARDING if mesh is not None else None
        state = init_train_state(
            model, opt, ids[:1, :8], rng=jax.random.PRNGKey(config["seed"]),
            mesh=mesh, param_rules=rules)
        step = make_train_step(model, opt, mesh=mesh, param_rules=rules)
        losses, times = [], []
        for _ in range(config["steps"]):
            t0 = time.monotonic()
            state, loss = step(state, ids, ids)
            losses.append(float(loss))  # waits for the device
            times.append(time.monotonic() - t0)
        return losses, times, count_params(state.params)

    reference = None
    if config.get("mesh"):
        # What the sharded step is compared with: the same step, same seed,
        # on one device of this process. Freed before the sharded run.
        reference, _, _ = run(None)
    mesh = (create_mesh(config["mesh"], devices=devices)
            if config.get("mesh") else None)
    losses, times, n_params = run(mesh)
    for i, (loss, dt) in enumerate(zip(losses, times)):
        train.report({
            "step": i + 1, "loss": loss, "step_s": dt, "params": n_params,
            "reference_loss": reference[i] if reference else None,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "mesh_devices": mesh.size if mesh is not None else 1,
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
            "attention_impl": cfg.attention_impl,
        })


def train_phase(seed: int, chips: int = 1) -> Dict[str, Any]:
    import math

    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    t0 = time.monotonic()
    config = {"model_config": TRAIN["model_config"], "seed": seed,
              "batch": TRAIN["batch"],
              "seq": TRAIN["seq"], "steps": 3 if chips > 1 else TRAIN["steps"],
              "learning_rate": TRAIN["learning_rate"],
              "mesh": {"fsdp": 2, "tensor": 2} if chips > 1 else None}
    result = DataParallelTrainer(
        train_loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=float(chips)),
    ).fit()
    hist = result.metrics_history
    check(len(hist) == config["steps"],
          f"train worker reported {len(hist)} of {config['steps']} steps")
    last = hist[-1]
    device = check_device("train worker", last, chips)
    check(last["mesh_devices"] == chips and last["attention_impl"] == "flash",
          f"train step ran on {last['mesh_devices']} devices with "
          f"{last['attention_impl']} attention")
    losses = [m["loss"] for m in hist]
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    compared = ""
    if chips > 1:
        gaps = [abs(m["loss"] / m["reference_loss"] - 1.0) for m in hist]
        check(max(gaps) <= LOSS_RTOL,
              f"sharded loss differs from the one-device step by {gaps} "
              f"(relative) > {LOSS_RTOL}")
        compared = (f" | one-device losses "
                    f"{[round(m['reference_loss'], 4) for m in hist]}, max "
                    f"relative gap {max(gaps):.5f} <= {LOSS_RTOL}")
    steady = statistics.median(m["step_s"] for m in hist[1:])
    print(f"train: device {device['platform']}/{device['kind']} x"
          f"{device['count']} (chips {last['visible_chips'] or '-'}) | "
          f"DataParallelTrainer, use_tpu worker, mesh {config['mesh']} | "
          f"{widths(TRAIN['model_config'])}, "
          f"{last['params'] / 1e9:.2f}B params, bf16 + remat + flash + "
          f"adafactor, batch "
          f"{config['batch']} x {config['seq']} | reduced: "
          f"{TRAIN['reduced']} | step 1 {hist[0]['step_s']:.1f}s (cold "
          f"compile + run), later steps median {steady:.2f}s | "
          f"{len(hist)} steps on a repeated batch, loss "
          f"{[round(x, 4) for x in losses]} finite and falling{compared} | "
          f"phase {time.monotonic() - t0:.0f}s", flush=True)
    return device


# ---------------------------------------------------------------------------
# Four chips: only what exists across chips, and what each is compared with
# ---------------------------------------------------------------------------
def near_tie(rep: Dict[str, Any], other: List[int]) -> bool:
    """Greedy tokens of two numerically different programs may part at a
    near tie, after which the sequences differ legitimately. True if `rep`
    agrees with `other` up to such a point (or throughout)."""
    for i, (mine, theirs) in enumerate(zip(rep["tokens"], other)):
        if mine != theirs:
            lps = dict(rep["top_logprobs"][i])
            return (theirs in lps
                    and abs(lps[mine] - lps[theirs]) <= LOGPROB_TOL)
    return True


def four_chip_phases(seed: int) -> Dict[str, Any]:
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    steps = 16
    ps = prompts(seed, 9)

    # 1. Four one-chip replicas behind the router.
    t0 = time.monotonic()
    serve.run(build_openai_app(llm_config(seed), num_replicas=4, num_tpus=1),
              route_prefix="/v1")
    port = serve.http_port()
    wait_ready(port)
    deadline = time.monotonic() + 600
    while len(actors := replica_actors()) < 4:
        check(time.monotonic() < deadline, f"only {len(actors)} replicas up")
        time.sleep(1.0)
    stats = {name: replica_call(a, "stats") for name, a in actors.items()}
    for name, st in stats.items():
        check_device(name, st, 1)
    chips = sorted(st["visible_chips"] for st in stats.values())
    pids = [st["pid"] for st in stats.values()]
    check(len(set(chips)) == 4 and len(set(pids)) == 4,
          f"replicas do not hold four distinct chips: chips {chips}, "
          f"pids {pids}")
    refs = [a.handle_request_unary.remote("self_check", (ps[0], steps), {})
            for a in actors.values()]
    import ray_tpu

    reps = ray_tpu.get(refs, timeout=900)
    self_lines = [check_self(name, rep) for name, rep in zip(actors, reps)]
    tokens = reps[0]["tokens"]
    check(all(r["tokens"] == tokens for r in reps),
          f"replicas disagree on greedy tokens: {[r['tokens'] for r in reps]}")
    waves = 0
    served: List[int] = []
    while waves < 6 and not (served and all(served)):
        wave(port, ps[1:9])
        waves += 1
        served = [replica_call(a, "stats")["tokens_out"]
                  - steps for a in actors.values()]
    check(all(served), f"after {waves} waves of 8 requests the router "
                       f"reached only some replicas: tokens {served}")
    check(sum(served) == waves * 8 * SERVE["max_tokens"],
          f"replicas generated {sum(served)} tokens for {waves * 8} requests")
    serve.shutdown()
    wait_gone(pids)
    print(f"replicas: 4 x num_tpus=1 behind the router, chips {chips}, pids "
          f"{pids}, each {PLATFORM} x1 | {widths(SERVE['model_config'])} | "
          f"self-check on each: "
          f"{self_lines[0]} | same {steps} greedy tokens "
          f"from all four | {waves * 8} HTTP requests in {waves} wave(s), "
          f"tokens per replica {served} | phase "
          f"{time.monotonic() - t0:.0f}s", flush=True)

    # 2. One tensor-parallel replica over the four chips.
    t0 = time.monotonic()
    serve.run(build_openai_app(llm_config(seed, tensor_parallel_size=4),
                               num_tpus=4), route_prefix="/v1")
    port = serve.http_port()
    wait_ready(port)
    (actor,) = replica_actors().values()
    st = replica_call(actor, "stats")
    device = check_device("tensor-parallel replica", st, 4)
    whole = sum(stats[next(iter(stats))]["param_bytes_per_device"])
    share = [b / whole for b in st["param_bytes_per_device"]]
    kv_whole = sum(stats[next(iter(stats))]["kv_bytes_per_device"])
    kv_share = [b / kv_whole for b in st["kv_bytes_per_device"]]
    check(max(share) < 0.3 and max(kv_share) < 0.3,
          f"tensor-parallel replica piles on a chip: parameter shares "
          f"{share}, KV shares {kv_share}")
    rep = replica_call(actor, "self_check", ps[0], steps)
    self_line = check_self("tensor-parallel replica", rep)
    check(near_tie(rep, tokens),
          f"tensor-parallel tokens {rep['tokens']} differ from the one-chip "
          f"replicas' {tokens} beyond a near tie")
    same = sum(1 for a, b in zip(rep["tokens"], tokens) if a == b)
    wave(port, ps[1:5])
    serve.shutdown()
    wait_gone([st["pid"]])
    print(f"tensor-parallel: 1 x num_tpus=4, tensor_parallel_size=4 (chips "
          f"{st['visible_chips']}) | per-device parameter share "
          f"{[round(s, 3) for s in share]} of "
          f"{whole / 2**30:.2f} GiB, KV share "
          f"{[round(s, 3) for s in kv_share]} | {self_line} | greedy tokens "
          f"vs the one-chip replicas: {same}/{steps} equal, any difference "
          f"at a near tie (<= {LOGPROB_TOL}) | 4 HTTP requests complete | "
          f"phase {time.monotonic() - t0:.0f}s", flush=True)

    # 3. One four-chip train worker, sharded step vs one device.
    train_device = train_phase(seed, chips=4)
    check(train_device == device,
          f"workers report different devices: {device} vs {train_device}")
    return device


# ---------------------------------------------------------------------------
def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def dump_worker_logs(session_dir: str) -> None:
    """A failed run leaves its reasons in the workers' logs."""
    import glob

    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "**",
                                              "*.log"), recursive=True),
                       key=os.path.getmtime):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-40:]
        if tail:
            print(f"--- {path}\n{''.join(tail)}", file=sys.stderr)


def main(argv: List[str] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and PLATFORM not in platforms.split(","):
        print(f"chip_smoke: no TPU: JAX_PLATFORMS={platforms} keeps JAX off "
              "it, and this script has no CPU mode", file=sys.stderr)
        return 2

    import ray_tpu
    from ray_tpu._private.accelerators import (
        compile_cache_dir,
        detect_resources,
    )

    # What ray_tpu.init() will advertise, asked before anything is started.
    found = int(detect_resources().get("TPU", 0))
    if found < args.chips:
        print(f"chip_smoke: no TPU: this host exposes {found} chip(s), "
              f"{args.chips} needed", file=sys.stderr)
        return 2

    t0 = time.monotonic()
    ray_tpu.init()
    try:
        cache = compile_cache_dir()
        before = cache_entries(cache)
        try:
            if args.chips == 1:
                device = serve_phase(args.seed)
                train_device = train_phase(args.seed)
                check(train_device == device,
                      f"workers report different devices: {device} vs "
                      f"{train_device}")
            else:
                device = four_chip_phases(args.seed)
        except BaseException:
            from ray_tpu._private import worker as worker_mod

            dump_worker_logs(worker_mod.global_worker().session_dir)
            raise
        from jax._src import xla_bridge

        check(not xla_bridge.backends_are_initialized(),
              "this process initialized a JAX backend; it must stay off the "
              "chip")
        print(f"compile cache: {cache} ({before} entries before, "
              f"{cache_entries(cache)} after) | total "
              f"{time.monotonic() - t0:.0f}s", flush=True)
    finally:
        ray_tpu.shutdown()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
