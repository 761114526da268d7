"""Training cells (`kind` train): `DataParallelTrainer` with one `use_tpu`
worker running `train/step.py`'s step. The train loop is the user's code in
this system, so the benchmark brings its own: it checks the first step's
loss against the float32 reference, warms the step, then runs steps for the
window, each fed a fresh batch of seeded ids made on the host and ended by
fetching its loss."""

from __future__ import annotations

import math
import time
from typing import Any, Dict


def train_loop(config: Dict[str, Any]) -> None:
    """Runs in the train worker, which holds the chip."""
    from benchmark import holder

    holder.cache_everything()
    compiles = holder.CompileCounter()

    import jax
    import numpy as np
    import optax

    from benchmark import reference
    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig, LlamaModel, count_params
    from ray_tpu.train.step import init_train_state, make_train_step

    kw, tr = config["model_kwargs"], config["traffic"]
    batch, seq = int(tr["batch"]), int(tr["seq"])
    model = LlamaModel(LlamaConfig(**kw))
    opt = optax.adafactor(float(tr["learning_rate"]))
    rng = np.random.default_rng(config["seed"])

    def host_batch():
        return rng.integers(0, kw["vocab_size"], (batch, seq),
                            dtype=np.int32)

    t_init = time.monotonic()
    ids = host_batch()
    state = init_train_state(
        model, opt, ids[:1, :8],
        rng=jax.random.PRNGKey(config["seed"]))
    step = make_train_step(model, opt)
    n_params = count_params(state.params)
    jax.block_until_ready(state)
    init_s = time.monotonic() - t_init

    # The reference reads the parameters before the step donates them.
    t_check = time.monotonic()
    ref_loss = float(jax.jit(
        lambda p, x: reference.next_token_loss(p, x, kw))(state.params, ids))
    check_s = time.monotonic() - t_check
    t_warm = time.monotonic()
    state, loss = step(state, ids, ids)
    first_loss = float(loss)
    for _ in range(2):
        state, loss = step(state, host_batch(), host_batch())
        float(loss)
    warm_s = time.monotonic() - t_warm

    tracer = None
    if config.get("trace"):
        t = config["trace"]
        tracer = holder.SliceTracer(t["dir"], t["delay_s"], t["length_s"])
    before = compiles.snapshot()
    losses, ends = [], []
    t0 = time.monotonic()
    if tracer:
        tracer.start()
    # The window ends with the step that crosses `seconds`, and the rate is
    # taken over all of it: no step is cut, none is left out.
    while time.monotonic() - t0 < config["seconds"]:
        with holder.span("train.host_batch"):
            ids = host_batch()
        with holder.span("train.step"):
            state, loss = step(state, ids, ids)
            losses.append(float(loss))     # waits for the device
        ends.append(time.monotonic() - t0)
    after = compiles.snapshot()
    out = {
        "steps": len(losses), "elapsed_s": ends[-1], "step_ends_s": ends,
        "tokens_per_step": batch * seq, "losses": losses,
        "first_loss": first_loss, "reference_loss": ref_loss,
        "params": n_params, "init_s": init_s, "check_s": check_s,
        "warm_s": warm_s, "window_start": t0,
        "compiles_in_window": after["programs"] - before["programs"],
        "compiled_in_window": compiles.names[before["programs"]:],
        "compiles_before": before, "cache_entries": holder.cache_entries(),
        "attention_impl": model.cfg.attention_impl,
        "device": holder.device_report(),
    }
    if tracer:
        out["trace_reduced"] = tracer.finish()
    train.report(out)


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    from benchmark import client, holder

    say, seconds, chips = ctx["say"], ctx["seconds"], ctx["cell"]["chips"]
    config = {"model_kwargs": ctx["model_kwargs"], "traffic": ctx["traffic"],
              "seed": ctx["seed"], "seconds": seconds,
              "trace": (holder.slice_options(ctx["cache_dir"], seconds)
                        if ctx["trace"] else None)}
    t0 = time.monotonic()
    result = DataParallelTrainer(
        train_loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=float(chips)),
    ).fit()
    if result.error or not result.metrics_history:
        raise RuntimeError(f"train worker reported nothing: "
                           f"{result.error}")
    m = result.metrics_history[-1]
    gone_s = client.wait_gone([m["device"]["pid"]])
    ctx["check_device"](m["device"]["platform"], m["device"]["count"], chips)
    tol = float(ctx["config"]["check"]["loss_rtol"])
    rel = abs(m["first_loss"] / m["reference_loss"] - 1.0)
    finite = all(math.isfinite(x) for x in m["losses"])
    correct = bool(rel <= tol and finite and m["steps"] > 0)
    tokens = m["steps"] * m["tokens_per_step"]
    e2e = {"setup_s": m["window_start"] - ctx["t_process"],
           "train_tok_per_s": tokens / m["elapsed_s"]}
    say(f"set-up: worker start to state ready "
        f"{m['window_start'] - t0 - m['check_s'] - m['warm_s']:.1f}s (of "
        f"which init program {m['init_s']:.1f}s) | reference loss "
        f"{m['check_s']:.1f}s | 3 warm steps {m['warm_s']:.1f}s | "
        f"{m['params'] / 1e9:.3f}B parameters, attention "
        f"{m['attention_impl']}")
    say(f"window: {m['steps']} steps of {m['tokens_per_step']} tokens in "
        f"{m['elapsed_s']:.3f}s | first-step loss {m['first_loss']:.5f} vs "
        f"float32 reference {m['reference_loss']:.5f}: relative gap "
        f"{rel:.5f} (tolerance {tol}) | losses finite {finite}, last "
        f"{m['losses'][-1]:.4f} | compiles in window "
        f"{m['compiles_in_window']} {m['compiled_in_window'][:5]} | cache entries {m['cache_entries']} | "
        f"chip released {gone_s:.1f}s after the trainer returned")
    device = {k: m["device"][k] for k in
              ("platform", "kind", "count", "memory_peak_bytes")}
    traces = [m["trace_reduced"]] if (m.get("trace_reduced") or {}).get(
        "window_s") else []
    obs = {"seconds": seconds, "train": m, "traces": traces, "e2e": e2e,
           "compiles_in_window": m["compiles_in_window"],
           "replicas": [{"device": m["device"]}]}
    return {"correct": correct, "attempted": m["steps"],
            "failed": 0 if finite else 1, "e2e": e2e, "device": device,
            "obs": obs}
