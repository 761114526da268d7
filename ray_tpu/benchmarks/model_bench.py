"""Model-level benchmarks: MFU/tokens-per-second on the real chip.

The reference records only control-plane microbenchmarks
(release/perf_metrics/microbenchmark.json); model-level throughput is
delegated to torch/vLLM. Here the framework IS the engine, so tokens/s and
MFU are first-class metrics (BASELINE.json north-star configs 1/2).

Timing note: every bench chains each step's output into the next step's
input and fetches a scalar at the end — the device cannot elide or
overlap-away any step, and the host clock stops only when the last is done.
"""

from __future__ import annotations

import time
from typing import Any, Dict

# Per-chip peak bf16 FLOP/s (dense MXU), keyed by jax's device_kind. Used
# for MFU. Source: Google Cloud TPU documentation, per-generation pages.
TPU_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,  # trillium
}


def _peak_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in TPU_PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it to "
            "TPU_PEAK_FLOPS with its source")
    return TPU_PEAK_FLOPS[kind]


def flash_attention_bench(
    *, batch: int = 4, seq: int = 4096, heads: int = 16, kv_heads: int = 4,
    head_dim: int = 128, iters: int = 30,
) -> Dict[str, Any]:
    """Pallas flash kernel vs the jnp reference on the real chip."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_reference, flash_attention

    key = jax.random.PRNGKey(0)
    q0 = jax.random.normal(key, (batch, seq, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(key, (batch, seq, kv_heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(key, (batch, seq, kv_heads, head_dim), jnp.bfloat16)
    flops = 4 * batch * heads * seq * seq * head_dim * 0.5  # causal

    def bench(f):
        q = f(q0, k, v)
        float(q.sum())  # warm (compile + execute)
        q = q0
        t0 = time.perf_counter()
        for _ in range(iters):
            q = f(q, k, v)
        float(q.sum())
        return (time.perf_counter() - t0) / iters

    t_flash = bench(jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)))
    t_ref = bench(jax.jit(
        lambda q, k, v: attention_reference(q, k, v, causal=True)))

    # Numerics on the same inputs.
    import jax.numpy as jnp
    o1 = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q0, k, v)
    o2 = jax.jit(lambda q, k, v: attention_reference(q, k, v, causal=True))(q0, k, v)
    err = float(jnp.abs(o1.astype(jnp.float32) - o2.astype(jnp.float32)).max())

    return {
        "flash_ms": t_flash * 1e3,
        "ref_ms": t_ref * 1e3,
        "flash_tflops": flops / t_flash / 1e12,
        "speedup_vs_reference": t_ref / t_flash,
        "max_abs_err": err,
    }


def llama_train_bench(
    *, batch: int = 8, seq: int = 1024, iters: int = 10,
) -> Dict[str, Any]:
    """Jitted fwd+bwd+adamw step of a ~0.5B Llama on one chip: tokens/s, MFU.

    Sized to fit a single v5e (16 GiB HBM) with f32 params + adam moments.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import LlamaConfig, LlamaModel, count_params
    from ray_tpu.train.step import TrainState, init_train_state, make_train_step

    cfg = LlamaConfig(
        vocab_size=16_384, hidden_size=2048, intermediate_size=5632,
        num_layers=8, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=seq, dtype=jnp.bfloat16, attention_impl="flash",
        remat=True)
    model = LlamaModel(cfg)
    opt = optax.adamw(3e-4)
    ids = jnp.zeros((batch, seq), jnp.int32)
    state = init_train_state(model, opt, ids)
    n_params = count_params(state.params)
    step = make_train_step(model, opt)

    state, loss = step(state, ids, ids)
    float(loss)  # warm: compile + one step
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, ids, ids)
    float(loss)
    float(state.step)
    dt = (time.perf_counter() - t0) / iters

    tokens = batch * seq
    # 6ND matmul + causal attention (fwd 4BHS²D·½ per layer, train ≈ 3× fwd).
    attn_flops = 6 * cfg.num_layers * batch * cfg.num_heads * seq * seq * cfg.head_dim * 0.5
    step_flops = 6 * n_params * tokens + attn_flops
    mfu = step_flops / dt / _peak_flops()
    return {
        "params": n_params,
        "step_ms": dt * 1e3,
        "tokens_per_s": tokens / dt,
        "mfu": mfu,
    }


def llm_serving_bench(*, batch: int = 8, prompt_len: int = 128,
                      max_tokens: int = 64) -> Dict[str, Any]:
    """BASELINE config 4 shape: continuous-batching decode throughput +
    TTFT on the real chip (paged KV + Pallas decode kernel)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(
        vocab_size=16_384, hidden_size=1024, intermediate_size=2816,
        num_layers=8, num_heads=8, num_kv_heads=4, head_dim=128,
        max_seq_len=2048, dtype=jnp.bfloat16, attention_impl="flash",
        remat=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, EngineConfig(
        max_seqs=batch, page_size=64, max_pages_per_seq=32))
    rng = np.random.default_rng(0)

    def run_wave():
        t0 = time.perf_counter()
        ttft = None
        for i in range(batch):
            eng.add_request(Request(
                f"r{i}", list(rng.integers(1, 16_000, prompt_len)),
                max_tokens=max_tokens))
        n_tokens = 0
        while eng.has_work():
            outs = eng.step()
            if outs and ttft is None:
                ttft = time.perf_counter() - t0
            n_tokens += len(outs)
        return n_tokens, time.perf_counter() - t0, ttft

    run_wave()  # warm: compiles prefill bucket + decode step
    n_tokens, dt, ttft = run_wave()
    return {
        "params": sum(x.size for x in jax.tree.leaves(params)),
        "tokens_per_s": n_tokens / dt,
        "ttft_s": ttft,
        "batch": batch,
    }


def llama_train_large_bench(
    *, batch: int = 4, seq: int = 2048, iters: int = 5,
) -> Dict[str, Any]:
    """BASELINE config 2 at real scale: the largest Llama that TRAINS on
    one v5e (16 GiB HBM).

    What fits and why (measured on chip): 2.37B params in bf16 with
    gradient rematerialization + adafactor (factored second moments —
    adam's fp32 m/v alone would be 8 bytes/param ≈ 19 GiB). Params 4.7 GiB
    + grads 4.7 GiB + factored optimizer state (~MBs) + remat'd
    activations ≈ 12 GiB. 3.2B initializes but its train step spills and
    thrashes (8.8% MFU at batch 2); 8B bf16 params alone are 16 GiB — the
    single-chip path toward 8B is int8 (serving, below) or multi-chip
    FSDP (parallel/, exercised by dryrun_multichip)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import LlamaConfig, LlamaModel, count_params
    from ray_tpu.train.step import init_train_state, make_train_step

    cfg = LlamaConfig(
        vocab_size=32_768, hidden_size=2560, intermediate_size=6912,
        num_layers=32, num_heads=20, num_kv_heads=4, head_dim=128,
        max_seq_len=seq, dtype=jnp.bfloat16, attention_impl="flash",
        remat=True)
    model = LlamaModel(cfg)
    opt = optax.adafactor(3e-4)
    ids = jnp.zeros((batch, seq), jnp.int32)
    state = init_train_state(model, opt, ids)
    n_params = count_params(state.params)
    step = make_train_step(model, opt)
    state, loss = step(state, ids, ids)
    float(loss)  # warm: compile + one step
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, ids, ids)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    tokens = batch * seq
    attn_flops = (6 * cfg.num_layers * batch * cfg.num_heads * seq * seq
                  * cfg.head_dim * 0.5)
    mfu = (6 * n_params * tokens + attn_flops) / dt / _peak_flops()
    return {"params": n_params, "step_ms": dt * 1e3,
            "tokens_per_s": tokens / dt, "mfu": mfu}


def _serving_wave(eng, *, batch: int, prompt_len: int, max_tokens: int,
                  vocab_hi: int = 30_000, seed: int = 0):
    """One continuous-batching wave: admit `batch` prompts, run to
    completion. Returns (tokens, wall_s, ttft_s). Shared by every serving
    bench so TTFT/token accounting can only be fixed in one place."""
    import numpy as np

    from ray_tpu.llm._internal.engine import Request

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    ttft = None
    n = 0
    for i in range(batch):
        eng.add_request(Request(
            f"r{i}", list(rng.integers(1, vocab_hi, prompt_len)),
            max_tokens=max_tokens))
    while eng.has_work():
        outs = eng.step()
        if outs and ttft is None:
            ttft = time.perf_counter() - t0
        n += len(outs)
    return n, time.perf_counter() - t0, ttft


def llm_serving_large_bench(*, batch: int = 8, prompt_len: int = 128,
                            max_tokens: int = 48) -> Dict[str, Any]:
    """BASELINE config 4 toward scale: a 1B+ bf16 model through the full
    engine (paged KV + Pallas decode + continuous batching)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.models.llama import LlamaConfig, LlamaModel, count_params

    cfg = LlamaConfig(
        vocab_size=32_768, hidden_size=2048, intermediate_size=5632,
        num_layers=24, num_heads=16, num_kv_heads=8, head_dim=128,
        max_seq_len=1024, dtype=jnp.bfloat16, attention_impl="flash",
        remat=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, EngineConfig(
        max_seqs=batch, page_size=64, max_pages_per_seq=16,
        decode_steps=8))
    _serving_wave(eng, batch=batch, prompt_len=prompt_len,
                  max_tokens=8)  # warm
    n, dt, ttft = _serving_wave(eng, batch=batch, prompt_len=prompt_len,
                                max_tokens=max_tokens)
    return {"params": count_params(params), "tokens_per_s": n / dt,
            "ttft_s": ttft, "batch": batch}


def llm_serving_8b_int8_bench(*, batch: int = 8, prompt_len: int = 128,
                              max_tokens: int = 48) -> Dict[str, Any]:
    """BASELINE config 4 at its NAMED scale: Llama-3-8B shape (8.03B
    params incl. the 128k vocab) served from ONE v5e via int8 weights
    (models/quant.py — bf16 8B weights alone exceed the 16 GiB HBM).
    Dequant runs inside the jitted step; HBM holds the 7.5 GiB int8 tree
    + paged KV (512-token contexts at this batch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.models.quant import (
        dequantize_tree,
        quantized_bytes,
        random_quantized_like,
    )

    import dataclasses
    import math

    cfg = dataclasses.replace(LlamaConfig.llama3_8b(),
                              max_seq_len=1024, remat=False)
    model = LlamaModel(cfg)
    shape = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    n_params = sum(math.prod(l.shape) for l in jax.tree.leaves(shape))
    qp = random_quantized_like(shape)
    eng = LLMEngine(model, qp, EngineConfig(
        max_seqs=batch, page_size=64, max_pages_per_seq=8,
        decode_steps=8), param_transform=dequantize_tree)
    _serving_wave(eng, batch=batch, prompt_len=prompt_len,
                  max_tokens=8)  # warm
    n, dt, ttft = _serving_wave(eng, batch=batch, prompt_len=prompt_len,
                                max_tokens=max_tokens)
    return {"params": n_params, "weight_bytes": quantized_bytes(qp),
            "tokens_per_s": n / dt, "ttft_s": ttft, "batch": batch}


def mnist_trainer_bench(ray_tpu_mod, *, epochs: int = 3) -> Dict[str, Any]:
    """BASELINE config 1: single-worker MNIST-shaped MLP DataParallelTrainer.

    Synthetic MNIST-shaped data (no network in this environment); measures
    end-to-end samples/s through the Train path (worker group, session
    reporting, jitted step)."""
    import numpy as np

    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    n, d, classes, bs = 8192, 784, 10, 256

    def train_loop(config):
        # The MLP config is the CPU-reference measurement (BASELINE config
        # 1): its worker holds no TPU lease, so the nodelet starts it on
        # the CPU platform.
        import jax
        import jax.numpy as jnp
        import optax
        from flax import linen as nn

        from ray_tpu import train as rt_train

        class Mlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.relu(nn.Dense(512)(x))
                return nn.Dense(classes)(x)

        rng = np.random.default_rng(0)
        xs = rng.standard_normal((n, d), dtype=np.float32)
        ys = rng.integers(0, classes, size=(n,))
        model = Mlp()
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, d)))["params"]
        opt = optax.adam(1e-3)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, xb, yb):
            def loss_fn(p):
                logits = model.apply({"params": p}, xb)
                onehot = jax.nn.one_hot(yb, classes)
                return optax.softmax_cross_entropy(logits, onehot).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state2 = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state2, loss

        t0 = time.perf_counter()
        seen = 0
        for _ in range(config["epochs"]):
            for i in range(0, n, bs):
                params, opt_state, loss = step(
                    params, opt_state, xs[i:i + bs], ys[i:i + bs])
                seen += bs
        float(loss)
        dt = time.perf_counter() - t0
        rt_train.report({"samples_per_s": seen / dt, "loss": float(loss)})

    trainer = DataParallelTrainer(
        train_loop, train_loop_config={"epochs": epochs},
        scaling_config=ScalingConfig(num_workers=1))
    result = trainer.fit()
    return {"samples_per_s": result.metrics["samples_per_s"],
            "final_loss": result.metrics["loss"]}
