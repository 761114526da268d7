"""The plain reference of SDAR-MoE (the Qwen3-MoE block under block
diffusion's mask): float32, `jax.numpy` only, matmuls at `highest`
precision, no kernels, no cache, no sorting of tokens by expert, nothing
imported from the program.

It follows huggingface.co/JetLM/SDAR-30B-A3B-Chat's config.json; what that
file does not state is under `assumed` in configs/sdar-30b-a3b-serve.json.

    block        h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h)), eps 1e-6
    Attn         q (32 x 128), k, v (4 x 128), no bias; q and k RMSNorm over
                 each head's 128 with a learned scale; rotary (rotate-half,
                 theta 1e6) on q and k; scores / sqrt(128); key j visible to
                 query i iff floor(j/B) <= floor(i/B); W_o
    MoE          p = softmax(W_r x) over the experts; the top_k largest,
                 divided by their sum; sum_e w_e W_down,e(silu(W_gate,e x) *
                 W_up,e x): every expert over every token, weight 0 where it
                 was not chosen (a scan over the experts, one upcast at a time)
    head         RMSNorm, W_head; the logit of `mask_token_id` is -inf; the
                 logits at a position are for that position (no shift)

`logprobs(params, ids, kw)` row r is the distribution of position r+1 given
ids[0..r] clean and MASK from r+1 to the end of r+1's block: what block
diffusion that reveals a block left to right (`remasking: sequential`) computes
in the pass that reveals position r+1, wherever the prompt ended. It is one
forward over 1 + B streams: the clean ids, and for each offset j the ids with
every block's positions from its j-th on replaced by MASK; stream j's queries
see the clean keys of earlier blocks and their own stream's keys of their own
block. `generate` runs the passes one by one under either remasking rule.

It reads the program's parameter tree (flax names, HF's projections, the
experts as stacks `gate_up` [E,H,2I] and `down` [E,I,H]) upcast to float32,
and nothing else of the program."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S,h,d], pos [S]: rotate-half rotary embedding."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = pos[:, None].astype(jnp.float32) * freqs  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _moe(p, x, kw, f32):
    """x [T,H] -> [T,H]: each token's top_k experts by plain indexing into a
    [T,E] weight table, then every expert over every token."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    probs = jax.nn.softmax(x @ f32(p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, kw["num_experts_per_tok"])
    table = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], chosen].set(
        top / jnp.sum(top, axis=-1, keepdims=True))
    inter = p["down"].shape[1]

    def expert(y, e):
        gate_up, down, w = e
        gu = x @ f32(gate_up)
        act = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        return y + w[:, None] * (act @ f32(down)), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["gate_up"], p["down"], table.T))
    return y


def _forward(params, clean, noisy: Sequence[Any], kw: Dict[str, Any]):
    """Final hidden states [1 + len(noisy), S, H] of the clean ids [S] (S a
    multiple of the block length) and of each noisy stream, whose queries see
    the clean keys of earlier blocks and their own keys of their own block."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    eps, b = kw["rms_norm_eps"], kw["block_length"]
    h, hk, d = kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]
    ids = jnp.stack([clean, *noisy])               # [N,S]
    n, s = ids.shape
    pos = jnp.arange(s)
    blk = pos // b
    earlier = blk[None, :] < blk[:, None]          # [q,k]
    same = blk[None, :] == blk[:, None]
    x = f32(params["embed_tokens"]["embedding"])[ids]  # [N,S,H]
    for i in range(kw["num_layers"]):
        p = params[f"layers_{i}"]
        a = p["self_attn"]
        y = _rms(x, f32(p["input_layernorm"]["scale"]), eps)
        proj = lambda name, heads: (y @ f32(a[name]["kernel"])).reshape(
            n, s, heads, d)
        rope = lambda t: jnp.stack(
            [_rope(one, pos, kw["rope_theta"]) for one in t])
        q = rope(_rms(proj("q_proj", h), f32(a["q_norm"]["scale"]), eps))
        k = rope(_rms(proj("k_proj", hk), f32(a["k_norm"]["scale"]), eps))
        v = proj("v_proj", hk)
        k, v = (jnp.repeat(t, h // hk, axis=2) for t in (k, v))
        own = jnp.einsum("nqhd,nkhd->nhqk", q, k) * d ** -0.5
        own = jnp.where(same[None, None], own, -jnp.inf)
        past = jnp.einsum("nqhd,khd->nhqk", q, k[0]) * d ** -0.5
        past = jnp.where(earlier[None, None], past, -jnp.inf)
        w = jax.nn.softmax(jnp.concatenate([past, own], axis=-1), axis=-1)
        mixed = (jnp.einsum("nhqk,khd->nqhd", w[..., :s], v[0])
                 + jnp.einsum("nhqk,nkhd->nqhd", w[..., s:], v))
        x = x + mixed.reshape(n, s, h * d) @ f32(a["o_proj"]["kernel"])
        y = _rms(x, f32(p["post_attention_layernorm"]["scale"]), eps)
        x = x + _moe(p["mlp"], y.reshape(n * s, -1), kw, f32).reshape(x.shape)
    return _rms(x, f32(params["norm"]["scale"]), eps)


def _head(params, x, kw):
    """Hidden states [..., H] -> log-probabilities [..., V]; MASK never."""
    import jax
    import jax.numpy as jnp

    logits = x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[-1]) == kw["mask_token_id"],
                       -jnp.inf, logits)
    return jax.nn.log_softmax(logits, axis=-1)


def logprobs(params: Dict[str, Any], ids, kw: Dict[str, Any]):
    """ids [S] -> float32 [S, vocab]; row r: position r+1 given ids[0..r]
    and MASK to the end of r+1's block (see the module's text)."""
    import jax
    import jax.numpy as jnp

    b, mask = kw["block_length"], kw["mask_token_id"]
    s = ids.shape[0]
    padded = (s + b) // b * b  # position s is read too
    clean = jnp.full((padded,), mask, jnp.int32).at[:s].set(ids)
    offset = jnp.arange(padded) % b
    noisy = [jnp.where(offset < j, clean, mask) for j in range(b)]
    with jax.default_matmul_precision("highest"):
        x = _forward(params, clean, noisy, kw)
        at = jnp.arange(1, s + 1)
        return _head(params, x[1 + at % b, at], kw)


def generate(params: Dict[str, Any], prompt: List[int], max_tokens: int,
             kw: Dict[str, Any], remasking: Optional[str] = None
             ) -> Tuple[List[int], Any, List[int], List[float]]:
    """Greedy block diffusion, pass by pass, every pass a forward over the
    whole sequence (no cache). Returns (tokens, their log-probability rows
    [n, vocab] from the pass that revealed each, the positions in the order
    they were revealed, and per pass the gap between the confidence of the
    last position revealed and the best one left masked, `inf` where none is
    left). The tests' oracle; the benchmark's check uses `logprobs`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, mask = kw["block_length"], kw["mask_token_id"]
    rule = remasking or kw["remasking"]
    per = b // kw["denoising_steps"]
    ids = list(prompt[:len(prompt) - len(prompt) % b])
    block = list(prompt[len(ids):])
    rows: Dict[int, Any] = {}
    order: List[int] = []
    margins: List[float] = []
    run = jax.jit(lambda seq: _head(
        params, _forward(params, seq, [], kw)[0, -b:], kw))
    with jax.default_matmul_precision("highest"):
        while len(ids) + len(block) < len(prompt) + max_tokens:
            block = block + [mask] * (b - len(block))
            for _ in range(kw["denoising_steps"]):
                lp = np.asarray(run(jnp.asarray(ids + block, jnp.int32)))
                masked = [j for j in range(b) if block[j] == mask]
                if rule == "low_confidence_static":
                    masked.sort(key=lambda j: (-lp[j].max(), j))
                    if masked[:per]:
                        margins.append(
                            float(lp[masked[per - 1]].max()
                                  - lp[masked[per]].max())
                            if len(masked) > per else float("inf"))
                for j in masked[:per]:
                    block[j] = int(lp[j].argmax())
                    rows[len(ids) + j] = lp[j]
                    order.append(len(ids) + j)
            ids, block = ids + block, []
    at = range(len(prompt), len(prompt) + max_tokens)
    return ([ids[p] for p in at], np.stack([rows[p] for p in at]),
            [p for p in order if p in at], margins)
