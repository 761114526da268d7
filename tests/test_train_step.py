"""Sharded train-step tests: tiny Llama on the virtual 8-device CPU mesh with
real DP/FSDP/TP(/SP) shardings — the same path dryrun_multichip exercises."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import LLAMA_SHARDING, LlamaConfig, LlamaModel
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.train.step import (TrainState, cross_entropy_loss,
                                init_train_state, make_train_step)


def _data(cfg, batch=8, seq=64, seed=0):
    rng = jax.random.PRNGKey(seed)
    ids = jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)
    return ids, ids


def test_single_device_train_step_decreases_loss():
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    opt = optax.adamw(1e-3)
    ids, labels = _data(cfg)
    state = init_train_state(model, opt, ids)
    step = make_train_step(model, opt)
    losses = []
    for _ in range(5):
        state, loss = step(state, ids, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert int(state.step) == 5


@pytest.mark.parametrize("mesh_shape", [
    {"data": 2, "fsdp": 2, "tensor": 2},
    {"fsdp": 4, "tensor": 2},
])
def test_sharded_train_step_matches_single_device(mesh_shape):
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    opt = optax.adamw(1e-3)
    ids, labels = _data(cfg)

    ref_state = init_train_state(model, opt, ids)
    ref_step = make_train_step(model, opt, donate=False)
    _, ref_loss = ref_step(ref_state, ids, labels)

    mesh = create_mesh(mesh_shape)
    state = init_train_state(model, opt, ids, mesh=mesh,
                             param_rules=LLAMA_SHARDING)
    step = make_train_step(model, opt, mesh=mesh, param_rules=LLAMA_SHARDING,
                           donate=False)
    _, loss = step(state, ids, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)


def test_sharded_params_are_actually_sharded():
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    opt = optax.adamw(1e-3)
    ids, _ = _data(cfg)
    mesh = create_mesh({"fsdp": 2, "tensor": 4})
    state = init_train_state(model, opt, ids, mesh=mesh,
                             param_rules=LLAMA_SHARDING)
    gate = state.params["layers_0"]["mlp"]["gate_proj"]["kernel"]
    # mlp axis sharded over tensor=4: each shard holds 1/4 of the columns.
    shard_shape = gate.sharding.shard_shape(gate.shape)
    assert shard_shape[1] == gate.shape[1] // 4
    assert shard_shape[0] == gate.shape[0] // 2  # embed_fsdp over fsdp=2


def test_ring_attention_train_step():
    cfg = LlamaConfig.tiny()
    cfg = type(cfg)(**{**cfg.__dict__, "attention_impl": "ring"})
    mesh = create_mesh({"data": 2, "seq": 4})
    model = LlamaModel(cfg, mesh=mesh)
    opt = optax.sgd(1e-2)
    ids, labels = _data(cfg, batch=4, seq=128)
    state = init_train_state(model, opt, ids, mesh=mesh,
                             param_rules=LLAMA_SHARDING)
    step = make_train_step(model, opt, mesh=mesh, param_rules=LLAMA_SHARDING)
    state, loss = step(state, ids, labels)
    assert jnp.isfinite(loss)


def test_cross_entropy_masking():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.array([[1, 2, 3, 4]])
    full = cross_entropy_loss(logits, labels)
    masked = cross_entropy_loss(logits, labels,
                                mask=jnp.array([[1, 1, 0, 0]]))
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-6)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_cross_entropy_is_the_log_softmax_form(dtype, masked):
    """`logsumexp - logit[label]` against `-log_softmax[label]`: the same
    number and the same gradient, with no [B, S, V] float32 array of
    log-probabilities between them."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    logits = (4.0 * jax.random.normal(keys[0], (3, 17, 96))).astype(dtype)
    labels = jax.random.randint(keys[1], (3, 17), 0, 96)
    mask = (jax.random.bernoulli(keys[2], 0.6, (3, 17)) if masked else None)

    def plain(logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        if mask is None:
            return nll.mean()
        m = mask.astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)

    want, want_grad = jax.value_and_grad(plain)(logits)
    got, got_grad = jax.value_and_grad(
        lambda x: cross_entropy_loss(x, labels, mask))(logits)
    assert got.dtype == jnp.float32 and got_grad.dtype == dtype
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    # (both forms round one float32 gradient to bf16: an ulp apart at most)
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(got_grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=tol, atol=tol * 1e-3)


def _remat_pair(impl):
    cfg = dataclasses.replace(LlamaConfig.tiny(), attention_impl=impl)
    return cfg, dataclasses.replace(cfg, remat=True)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_remat_keeps_the_loss_and_the_gradients(impl):
    """What a remat'd layer keeps is what its recomputation would make
    again: loss and gradients are those of the layer that stores all."""
    plain, remat = _remat_pair(impl)
    ids, labels = _data(plain, batch=2, seq=64)
    params = LlamaModel(plain).init(jax.random.PRNGKey(0), ids)["params"]

    def loss_and_grads(cfg):
        def loss(p):
            logits = LlamaModel(cfg).apply({"params": p}, ids)
            return cross_entropy_loss(logits[:, :-1], labels[:, 1:])
        return jax.jit(jax.value_and_grad(loss))(params)

    want, want_grads = loss_and_grads(plain)
    got, got_grads = loss_and_grads(remat)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        got_grads, want_grads)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_a_remat_layer_keeps_the_narrow_values_and_nothing_wide(impl,
                                                                capsys):
    """The residuals JAX saves for a one-layer model with `remat=True`:
    from inside the attention block exactly q, k, v as rotated (k and v at
    their KV heads), the projection's output and, on the flash path, the
    kernel's output and its row sums without their one-lane last axis;
    nothing as wide as the FFN."""
    from jax.ad_checkpoint import print_saved_residuals

    cfg = dataclasses.replace(_remat_pair(impl)[1], num_layers=1)
    model = LlamaModel(cfg)
    b, s, h, hk, d = 2, 64, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ids = jnp.ones((b, s), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    print_saved_residuals(lambda p: model.apply(p, ids).sum(), params)
    saved = [line for line in capsys.readouterr().out.splitlines()
             if "from the argument" not in line
             and "from a constant" not in line]
    shape = lambda *dims: "f32[" + ",".join(map(str, dims)) + "]"
    want = [shape(b, s, h, d), shape(b, s, hk, d), shape(b, s, hk, d),
            shape(b, s, cfg.hidden_size)]
    if impl == "flash":
        want += [shape(b, h, s, d), shape(b, h, s)]
        row_sums = [line for line in saved if "flash_lse" in line]
        assert [line.split()[0] for line in row_sums] == [shape(b, h, s)]
    inside = [line.split()[0] for line in saved
              if "(Attention." in line or "(flash_attention)" in line]
    assert sorted(inside) == sorted(want), saved
    assert not [line for line in saved
                if str(cfg.intermediate_size) in line.split()[0]], saved
