"""Functional training core: sharded train/eval steps for flax models.

TPU-first design (reference counterpart: ray.train's torch DDP loop,
python/ray/train/torch/train_loop_utils.py — there the collective plane is
NCCL calls on grads; here the step is a single pjit'd XLA program and the
mesh + shardings make XLA insert the collectives over ICI):

- params/opt-state sharded by ParamShardingRules (DP/FSDP/TP on one mesh);
- batch sharded over (data, fsdp); loss psum'd implicitly by jit;
- bf16 activations, f32 params/optimizer (flax param_dtype), donated carries.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel.sharding import ParamShardingRules, sharding_for


@dataclasses.dataclass
class TrainState:
    """Minimal train state (flax.training.TrainState without the apply_fn
    indirection — the step closes over the model)."""

    step: jax.Array
    params: Any
    opt_state: Any

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy. logits [B,S,V], labels [B,S]."""
    logits = logits.astype(jnp.float32)
    # logsumexp minus the label's logit: no [B,S,V] float32 array of
    # log-probabilities. The logit is picked by comparison, not by a gather:
    # its gradient is then a select inside the softmax's fusion, in float32,
    # where a gather's would be a scatter into a [B,S,V] array of its own.
    hit = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) == labels[..., None]
    nll = jax.nn.logsumexp(logits, axis=-1) \
        - jnp.where(hit, logits, 0.0).sum(axis=-1)
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_train_step(
    model: Any,
    optimizer: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
    param_rules: Optional[ParamShardingRules] = None,
    donate: bool = True,
) -> Callable[[TrainState, jax.Array, jax.Array], Tuple[TrainState, jax.Array]]:
    """Build a jitted (state, input_ids, labels) -> (state, loss) step.

    With a mesh, in/out shardings are attached so the compiled program is a
    single SPMD executable: grads reduce over (data, fsdp), parameters
    all-gather along fsdp, tensor-parallel matmuls psum along tensor.
    """

    def loss_fn(params, input_ids, labels):
        logits = model.apply({"params": params}, input_ids)
        # Shift: predict token t+1 from prefix ≤ t.
        return cross_entropy_loss(logits[:, :-1], labels[:, 1:])

    def step(state: TrainState, input_ids: jax.Array,
             labels: jax.Array) -> Tuple[TrainState, jax.Array]:
        loss, grads = jax.value_and_grad(loss_fn)(state.params, input_ids,
                                                  labels)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(state.step + 1, params, opt_state), loss

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    rules = param_rules
    batch_sh = sharding_for(mesh, ("batch", None))
    repl = NamedSharding(mesh, PartitionSpec())

    def sharded_jit(state_shardings):
        return jax.jit(
            step,
            in_shardings=(state_shardings, batch_sh, batch_sh),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,) if donate else (),
        )

    # Shardings for the state are derived lazily from the first state's
    # structure (opt_state mirrors params via tree_map).
    cache: dict = {}

    def wrapped(state: TrainState, input_ids, labels):
        if "fn" not in cache:
            param_sh = (rules.tree_shardings(mesh, state.params)
                        if rules is not None else
                        jax.tree.map(lambda _: repl, state.params))
            opt_sh = _shard_opt_state_like(state.opt_state, state.params,
                                           param_sh, repl)
            cache["fn"] = sharded_jit(TrainState(repl, param_sh, opt_sh))
        return cache["fn"](state, input_ids, labels)

    return wrapped


def _shard_opt_state_like(opt_state, params, param_sh, repl):
    """Optimizer-state leaves that mirror a parameter (adam m/v) get that
    parameter's sharding; scalars (counts) are replicated. Matching is by
    array shape identity with the param tree structure."""
    flat_params, ptree = jax.tree_util.tree_flatten(params)
    flat_sh = jax.tree_util.tree_flatten(param_sh)[0]

    def one(leaf):
        if leaf is None:
            return None
        for p, s in zip(flat_params, flat_sh):
            if getattr(leaf, "shape", None) == p.shape:
                return s
        return repl

    # Sub-trees of opt_state whose structure equals the param tree get mapped
    # param-wise; everything else is replicated.
    def map_state(node):
        try:
            flat, tree = jax.tree_util.tree_flatten(node)
        except Exception:
            return repl
        if tree == ptree:
            return jax.tree_util.tree_unflatten(tree, flat_sh)
        return jax.tree.map(one, node)

    if isinstance(opt_state, tuple) and not hasattr(opt_state, "shape"):
        return tuple(map_state(s) for s in opt_state)
    return map_state(opt_state)


def init_train_state(
    model: Any,
    optimizer: optax.GradientTransformation,
    sample_input: jax.Array,
    *,
    rng: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    param_rules: Optional[ParamShardingRules] = None,
) -> TrainState:
    """Initialize params (+opt state) directly with the target shardings so
    large models never materialize unsharded (jit out_shardings on the init
    function — the standard big-model init recipe)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def init_fn(rng):
        params = model.init(rng, sample_input)["params"]
        return TrainState(jnp.zeros((), jnp.int32), params,
                          optimizer.init(params))

    if mesh is None or param_rules is None:
        return jax.jit(init_fn)(rng)

    shapes = jax.eval_shape(init_fn, rng)
    param_sh = param_rules.tree_shardings(mesh, shapes.params)
    repl = NamedSharding(mesh, PartitionSpec())
    opt_sh = _shard_opt_state_like(shapes.opt_state, shapes.params, param_sh,
                                   repl)
    state_sh = TrainState(repl, param_sh, opt_sh)
    return jax.jit(init_fn, out_shardings=state_sh)(rng)
