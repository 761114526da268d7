"""What the loader hands serving: the tree the programs compute with. A leaf
the model only converts to its compute dtype is rounded once, at load; the
rest, a float32 model's whole tree and training's masters stay as they are."""

import dataclasses
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.llm._internal.engine import EngineConfig, LLMEngine, Request
from ray_tpu.llm._internal.server import load_model_and_params
from ray_tpu.models import serving_params
from ray_tpu.models.llama import LlamaConfig, LlamaModel

SEED = 3
ENGINE = dict(max_seqs=2, page_size=8, max_pages_per_seq=8, decode_steps=4,
              prefill_buckets=(32,))


def _model_config(dtype):
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=dtype)
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _float32_tree(model):
    """What `model.init` gives for SEED: training's float32 masters."""
    return jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(SEED))


def _generate(model, params, tokens=10, capture=None):
    eng = LLMEngine(model, params, EngineConfig(**ENGINE))
    if capture is not None:
        run = eng._run_program

        def recording(kind, key, fn, args):
            capture[kind] = (fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return run(kind, key, fn, args)

        eng._run_program = recording
    eng.add_request(Request("r", list(range(5, 25)), max_tokens=tokens,
                            logprobs=5))
    outs = []
    while eng.has_work():
        outs += eng.step()
    return eng, [(o.token, o.logprob, o.top_logprobs) for o in outs]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("source", ["seeded", "params_path"])
def test_loader_gives_the_tree_the_programs_compute_with(source, dtype,
                                                         tmp_path):
    llm_config = {"model": "custom", "model_config": _model_config(dtype),
                  "seed": SEED}
    masters = _float32_tree(LlamaModel(LlamaConfig(**_model_config(dtype))))
    if source == "params_path":
        llm_config["params_path"] = str(tmp_path / "params.pkl")
        with open(llm_config["params_path"], "wb") as f:
            pickle.dump(jax.tree.map(np.asarray, masters), f)
    model, params = load_model_and_params(llm_config)

    assert jax.tree.structure(params) == jax.tree.structure(masters)
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = path[-1].key
        want = dtype if name in ("kernel", "embedding") else jnp.float32
        assert name in ("kernel", "embedding", "scale")
        assert leaf.dtype == want, jax.tree_util.keystr(path)
        assert isinstance(leaf, jax.Array)
    if dtype == jnp.float32:
        for got, want in zip(jax.tree.leaves(params),
                             jax.tree.leaves(masters)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        # Rounded to nearest, from the same float32 values.
        for got, want in zip(jax.tree.leaves(params),
                             jax.tree.leaves(masters)):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want.astype(got.dtype)))

    # The programs compute what they computed on the float32 tree: the same
    # greedy tokens and the same top-five logprobs, bit for bit.
    _, rounded = _generate(model, params)
    _, wide = _generate(model, masters)
    assert len(rounded) == 10 and rounded == wide


def test_programs_take_no_float32_weight():
    """Neither the decode program nor a prefill of a bf16 model built
    through the loader has a float32 tensor of a weight's shape: not as a
    parameter, and so not as the operand of a convert."""
    llm_config = {"model": "custom", "seed": SEED,
                  "model_config": _model_config(jnp.bfloat16)}
    model, params = load_model_and_params(llm_config)
    captured = {}
    eng, _ = _generate(model, params, tokens=6, capture=captured)
    fn, shapes = captured["prefill"]
    texts = {"decode": eng.lowered_decode_text(),
             "prefill": fn.lower(*shapes).as_text()}
    weights = {x.shape for x in jax.tree.leaves(params) if x.ndim >= 2}
    assert len(weights) >= 6
    for kind, text in texts.items():
        for shape in weights:
            dims = "x".join(map(str, shape))
            assert f"tensor<{dims}xbf16>" in text, (kind, shape)
            assert f"tensor<{dims}xf32>" not in text, (kind, shape)
    # The float32 tree's programs are what this guards against.
    masters = LLMEngine(model, _float32_tree(model), EngineConfig(**ENGINE))
    assert "tensor<128x256xf32>" in masters.lowered_decode_text()


def test_train_step_keeps_float32_masters():
    """Training never meets the loader: `LlamaModel`'s parameters are
    float32 before and after a step, whatever the compute dtype."""
    from ray_tpu.train.step import init_train_state, make_train_step

    model = LlamaModel(LlamaConfig(**_model_config(jnp.bfloat16)))
    opt = optax.sgd(1e-2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 512)
    state = init_train_state(model, opt, ids)
    state, loss = make_train_step(model, opt)(state, ids, ids)
    assert np.isfinite(float(loss))
    assert {x.dtype for x in jax.tree.leaves(state.params)} == {
        jnp.dtype(jnp.float32)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_rounding_is_a_no_op_on_the_hybrid(dtype):
    """The hybrid holds its weights in the compute dtype already and
    consumes its float32 norm scales in float32: every leaf comes back as
    the object it was."""
    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig, OlmoHybridModel

    model = OlmoHybridModel(dataclasses.replace(
        OlmoHybridConfig.tiny(), dtype=dtype, param_dtype=dtype))
    params = model.init_params(jax.random.PRNGKey(1))
    assert {x.dtype for x in jax.tree.leaves(params)} >= {
        jnp.dtype(jnp.float32)}
    after = serving_params(model, params)
    before = jax.tree.leaves(params)
    assert len(before) > 20
    assert all(a is b for a, b in zip(jax.tree.leaves(after), before))


def test_a_float32_use_keeps_a_leaf_float32():
    """Which leaves are rounded is read off how the model uses them, not
    off their names: the MoE router's `kernel` multiplies in float32 and
    stays, the experts' weights are converted at use and are rounded."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.bfloat16,
                              num_experts=2)
    model = LlamaModel(cfg)
    mlp = serving_params(model, _float32_tree(model))["layers_0"]["mlp"]
    assert mlp["router"]["kernel"].dtype == jnp.float32
    assert {mlp[k].dtype for k in ("gate_kernel", "up_kernel",
                                   "down_kernel")} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("held", ["float32 masters", "bf16 as published"])
def test_sdar_moe_keeps_its_router_float32_and_one_copy_of_its_experts(held):
    """The block-diffusion MoE family: the router's kernel multiplies in
    float32 and the norm scales are consumed in float32, so both stay;
    every expert stack, projection, the embedding and the head are held in
    bf16, rounded from float32 masters or, drawn in bf16 as published, the
    very objects the initializer made (no second copy)."""
    from ray_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeModel

    param_dtype = jnp.float32 if held == "float32 masters" else jnp.bfloat16
    model = SdarMoeModel(SdarMoeConfig.tiny(dtype=jnp.bfloat16,
                                            param_dtype=param_dtype))
    params = model.init_params(jax.random.PRNGKey(1))
    after = serving_params(model, params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(after)[0]:
        name = path[-1].key
        assert name in ("kernel", "embedding", "scale", "router", "gate_up",
                        "down")
        want = jnp.float32 if name in ("scale", "router") else jnp.bfloat16
        assert leaf.dtype == want, jax.tree_util.keystr(path)
    if param_dtype == jnp.bfloat16:
        assert all(a is b for a, b in zip(jax.tree.leaves(after),
                                          jax.tree.leaves(params)))
    else:
        mlp, was = after["layers_0"]["mlp"], params["layers_0"]["mlp"]
        assert mlp["router"] is was["router"]
        np.testing.assert_array_equal(
            np.asarray(mlp["gate_up"]),
            np.asarray(was["gate_up"].astype(jnp.bfloat16)))


def test_sdar_moe_at_depth_six_is_held_in_8_72e9_bytes_of_bf16():
    """What `ray_tpu.engine.params_placed` reports for the benchmark's cut,
    from shapes alone: 4.36B parameters in bf16, the six routers and the
    norm scales (6.4 MB) in float32."""
    from ray_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeModel

    model = SdarMoeModel(SdarMoeConfig(num_layers=6))
    shapes = jax.eval_shape(lambda key: serving_params(model, model.init(
        key, jnp.zeros((1, 4), jnp.int32))["params"]), jax.random.PRNGKey(0))
    eng = LLMEngine.__new__(LLMEngine)
    eng.params = shapes
    held = eng._describe_params()
    assert set(held) == {"bfloat16", "float32"}
    assert 8.71e9 < held["bfloat16"] < 8.73e9
    assert held["float32"] == 4 * (6 * (2048 * 128 + 2 * 2048 + 2 * 128)
                                   + 2048)


def test_stats_and_mark_say_what_the_replica_holds():
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.llm._internal.server import LLMServer

    srv = LLMServer({"model": "custom", "seed": SEED,
                     "model_config": _model_config(jnp.bfloat16),
                     "engine_config": ENGINE})
    try:
        stats = srv.stats()
    finally:
        srv._running = False
    by_dtype = stats["param_bytes_by_dtype"]
    n = {k: sum(x.size for x in jax.tree.leaves(srv.params)
                if x.dtype == k) for k in (jnp.bfloat16, jnp.float32)}
    assert by_dtype == {"bfloat16": 2 * n[jnp.bfloat16],
                        "float32": 4 * n[jnp.float32]}
    assert by_dtype["float32"] < by_dtype["bfloat16"] // 100
    assert stats["param_bytes_per_device"] == [sum(by_dtype.values())]
    placed = [e for e in fr.dump_events() if e.get("kind") == "span"
              and e["name"] == "ray_tpu.engine.params_placed"][-1]
    assert placed["args"] == {"bfloat16_bytes": by_dtype["bfloat16"],
                              "float32_bytes": by_dtype["float32"]}
