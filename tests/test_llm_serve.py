"""LLM-on-Serve e2e (BASELINE config 4 shape: streaming replicas behind
serve; reference: llm/tests/serve)."""

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance(ray_start_regular):
    yield
    serve.shutdown()


def test_llm_deployment_streams_tokens(serve_instance):
    from ray_tpu.llm import build_llm_deployment

    app = build_llm_deployment(
        {"model": "tiny", "model_config": {"vocab_size": 128},
         "engine_config": {"max_seqs": 2, "page_size": 4,
                           "max_pages_per_seq": 16}})
    handle = serve.run(app)

    gen = handle.options(method_name="generate", stream=True).remote(
        [5, 17, 42], max_tokens=6)
    items = list(gen)
    assert len(items) == 6
    assert all(isinstance(i["token"], int) for i in items)
    # the engine timed the first token under the request's one id, the
    # handle's: the replica's `ray_tpu.request.first_token` mark
    from ray_tpu.util import state

    assert items[0]["rid"] == gen.request_id
    (first,) = [e["args"] for node in state.flight_record()["nodes"].values()
                for w in node["workers"].values() for e in w["events"]
                if e.get("name") == "ray_tpu.request.first_token"
                and e["args"]["rid"] == gen.request_id]
    assert first["queue_ms"] >= 0 and first["prefill_ms"] > 0

    # Unary path + stats through the same replica.
    out = handle.options(method_name="generate_all").remote(
        [1, 2, 3], max_tokens=4).result(timeout=120)
    assert len(out["tokens"]) == 4
    stats = handle.options(method_name="stats").remote().result(timeout=60)
    assert stats["running"] == 0 and stats["waiting"] == 0


def test_llm_concurrent_requests_batched(serve_instance):
    from ray_tpu.llm import build_llm_deployment

    app = build_llm_deployment(
        {"model": "tiny", "model_config": {"vocab_size": 128},
         "engine_config": {"max_seqs": 4, "page_size": 4,
                           "max_pages_per_seq": 16}})
    handle = serve.run(app)
    gens = [handle.options(method_name="generate", stream=True).remote(
        [i + 1, i + 2], max_tokens=5) for i in range(4)]
    results = [list(g) for g in gens]
    assert all(len(r) == 5 for r in results)
