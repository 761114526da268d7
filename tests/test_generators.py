"""Streaming generator tests (reference: python/ray/tests/
test_streaming_generator*.py — item streaming, backpressure, errors)."""

import time

import numpy as np
import pytest

import ray_tpu


def test_task_generator_streams(ray_start_regular):
    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            yield i * 2

    g = gen.options(num_returns="dynamic").remote(1000)
    vals = [ray_tpu.get(ref) for ref in g]
    assert vals == [i * 2 for i in range(1000)]


def test_generator_first_item_before_task_finishes(ray_start_regular):
    @ray_tpu.remote
    def slow_gen():
        for i in range(10):
            yield i
            time.sleep(0.3)

    t0 = time.time()
    g = slow_gen.options(num_returns="dynamic").remote()
    first = ray_tpu.get(next(iter(g)))
    dt = time.time() - t0
    assert first == 0
    assert dt < 2.5  # well before the ~3s full run (streamed, not buffered)


def test_generator_large_items_via_shm(ray_start_regular):
    @ray_tpu.remote
    def big_gen():
        for i in range(5):
            yield np.full(300_000, i, dtype=np.uint8)  # > inline threshold

    g = big_gen.options(num_returns="dynamic").remote()
    arrs = [ray_tpu.get(r) for r in g]
    assert len(arrs) == 5
    assert all(int(a[0]) == i and len(a) == 300_000
               for i, a in enumerate(arrs))


def test_actor_generator(ray_start_regular):
    @ray_tpu.remote
    class Gen:
        def stream(self, n):
            for i in range(n):
                yield {"i": i}

    a = Gen.remote()
    g = a.stream.options(num_returns="dynamic").remote(50)
    items = [ray_tpu.get(r) for r in g]
    assert [it["i"] for it in items] == list(range(50))


def test_generator_error_mid_stream(ray_start_regular):
    @ray_tpu.remote
    def bad_gen():
        yield 1
        yield 2
        raise ValueError("boom")

    g = bad_gen.options(num_returns="dynamic").remote()
    it = iter(g)
    assert ray_tpu.get(next(it)) == 1
    assert ray_tpu.get(next(it)) == 2
    with pytest.raises(Exception, match="boom"):
        ray_tpu.get(next(it))
    with pytest.raises(StopIteration):
        next(it)


def test_actor_streams_submitted_together_end_apart(ray_start_regular):
    """Streaming calls submitted in one breath go out one frame each: a
    stream ends for its consumer when its generator does, not when the
    longest stream submitted beside it does (a shared frame replies only
    once every member has finished)."""
    @ray_tpu.remote(max_concurrency=8)
    class Gen:
        def stream(self, n, linger_s):
            for i in range(n):
                yield i
            time.sleep(linger_s)

    a = Gen.remote()
    assert ray_tpu.get(next(iter(
        a.stream.options(num_returns="dynamic").remote(1, 0.0)))) == 0
    linger = [0.0, 4.0, 0.0, 4.0, 0.0, 4.0, 0.0, 4.0]
    t0 = time.time()
    gens = [a.stream.options(num_returns="dynamic").remote(3, s)
            for s in linger]
    for g, s in zip(gens, linger):
        if not s:
            assert [ray_tpu.get(r) for r in g] == [0, 1, 2]
    # every short stream has ended, none waited for a lingering neighbour
    assert time.time() - t0 < 3.0
    for g, s in zip(gens, linger):
        if s:
            assert [ray_tpu.get(r) for r in g] == [0, 1, 2]
    assert time.time() - t0 >= 4.0
