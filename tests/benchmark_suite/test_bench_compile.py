"""The cells' whole programs at the configurations' widths and settled
depths, compiled for a described v5e chip: they must fit its memory. No
chip time, on every later PR; a compile that passes is not a chip run.

The topology is described inside a fixture (never while a module is
imported): only one process may load the TPU's library, and every xdist
worker imports every test file."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest

from bench_helpers import REPO
from benchmark import sizing
from benchmark.manifest import Manifest


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The engine takes the Mosaic decode kernel, and flash attention its
    compiled (not interpreted) form, where the default backend is a TPU;
    here it is the CPU, so the test says so in its place."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _cell(name):
    m = Manifest(REPO)
    cell = m.cell(name)
    cfg = m.config(cell["config"])
    return (m.family(cfg["family"]).model_kwargs(cfg),
            m.traffic(cell["traffic"]))


@pytest.mark.parametrize("cell,bucket", [("chat-steady", 512),
                                         ("decode-heavy", 128)])
def test_serving_cell_fits_one_chip(one_chip, on_tpu, cell, bucket):
    kw, traffic = _cell(cell)
    ec = traffic["engine_config"]
    decode = sizing.lower_decode(kw, ec, one_chip).compile()
    assert "tpu_custom_call" in decode.as_text()      # the paged kernel
    prefill = sizing.lower_prefill(kw, ec, bucket, ec["max_seqs"],
                                   one_chip).compile()
    for program in (decode, prefill):
        peak, parts = sizing.peak_gib(program)
        # arguments hold the weights and the whole KV pool
        assert parts["args"] * sizing.GIB >= sizing.kv_pool_bytes(kw, ec)
        assert peak <= sizing.USABLE_GIB - 1.0, (cell, peak, parts)
    # a deployment-sized cell: above a quarter of the chip
    assert sizing.peak_gib(decode)[0] >= 4.0


def test_train_cell_fits_one_chip(one_chip, on_tpu):
    kw, traffic = _cell("train-2k")
    step = sizing.lower_train_step(kw, traffic["batch"], traffic["seq"],
                                   traffic["learning_rate"],
                                   one_chip).compile()
    assert step.as_text().count("tpu_custom_call") >= 3   # flash fwd + bwd
    peak, parts = sizing.peak_gib(step)
    assert 4.0 <= peak <= sizing.USABLE_GIB - 1.0, (peak, parts)
