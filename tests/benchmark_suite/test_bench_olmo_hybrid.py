"""The `olmo_hybrid` family in the benchmark: its configuration against the
published config and the rule, its operation and byte counts, its three
readers on a hand-made trace, the harness's own reference check at a tiny
size on the CPU, and the cell's whole programs compiled for a described v5e
chip (no chip time; a compile that passes is not a chip run).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU's library."""

import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, TINY_TRAFFIC, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver
from benchmark import sizing

CONFIG, CELL, FAMILY = ("olmo-hybrid-7b-serve", "hybrid-decode-heavy",
                        "olmo_hybrid")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# The ten per-layer lists two accepted tests pin to the cells they had.
PINNED = ("queue_wait_mean_ms", "prefill_mean_ms", "admit_batch_mean",
          "admit_stall_mean_ms", "decode_rows_active_pct",
          "paged_decode_kernel_us", "flash_fwd_kernel_ms",
          "flash_bwd_kernel_ms", "stream_lag_mean_ms",
          "stream_tokens_per_item")
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"gdn_decode_kernel_us": ("us", "device_trace", "kernels",
                                "tpot_p95_ms"),
       "gdn_decode_hbm_pct": ("%", "device_trace", "kernels", "tpot_p95_ms"),
       "decode_cache_kernels_pct": ("%", "device_trace", "kernels",
                                    "tpot_p95_ms")}


@pytest.fixture(scope="module")
def m():
    return mf.Manifest(REPO)


@pytest.fixture(scope="module")
def cfg(m):
    return m.config(CONFIG)


# -- the manifest's entries --------------------------------------------------
def test_manifest_is_clean_and_lists_the_cell_where_it_reports(m):
    assert mf.check(m) == []
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-heavy", 1)
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "tpot_p95_ms", "out_tok_per_s", "setup_s"}
    layer = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert layer == set(SHARED) | set(NEW)
    for name in PINNED:
        assert CELL not in m.per_layer[name]["workloads"]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["source"], entry["layer"],
            entry["moves"]) == NEW[metric]
    assert entry["workloads"] == [CELL]
    assert callable(m.reader(metric))
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {x["layer"] for x in m.data["per_layer"][:22]}


# -- the configuration against its source ------------------------------------
def test_configuration_runs_the_published_widths(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert m.configs[CONFIG]["reduced"] == ["num_hidden_layers",
                                            "layer_types"]
    published = cfg["published"]
    assert published["num_hidden_layers"] == 32
    assert published["layer_types"] == PERIOD * 8
    assert cfg["num_hidden_layers"] == 16           # four whole periods
    assert cfg["layer_types"] == published["layer_types"][:16]
    assert cfg["deployment"] and set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types"}
    # every other key of the source as published, widths among them
    for key, value in published.items():
        if key not in ("num_hidden_layers", "layer_types"):
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["vocab_size"]) == (3840, 11008, 100352)
    assert (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]) == (
        30, 96, 192, 4)
    for key in ("block_norm", "qk_norm", "rotary", "conv_bias",
                "gate_init", "weights"):
        assert cfg["assumed"][key], key
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]


def test_catalog_row_is_the_published_block(cfg):
    """Where the catalog of public architectures is installed, every number
    of its row stands under the same key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg["published"].get(key, cfg.get(key)) == value, key


@pytest.mark.parametrize("fault,says", [
    ("depth_not_listed", "`reduced` does not list it"),
    ("types_not_listed", "layer_types is"),
    ("no_deployment", "states no `deployment`"),
    ("width_cut", "hidden_size is 1920"),
])
def test_the_rule_refuses_a_cut_that_is_not_stated(tmp_path, fault, says):
    root = tiny_root(tmp_path)
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    reduced = ["num_hidden_layers", "layer_types"]
    if fault == "depth_not_listed":
        reduced = ["layer_types"]
    elif fault == "types_not_listed":
        reduced = ["num_hidden_layers"]
    elif fault == "no_deployment":
        del config["deployment"]
    else:
        config["hidden_size"] = 1920
    with open(os.path.join(root, "benchmark", "configs", "h.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "h", "source": "tests", "why": "tests",
                            "file": "benchmark/configs/h.json",
                            "reduced": reduced})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    problems = mf.published_problems(mf.Manifest(root), "h")
    assert any(says in p for p in problems), problems


# -- operation and byte counts -----------------------------------------------
LINEAR_LAYER = 88_704_000 + 126_812_160     # mixer projections + SwiGLU
FULL_LAYER = 58_982_400 + 126_812_160


def test_matmul_params_and_kernel_bytes(m, cfg):
    family = m.family(FAMILY)
    kw = family.model_kwargs(cfg)
    assert kw["vocab_size"] == 100352 and len(kw["layer_types"]) == 16
    assert (kw["num_heads"], kw["head_dim"]) == (30, 128)
    assert family.matmul_params(cfg) == (
        12 * LINEAR_LAYER + 4 * FULL_LAYER + 3840 * 100352)
    assert family.matmul_params(cfg) == 3_714_723_840
    whole = dict(cfg, num_hidden_layers=32, layer_types=PERIOD * 8)
    assert family.matmul_params(whole) == (
        24 * LINEAR_LAYER + 8 * FULL_LAYER + 3840 * 100352)
    # full layers only, causal half
    assert family.attention_flops_per_token(cfg, 1000) == (
        4 * 2 * 2 * 30 * 128 * 1000 * 0.5)
    # one call: 16 rows x 30 heads x (state in and out + 4 key columns +
    # beta*v + o), float32: the issue's 70.8 MB of state and 1.5 MB beside
    state = 16 * 30 * 96 * 192 * 4
    assert family.state_bytes(cfg, 16) == state
    assert family.gdn_decode_bytes(cfg, 16) == (
        2 * state + 16 * 30 * (4 * 96 + 2 * 192) * 4) == 72_253_440


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.olmo_hybrid` (the parent): an error
    from `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.olmo_hybrid"):
        family.model_kwargs(cfg)


# -- the readers on a hand-made trace ----------------------------------------
def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _trace(n=6, kernel_us=100.0):
    """`n` decode dispatches of 8 active rows on 12 state layers, and per
    dispatch two `gdn_decode` calls of `kernel_us` each and a `paged_decode`
    call of 50 us; a fusion that borrows the kernel's name does not count."""
    host, ops = [_ev("bench.window", 0, 1000)], []
    for i in range(n):
        t = 100 * i
        host.append(_ev("ray_tpu.engine.dispatch_decode", t, 5, active=8,
                        max_seqs=16, steps=8, chained=1, new_program=0,
                        state_rows=96))
        ops += [_ev(f"%gdn_decode.{i} = (f32[16,3,10,192]{{3,2,1,0}}, f32[16,"
                    "30,96,192]{3,2,1,0}) custom-call(%a, %c)", t + 10,
                    kernel_us),
                _ev(f"%gdn_decode.{100 + i} = (f32[16,3,10,192]{{3,2,1,0}}, "
                    "f32[16,30,96,192]{3,2,1,0}) custom-call(%a, %c)", t + 20,
                    kernel_us),
                _ev(f"%gdn_decode_fusion.{i} = f32[16]{{0}} fusion(%x)",
                    t + 30, 900),
                _ev(f"%paged_decode.{i} = bf16[16,30,1,128]{{3,2,1,0}} "
                    "custom-call(%pt, %q)", t + 40, 50)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="llm-engine", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])])


@pytest.fixture
def obs(m, cfg, monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    traces = {str(path): _trace()}
    monkeypatch.setattr(program_trace.xplane, "load", traces.__getitem__)
    program_trace._read.cache_clear()

    def rewrite(*args):
        traces[str(path)] = _trace(*args)
        program_trace._read.cache_clear()

    # the decode program ran 1,000 us a dispatch in the slice
    modules = {"jit_decode(123)": {"seconds": 6 * 1000e-6, "count": 6},
               "jit_prefill(7)": {"seconds": 1.0, "count": 1}}
    yield {"traces": [{"path": str(path), "window_s": 0.001,
                       "modules": modules}],
           "config": cfg, "family": m.family(FAMILY),
           "traffic": m.traffic("decode-heavy"),
           "peaks": m.peaks("TPU v5 lite"), "rewrite": rewrite}
    program_trace._read.cache_clear()


def _nothing(read, obs):
    # a program without the kernel or the counters (the parent), a run
    # without a trace, a run without a chip
    assert read(dict(obs, traces=[{"path": "/nonexistent/x.pb"}])) is None
    assert read(dict(obs, traces=[])) is None
    assert read({}) is None and read({"seconds": 1.0}) is None


def test_kernel_time_reader(m, obs):
    read = m.reader("gdn_decode_kernel_us")
    assert read(obs) == pytest.approx(100.0)
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_roofline_share_reader(m, obs):
    read = m.reader("gdn_decode_hbm_pct")
    least_us = 72_253_440 / 819e9 * 1e6          # 88.2 us at the peak
    assert read(obs) == pytest.approx(100 * least_us / 100.0)
    # a call at the peak reads 100, and no call can read more
    obs["rewrite"](6, least_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("llama"))) is None
    _nothing(read, obs)


def test_cache_kernels_share_reader(m, obs):
    read = m.reader("decode_cache_kernels_pct")
    # two gdn_decode calls of 100 us and one paged_decode of 50 us in every
    # 1,000 us of the decode program; the prefill program is not counted
    assert read(obs) == pytest.approx(100 * (2 * 100 + 50) / 1000)
    obs["rewrite"](2)        # four gdn_decode calls: nothing to average
    assert read(obs) is None
    obs["rewrite"](6)
    assert read(dict(obs, traces=[dict(obs["traces"][0], modules={})])) is None
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_HYBRID = {
    "family": FAMILY, "source": "OlmoHybridConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 512,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "layer_types": PERIOD * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 24, "linear_value_head_dim": 48,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "published": {"hidden_size": 128, "num_hidden_layers": 8},
    "reduced": {}, "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.17 at the rehearsal's seed
    "check": {"logprob_tol": 0.25},
}


def test_bench_check_reads_the_hybrids_reference(tmp_path, monkeypatch):
    """`BenchServer` builds the family from `llm_config["family"]`, and
    `bench_check` compares its engine (paged prefill, chunkwise state, then
    the decode path) with `references/olmo_hybrid.py` on the same bf16
    weights."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs", "tiny-hybrid.json"),
              "w") as f:
        json.dump(TINY_HYBRID, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-hybrid", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-hybrid.json", "reduced": []})
    add_cell(data, "hybrid-closed", "tiny-hybrid", "tiny-closed",
             "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    assert TINY_TRAFFIC["tiny-closed"]["kind"] == "serve_closed"
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("hybrid-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    server = BenchServer(config)
    try:
        assert type(server.server.model).__name__ == "OlmoHybridModel"
        assert server.server.engine.prefix_cache is None
        out = server.bench_check(
            serve_driver.check_prompt(512, seed), serve_driver.CHECK_STEPS)
        cache = server.stats()["cache"]
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    assert (cache["kv_layers"], cache["state_layers"]) == (2, 6)


# -- the cell's whole programs, compiled for a described v5e -----------------
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
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_hybrid_cell_fits_one_chip(m, cfg, one_chip, monkeypatch):
    import re

    import jax

    # The engine takes both Mosaic kernels where the default backend is a
    # TPU; here it is the CPU, so the test says so in its place.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family = m.family(FAMILY)
    model = family.model(family.model_kwargs(cfg))
    ec = m.traffic(m.cell(CELL)["traffic"])["engine_config"]
    # 4 full layers of K/V pages and 12 layers of state and tails
    pages = 2 * 30 * (16 * 20 + 1) * 64 * 128 * 2
    state = 16 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert sizing.kv_pool_bytes(model, ec) == 4 * pages + 12 * state
    decode = sizing.lower_decode(model, ec, one_chip).compile()
    text = decode.as_text()
    kernels = set(re.findall(r"%((?:gdn|paged)_decode)[.\d]* = ", text))
    assert kernels == {"gdn_decode", "paged_decode"}   # two kinds
    assert text.count("tpu_custom_call") >= 16
    # the state pool goes through the kernel in place: no copy of it
    assert not re.search(r"= f32\[16,30,96,192\]\S* copy\(", text)
    prefill = sizing.lower_prefill(model, ec, 128, ec["max_seqs"],
                                   one_chip).compile()
    for program in (decode, prefill):
        peak, parts = sizing.peak_gib(program)
        assert parts["args"] * sizing.GIB >= sizing.kv_pool_bytes(model, ec)
        assert 4.0 <= peak <= sizing.USABLE_GIB - 1.0, (peak, parts)
