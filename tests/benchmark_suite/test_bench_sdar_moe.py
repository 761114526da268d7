"""The `sdar_moe` family in the benchmark: its configuration against the
published config and the rule, its operation and byte counts, its four
readers on a hand-made trace, the harness's own reference check at a tiny
size on the CPU (and the longer one a builder runs on the chip: four blocks
and a remainder), and the cell's whole programs compiled for a described v5e
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

CONFIG, CELL, FAMILY = ("sdar-30b-a3b-serve", "sdar-decode-heavy",
                        "sdar_moe")
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"moe_gmm_kernel_us": ("us", "lower", "device_trace", "kernels"),
       "moe_gmm_hbm_pct": ("%", "higher", "device_trace", "kernels"),
       "denoise_passes_per_token": ("count", "lower", "program_counter",
                                    "engine scheduler"),
       "experts_touched_pct": ("%", "higher", "program_counter", "kernels")}


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
    # new entries stand at the end of their lists
    assert m.data["configs"][-1]["name"] == CONFIG
    assert m.data["workloads"][-1]["name"] == CELL
    assert [x["name"] for x in m.data["per_layer"][-4:]] == list(NEW)
    for x in m.data["end_to_end"] + m.data["per_layer"]:
        if CELL in x.get("workloads", []):
            assert x["workloads"][-1] == CELL, x["name"]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW[metric]
    assert entry["moves"] == "tpot_p95_ms" and entry["workloads"] == [CELL]
    assert callable(m.reader(metric))
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {x["layer"] for x in m.data["per_layer"][:25]}


# -- the configuration against its source ------------------------------------
def test_configuration_runs_the_published_widths(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert m.configs[CONFIG]["reduced"] == ["num_hidden_layers"]
    published = cfg["published"]
    assert published["num_hidden_layers"] == 48
    assert cfg["num_hidden_layers"] == 6 and 48 % 6 == 0
    assert cfg["deployment"] and set(cfg["reduced"]) == {"num_hidden_layers"}
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["vocab_size"]) == (2048, 768, 151936)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"]) == (128, 8)
    assert (cfg["block_length"], cfg["denoising_steps"], cfg["remasking"],
            cfg["mask_token_id"]) == (4, 4, "sequential", 151935)
    for key in ("qk_norm", "block_length", "denoising_steps", "no_shift",
                "mask_token_id", "commit_pass", "remasking", "initialisers",
                "weights", "intermediate_size"):
        assert cfg["assumed"][key], key
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]


def test_mask_token_is_one_the_traffic_never_draws(cfg):
    import random

    from benchmark import loadgen, tokenizer_gen

    vocab, mask = cfg["vocab_size"], cfg["mask_token_id"]
    assert tokenizer_gen.is_silent(mask, vocab)
    drawn = loadgen.text_ids(random.Random(0), 20_000, vocab)
    assert max(drawn) < vocab - len(tokenizer_gen.SPECIALS) <= mask < vocab


def test_catalog_row_is_the_published_block(cfg):
    """Where the catalog of public architectures is installed, every number
    of its row stands under the same key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key


# -- operation and byte counts -----------------------------------------------
def test_matmul_params_and_kernel_bytes(m, cfg):
    family = m.family(FAMILY)
    kw = family.model_kwargs(cfg)
    assert (kw["num_layers"], kw["num_experts"], kw["num_experts_per_tok"],
            kw["block_length"]) == (6, 128, 8, 4)
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    layer = attn + 2048 * 128 + 8 * 3 * 2048 * 768
    assert family.matmul_params(cfg) == 6 * layer + 2048 * 151936
    assert family.attention_flops_per_token(cfg, 1000) == (
        6 * 2 * 2 * 32 * 128 * 1000 * 0.5)
    # a decode forward: 64 tokens x 8 assignments; every expert touched is
    # 603.98M parameters a layer, the issue's "604M expert parameters"
    rows = 64 * 8
    weights = 128 * 3 * 2048 * 768
    assert weights == 603_979_776
    assert family.moe_gmm_bytes(cfg, 64, 128) == 2 * (
        weights + rows * (2048 + 1536) + rows * (768 + 2048))
    # an expert nobody chose costs nothing: the count follows the counter
    assert family.moe_gmm_bytes(cfg, 64, 64) < 0.51 * family.moe_gmm_bytes(
        cfg, 64, 128)
    assert family.forward_passes_per_token(cfg) == 1.25


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.sdar_moe` (the parent): an error from
    `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.sdar_moe"):
        family.model_kwargs(cfg)


def test_reference_imports_nothing_of_the_program(m):
    with open(m.path("benchmark", "references", FAMILY + ".py")) as f:
        lines = [ln.strip() for ln in f]
    imports = [ln for ln in lines if ln.startswith(("import ", "from "))]
    assert imports and not any("ray_tpu" in ln or "benchmark" in ln
                               for ln in imports)
    assert {ln.split()[1].split(".")[0] for ln in imports} <= {
        "__future__", "typing", "jax", "numpy"}


# -- the readers on a hand-made trace ----------------------------------------
def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _trace(n=6, gate_up_us=800.0, down_us=400.0, touched=96.0):
    """`n` decode windows of 16 active rows: 2 blocks of 4 denoising passes
    and a commit pass; per window one layer-forward's two `moe_gmm` calls
    inside a `jit_decode` launch, one more pair under `jit_prefill` (twice
    as long), and an emit of 120 tokens whose forwards touched
    `touched` experts a layer and forward (of 128; 6 layers x 10 forwards)."""
    host, ops, mods = [_ev("bench.window", 0, 100_000)], [], []
    for i in range(n):
        t = 10_000 * i
        host.append(_ev("ray_tpu.engine.dispatch_decode", t, 5, active=16,
                        max_seqs=16, steps=8, chained=1, new_program=0,
                        state_rows=0, block_length=4, denoise_passes=8,
                        commit_passes=2))
        host.append(_ev("ray_tpu.engine.emit", t + 50, 5, tokens=120,
                        finished=0, skipped=2,
                        experts_touched=int(touched * 60),
                        expert_load_max=9 * 60))
        mods += [_ev(f"jit_decode({7 + i})", t + 100, 5_000),
                 _ev("jit_prefill(3)", t + 6_000, 3_900)]
        call = lambda k, at, us, cols: _ev(
            f"%moe_gmm.{k} = bf16[2432,{cols}]{{1,0}} custom-call(%te, %tu, "
            "%lhs, %rhs)", at, us)
        ops += [call(2 * i, t + 200, gate_up_us, 1536),
                call(2 * i + 1, t + 2_000, down_us, 2048),
                _ev(f"%moe_gmm_fusion.{i} = bf16[16]{{0}} fusion(%x)",
                    t + 3_000, 900),
                call(100 + 2 * i, t + 6_100, 2 * gate_up_us, 1536),
                call(101 + 2 * i, t + 6_100 + 2 * gate_up_us + 10,
                     2 * down_us, 2048)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="llm-engine", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                        NS(name="XLA Modules", events=mods)])])


@pytest.fixture
def obs(m, cfg, monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    traces = {str(path): _trace()}
    monkeypatch.setattr(program_trace.xplane, "load", traces.__getitem__)
    program_trace._read.cache_clear()

    def rewrite(*args, **kw):
        traces[str(path)] = _trace(*args, **kw)
        program_trace._read.cache_clear()

    yield {"traces": [{"path": str(path), "window_s": 0.1, "modules": {}}],
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


def test_kernel_time_reader_counts_the_decode_programs_calls(m, obs):
    read = m.reader("moe_gmm_kernel_us")
    # a layer's two calls together; the prefill's calls and the fusion that
    # borrows the name are not counted
    assert read(obs) == pytest.approx(1200.0)
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_experts_touched_reader(m, obs):
    read = m.reader("experts_touched_pct")
    assert read(obs) == pytest.approx(75.0)
    obs["rewrite"](6, touched=128.0)
    assert read(obs) == pytest.approx(100.0)
    assert read(dict(obs, config={})) is None
    _nothing(read, obs)


def test_roofline_share_reader_cannot_pass_100(m, obs):
    read = m.reader("moe_gmm_hbm_pct")
    family = m.family(FAMILY)
    least_us = family.moe_gmm_bytes(obs["config"], 64, 96) / 819e9 * 1e6
    assert read(obs) == pytest.approx(100 * least_us / 1200.0)
    # the calls at the peak for the experts they touched read 100
    obs["rewrite"](6, least_us * 2 / 3, least_us / 3)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("llama"))) is None
    _nothing(read, obs)


def test_passes_per_token_reader(m, obs):
    read = m.reader("denoise_passes_per_token")
    # 16 rows x 10 forwards a window over the 120 tokens it emitted
    assert read(obs) == pytest.approx(16 * 10 / 120)
    obs["rewrite"](3)
    assert read(obs) is None
    _nothing(read, obs)


def test_other_families_spans_would_read_one_pass_a_token(m, monkeypatch,
                                                          tmp_path):
    host = [_ev("bench.window", 0, 1000)]
    for i in range(6):
        host += [_ev("ray_tpu.engine.dispatch_decode", 100 * i, 5, active=4,
                     max_seqs=16, steps=8, block_length=1, denoise_passes=8,
                     commit_passes=0),
                 _ev("ray_tpu.engine.emit", 100 * i + 50, 5, tokens=32,
                     finished=0, skipped=0)]
    path = tmp_path / "l.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(program_trace.xplane, "load", lambda p: NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="llm-engine", events=host)])]))
    program_trace._read.cache_clear()
    obs = {"traces": [{"path": str(path)}]}
    assert m.reader("denoise_passes_per_token")(obs) == pytest.approx(1.0)
    assert m.reader("experts_touched_pct")(obs) is None
    assert m.reader("moe_gmm_kernel_us")(obs) is None
    program_trace._read.cache_clear()


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_SDAR = {
    "family": FAMILY, "source": "SdarMoeConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 64, "moe_intermediate_size": 32,
    "intermediate_size": 192, "num_experts": 16, "num_experts_per_tok": 8,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32,
    "max_position_embeddings": 512, "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "block_length": 4, "denoising_steps": 4, "remasking": "sequential",
    "mask_token_id": 511,
    "published": {"hidden_size": 64, "num_hidden_layers": 2},
    "reduced": {}, "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.03-0.05 at these seeds
    "check": {"logprob_tol": 0.25},
}


def test_bench_check_reads_the_familys_reference(tmp_path, monkeypatch):
    """`BenchServer` builds the family from `llm_config["family"]`, and
    `bench_check` compares its engine (cache-fill prefill, then blocks of
    denoising and commit passes) with `references/sdar_moe.py` on the same
    bf16 weights: the harness's own four steps on an aligned prompt of 100,
    and the builder's 16 steps on a prompt of 101 (four blocks, a remainder
    of one, three commit passes behind the last position compared)."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs", "tiny-sdar.json"),
              "w") as f:
        json.dump(TINY_SDAR, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-sdar", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-sdar.json", "reduced": []})
    add_cell(data, "sdar-closed", "tiny-sdar", "tiny-closed", "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    assert TINY_TRAFFIC["tiny-closed"]["kind"] == "serve_closed"
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("sdar-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    server = BenchServer(config)
    try:
        assert type(server.server.model).__name__ == "SdarMoeModel"
        assert server.server.engine.prefix_cache is None
        prompt = serve_driver.check_prompt(512, seed)
        out = server.bench_check(prompt, serve_driver.CHECK_STEPS)
        longer = server.bench_check(prompt + prompt[:1], 16)
        warm = server.bench_warm({"32": 30}, 2, 9)
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    assert longer["positions"] == 16
    assert longer["max_logprob_gap"] <= 0.25, longer["max_logprob_gap"]
    assert not warm["missing"] and warm["programs"] == [(32, 1), (32, 2)]


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


def test_sdar_prefill_fits_one_chip_and_computes_no_head(m, cfg, one_chip,
                                                         monkeypatch):
    """The largest prefill (16 prompts of bucket 128: 16,384 assignments
    over tiles of 128 rows) beside the whole depth's weights; it fills the
    cache and samples nothing, so neither the head's [2048, 151936] nor the
    last layer's experts (whose output only the head would read) are
    arguments of the compiled program. (The decode program at two layers:
    tests/test_tpu_compile.py; at six it needs arguments `sizing` cannot
    describe, PERF.md section 7.)"""
    import re

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family = m.family(FAMILY)
    model = family.model(family.model_kwargs(cfg))
    ec = m.traffic(m.cell(CELL)["traffic"])["engine_config"]
    pages = 2 * (16 * 20 + 1) * 64 * 4 * 128 * 2
    assert sizing.kv_pool_bytes(model, ec) == 6 * pages
    prefill = sizing.lower_prefill(model, ec, 128, ec["max_seqs"],
                                   one_chip).compile()
    text = prefill.as_text()
    assert len(re.findall(r"%moe_gmm[.\d]* = ", text)) == 2 * 6 - 2
    peak, parts = sizing.peak_gib(prefill)
    assert parts["args"] * sizing.GIB >= sizing.kv_pool_bytes(model, ec)
    unread = (2048 * 151936 + 128 * 3 * 2048 * 768) * 2 / sizing.GIB
    assert 8.12 - unread - 0.05 <= parts["args"] - 0.24 <= (
        8.12 - unread + 0.05)
    assert 4.0 <= peak <= sizing.USABLE_GIB - 1.0, (peak, parts)
