"""The `mellum` family in the benchmark: its configuration against the
published config and the rule (depth alone is reduced, no width is), its
parameter, byte and operation counts, the new traffic file's numbers, its
five readers on a hand-made trace, and the harness's own reference check at
a tiny size on the CPU. The cell's whole programs are compiled for a described
v5e in tests/test_tpu_compile.py (one file holds every such compile: only one
process may load the TPU's library). Nothing here asserts where in a list of
the manifest an entry stands."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver

CONFIG, CELL, FAMILY, TRAFFIC = ("mellum2-12b-a2.5b-serve",
                                 "mellum-code-context", "mellum",
                                 "code-context")
# The lists the cell was appended to (the seven PR 42's cell joined).
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"swa_decode_kernel_us": ("us", "lower", "device_trace",
                                "tpot_p95_ms"),
       "swa_decode_hbm_pct": ("%", "higher", "device_trace", "tpot_p95_ms"),
       "swa_flash_kernel_ms": ("ms", "lower", "device_trace",
                               "out_tok_per_s"),
       "swa_flash_mxu_pct": ("%", "higher", "device_trace", "out_tok_per_s"),
       "kv_read_window_pct": ("%", "lower", "program_counter",
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
        CONFIG, TRAFFIC, 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 0 < len(cell["why"]) <= 200
    entry = m.configs[CONFIG]
    assert 0 < len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "tpot_p95_ms", "out_tok_per_s", "setup_s"}
    layer = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert layer == set(SHARED) | set(NEW)
    # one use of the pair, and the only cell of its configuration
    assert [w["name"] for w in m.data["workloads"]
            if (w["config"], w["traffic"]) == (CONFIG, TRAFFIC)] == [CELL]
    assert sum(w["chips"] == 4 for w in m.data["workloads"]) == 0


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == NEW[metric]
    assert entry["layer"] == "kernels" == m.per_layer[
        "paged_decode_kernel_us"]["layer"]
    assert entry["workloads"] == [CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert callable(m.reader(metric))


def test_traffic_file_holds_the_issues_numbers(m):
    from benchmark import loadgen
    from ray_tpu.llm._internal.engine import EngineConfig

    traffic = m.traffic(TRAFFIC)
    assert (traffic["kind"], traffic["clients"], traffic["rounds"]) == (
        "serve_closed", 8, 32)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 4096}
    assert traffic["output_len"] == {"dist": "uniform", "min": 256,
                                     "max": 512}
    assert traffic["engine_config"] == {
        "max_seqs": 8, "page_size": 64, "max_pages_per_seq": 72,
        "prefill_buckets": [4096]}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert (traffic["max_ongoing_requests"], traffic["drain_s"]) == (64, 60.0)
    assert "arrivals" not in traffic and "prefix" not in traffic
    # every prompt in the 4,096 bucket; with its answer and the window a
    # decode program may overshoot by, inside the slot's pages; every context
    # from two to four and a half windows
    ec = EngineConfig(**traffic["engine_config"])
    assert loadgen.buckets_used(traffic, list(ec.prefill_buckets)) == [4096]
    reqs = loadgen.requests(traffic, 98304, 2 ** 31 + 5, 40.0)
    assert len(reqs) == 8 * 32
    assert all(2048 <= len(r.prompt) <= 4096 and 256 <= r.max_tokens <= 512
               for r in reqs)
    assert max(len(r.prompt) + r.max_tokens + ec.decode_steps - 1
               for r in reqs) <= 72 * 64 == 4608
    assert min(len(r.prompt) for r in reqs) >= 2 * 1024
    assert serve_driver.warm_spec(traffic)["prompt_lens"] == {"4096": 4086}
    assert serve_driver.warm_spec(traffic)["max_nb"] == 8


# -- the configuration against its source ------------------------------------
def test_configuration_cuts_depth_and_no_width(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert "3,794,968,832" in cfg["reduced"]["num_hidden_layers"]
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"],
            cfg["published"]["num_hidden_layers"]) == (8, 28)
    for said in ("four pipeline stages of 8, 8, 8 and 4", "all 64 experts",
                 "first stage", "final norm", "idle share"):
        assert said in cfg["deployment"], said
    # two whole periods: 6 sliding layers and 2 full ones, the published 21 : 7
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    run_types = m.family(FAMILY).layer_types(cfg)
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert run_types == period * 2 and cfg["layer_types"] == period * 7
    assert set(cfg["mlp_layer_types"]) == {"sparse"}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["vocab_size"],
            cfg["intermediate_size"]) == (
        2304, 32, 4, 128, 64, 896, 8, 1024, 98304, 7168)
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    for key in ("q_norm_k_norm", "layer_types", "sliding_window",
                "rope_parameters", "intermediate_size", "mtp_head", "weights",
                "init", "head"):
        assert cfg["assumed"][key], key
    assert cfg["run"]["max_seq_len"] == 4608
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]
    memory = cfg["memory_analysis"]
    assert 0.25 * 15.75 < memory["decode"]["peak_gib"] < \
        memory["prefill_4096x8"]["peak_gib"] < 15.75


def test_catalog_row_is_the_published_block(m, cfg):
    """Where the catalog of public architectures is installed, every key of
    its row's `config` stands in the file under the same key, as published,
    but for the one the manifest lists as reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"] == m.configs[CONFIG]["source"]
    for key, value in row["config"].items():
        assert cfg["published"][key] == value, key
        assert cfg[key] == value or key in m.configs[CONFIG]["reduced"], key
    assert row["layers"] == cfg["published"]["num_hidden_layers"]


def test_the_rule_refuses_a_cut_this_file_does_not_state(tmp_path):
    root = tiny_root(tmp_path)
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    config["sliding_window"] = 512
    with open(os.path.join(root, "benchmark", "configs", "cut.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "cut", "source": "tests", "why": "tests",
                            "file": "benchmark/configs/cut.json",
                            "reduced": ["num_hidden_layers"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    bad = mf.published_problems(mf.Manifest(root), "cut")
    assert any("sliding_window is 512" in b for b in bad)


# -- the family's counts -------------------------------------------------------
def test_parameter_count_is_the_issues_arithmetic(m, cfg):
    family = m.family(FAMILY)
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512 + 256
    assert family.attention_params(cfg) == attention == 21_233_920
    expert = 2304 * 1792 + 896 * 2304
    assert family.expert_params(cfg) == expert == 6_193_152
    layer = attention + 2304 * 64 + 64 * expert + 4608
    assert family.layer_params(cfg) == layer == 417_747_712
    vocabulary = 2 * 98304 * 2304
    assert vocabulary == 452_984_832
    assert family.parameters(cfg) == 8 * layer + vocabulary + 2304 \
        == 3_794_968_832
    assert 2 * family.parameters(cfg) / 2 ** 30 == pytest.approx(7.069,
                                                                 abs=1e-3)
    whole = family.parameters(dict(cfg, num_hidden_layers=28))
    assert whole == 28 * layer + vocabulary + 2304 == 12_149_923_072  # 12.15B
    assert (family.sliding_layers(cfg), family.full_layers(cfg)) == (6, 2)
    # what multiplies a token: 8 of the 64 experts, no norm, the head
    active = attention - 256 + 2304 * 64 + 8 * expert
    assert family.matmul_params(cfg) == 8 * active + 98304 * 2304
    assert family.matmul_params(dict(cfg, num_hidden_layers=28)) \
        + 98304 * 2304 == pytest.approx(2.44e9, rel=5e-3)   # the A2.5B
    pair = 2 * 2 * 32 * 128
    assert family.attention_flops_per_token(cfg, 512) == pair * 8 * 256
    assert family.attention_flops_per_token(cfg, 4096) == pair * (
        2 * 2048 + 6 * 1024)
    kw = family.model_kwargs(cfg)
    assert (kw["num_experts"], kw["num_experts_per_tok"], kw["head_dim"],
            kw["sliding_window"], kw["max_seq_len"],
            len(kw["layer_types"])) == (64, 8, 128, 1024, 4608, 8)
    assert (kw["rope_theta"], kw["yarn_factor"], kw["yarn_beta_fast"],
            kw["yarn_original_max_position_embeddings"],
            kw["yarn_attention_factor"]) == (
        500000.0, 16.0, 32.0, 8192, 1.2772588722239782)


def test_window_counts_are_floors(m, cfg):
    family = m.family(FAMILY)
    # K and V of a token: 2 x 4 heads x 128 x 2 bytes
    assert family.kv_token_bytes(cfg) == 2048
    assert family.swa_decode_bytes(cfg, 8 * 1024) == 16 * 2 ** 20
    # a row's visible pairs: a triangle up to the window, a band past it
    per_pair = 4 * 32 * 128
    assert family.swa_flash_flops(cfg, 1000, 1) == per_pair * 1000 * 1001 / 2
    band = 1024 * 1025 / 2 + (3072 - 1024) * 1024
    assert family.swa_flash_flops(cfg, 3072, 1) == per_pair * band
    assert family.swa_flash_flops(cfg, 8 * 3072, 8) == 8 * per_pair * band
    # equal rows are the fewest pairs of any split: never over the truth
    exact = lambda n: sum(min(i, 1024) for i in range(1, n + 1))
    assert family.swa_flash_flops(cfg, 2048 + 4096, 2) <= per_pair * (
        exact(2048) + exact(4096))
    assert exact(3072) == band


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.mellum` (the parent): an error from
    `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.mellum"):
        family.model_kwargs(cfg)
    with pytest.raises(RuntimeError, match="ray_tpu.models.mellum"):
        run.context(m, m.cell(CELL), 1, 1.0, False)


@pytest.mark.parametrize("key,value,says", [
    ("tie_word_embeddings", True, "untied head"),
    ("attention_bias", True, "no attention bias"),
    ("mlp_layer_types", ["dense"] * 28, "no dense layer"),
    ("norm_topk_prob", False, "renormalises"),
    ("use_sliding_window", False, "sliding window"),
    ("rope_parameters", {
        "full_attention": {"rope_type": "default", "rope_theta": 500000},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
     "YaRN"),
])
def test_family_file_refuses_a_config_the_model_is_not(m, cfg, key, value,
                                                       says):
    family = m.family(FAMILY)
    with pytest.raises(ValueError, match=says):
        family.model_kwargs(dict(cfg, **{key: value}))


# -- the readers on a hand-made trace ----------------------------------------
def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _trace(n=3, decode_us=40.0, flash_us=1200.0, context=26_000,
           window=8192, tokens=3072, nb=1, steps=8):
    """`n` prefill dispatches of `nb` prompts (`tokens` prompt tokens
    together) with two `swa_flash` calls each, and `2 n` decode windows of
    `steps` token steps with two `swa_decode` calls each, whose rows hold
    `context` tokens of which `window` lie inside their windows. A fusion
    that borrows a kernel's name does not count, nor the unwindowed
    kernels."""
    host, ops = [_ev("bench.window", 0, 1e6)], []
    for i in range(n):
        t = 100_000 * i
        host.append(_ev("ray_tpu.engine.prefill_dispatch", t, 50, bucket=4096,
                        nb=nb, tokens=tokens, cached_tokens=0, head_rows=nb))
        ops += [_ev(f"%swa_flash.{i} = bf16[{nb},32,4096,128]{{3,2,1,0}} "
                    "custom-call(%q, %k, %v)", t + 10, flash_us),
                _ev(f"%swa_flash.{100 + i} = bf16[{nb},32,4096,128]"
                    "{3,2,1,0} custom-call(%q, %k, %v)", t + 3000, flash_us),
                _ev(f"%swa_flash_fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 6000, 900),
                _ev(f"%flash_fwd.{i} = bf16[{nb},32,4096,128]{{3,2,1,0}} "
                    "custom-call(%q, %k, %v)", t + 8000, 2500)]
        for j in range(2):
            at = t + 20_000 + 30_000 * j
            host.append(_ev("ray_tpu.engine.dispatch_decode", at, 30,
                            active=8, max_seqs=8, steps=steps,
                            context_tokens=context, window_tokens=window))
            ops += [_ev(f"%swa_decode.{4 * i + 2 * j} = bf16[8,1,32,128]"
                        "{3,2,1,0} custom-call(%pt, %lens, %q)", at + 100,
                        decode_us),
                    _ev(f"%swa_decode.{4 * i + 2 * j + 1} = bf16[8,1,32,128]"
                        "{3,2,1,0} custom-call(%pt, %lens, %q)", at + 300,
                        decode_us),
                    _ev(f"%paged_decode.{2 * i + j} = bf16[8,1,32,128]"
                        "{3,2,1,0} custom-call(%pt, %lens, %q)", at + 500,
                        90)]
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

    def rewrite(*args, **kw):
        traces[str(path)] = _trace(*args, **kw)
        program_trace._read.cache_clear()

    yield {"traces": [{"path": str(path), "window_s": 1.0, "busy_s": 0.5,
                       "devices": 1, "modules": {}}],
           "config": cfg, "family": m.family(FAMILY),
           "traffic": m.traffic(TRAFFIC),
           "peaks": m.peaks("TPU v5 lite"), "rewrite": rewrite}
    program_trace._read.cache_clear()


def _nothing(read, obs):
    # a program without the kernel or the counters (the parent), a run
    # without a trace, a run without a chip
    assert read(dict(obs, traces=[{"path": "/nonexistent/x.pb"}])) is None
    assert read(dict(obs, traces=[])) is None
    assert read({}) is None and read({"seconds": 1.0}) is None


@pytest.mark.parametrize("metric,value", [
    ("swa_decode_kernel_us", 40.0), ("swa_flash_kernel_ms", 1.2)])
def test_kernel_time_readers(m, obs, metric, value):
    read = m.reader(metric)
    assert read(obs) == pytest.approx(value)
    obs["rewrite"](1)        # two and four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_decode_share_reader_cannot_pass_100(m, obs):
    read = m.reader("swa_decode_hbm_pct")
    least_us = 8192 * 2048 / 819e9 * 1e6        # 20.5 us at the peak
    assert read(obs) == pytest.approx(100 * least_us / 40.0)
    # a call at the bound reads 100, and none reads more
    obs["rewrite"](3, decode_us=least_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # the spans weigh by their token steps: a window of no steps counts none
    obs["rewrite"](3, steps=0)
    assert read(obs) is None
    obs["rewrite"](3)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("sdar_moe"))) is None
    obs["rewrite"](1)        # two spans, four calls
    assert read(obs) is None
    _nothing(read, obs)


def test_flash_share_reader_cannot_pass_100(m, obs):
    read = m.reader("swa_flash_mxu_pct")
    family = m.family(FAMILY)
    least_us = family.swa_flash_flops(obs["config"], 3072, 1) / 197e12 * 1e6
    assert least_us == pytest.approx(218.1, abs=0.1)
    assert read(obs) == pytest.approx(100 * least_us / 1200.0)
    obs["rewrite"](3, flash_us=least_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # a wave of 8 prompts counts 8 rows' pairs
    obs["rewrite"](3, tokens=8 * 3072, nb=8)
    assert read(obs) == pytest.approx(100 * 8 * least_us / 1200.0)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("granite_hybrid"))) is None
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_window_share_of_the_kv_read_reader(m, obs):
    read = m.reader("kv_read_window_pct")
    assert read(obs) == pytest.approx(
        100 * (6 * 8192 + 2 * 26_000) / (8 * 26_000))
    # every context inside one window: what full layers would read
    obs["rewrite"](3, context=6000, window=6000)
    assert read(obs) == pytest.approx(100.0)
    obs["rewrite"](2)        # four spans: nothing to average
    assert read(obs) is None
    obs["rewrite"](3)
    assert read(dict(obs, family=m.family("llama"))) is None
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_MELLUM = {
    "family": FAMILY,
    "source": "MellumConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 64, "moe_intermediate_size": 32,
    "intermediate_size": 128, "num_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 8, "use_sliding_window": True, "max_window_layers": 0,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "published": {"hidden_size": 64, "num_hidden_layers": 8,
                  "num_experts": 16},
    "reduced": {"num_hidden_layers": "4 of 8"},
    "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.05 at the rehearsal's seed
    "check": {"logprob_tol": 0.25},
}
TINY_TRAFFIC = {
    "kind": "serve_closed", "clients": 3, "rounds": 4,
    "prompt_len": {"dist": "uniform", "min": 70, "max": 120},
    "output_len": {"dist": "uniform", "min": 20, "max": 40},
    "engine_config": {"max_seqs": 4, "page_size": 8, "max_pages_per_seq": 24,
                      "prefill_buckets": [128]},
    "max_ongoing_requests": 16, "drain_s": 60.0}


def test_bench_check_reads_the_familys_reference(tmp_path, monkeypatch):
    """`BenchServer` builds the family from `llm_config["family"]` and
    `bench_check` compares its engine (a prefill over the call's own keys
    that fills pages and rings, then decode steps over both) with
    `references/mellum.py` on the same bf16 weights: the 100-token prompt is
    twelve of the tiny model's windows, so here the check reaches the band
    that at the published window it cannot."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-mellum.json"), "w") as f:
        json.dump(TINY_MELLUM, f)
    with open(os.path.join(root, "benchmark", "workloads",
                           "tiny-context.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-mellum", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-mellum.json",
        "reduced": ["num_hidden_layers"]})
    add_cell(data, "mellum-closed", "tiny-mellum", "tiny-context",
             "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("mellum-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    assert config["model_config"]["sliding_window"] == 8
    assert len(config["model_config"]["layer_types"]) == 4
    server = BenchServer(config)
    try:
        model = server.server.model
        assert type(model).__name__ == "MellumModel"
        assert model.ring_layer_ids == (0, 1, 2)
        assert server.server.engine.prefix_cache is None
        out = server.bench_check(
            serve_driver.check_prompt(512, seed), serve_driver.CHECK_STEPS)
        cache = server.stats()["cache"]
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    assert (cache["kv_layers"], cache["ring_layers"]) == (1, 3)
    # two rings' worth of pages a slot: 2 x 3 layers x (k, v) x 4 slots
    assert cache["ring_bytes"] == 3 * 2 * 4 * 2 * 8 * 128 * 2
