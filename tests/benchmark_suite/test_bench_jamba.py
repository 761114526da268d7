"""The `jamba` family in the benchmark: its configuration against the
published config and the rule (nothing reduced), its parameter and byte
counts, its four readers on a hand-made trace, and the harness's own
reference check at a tiny size on the CPU. The cell's whole programs are
compiled for a described v5e in tests/test_tpu_compile.py (one file holds
every such compile: only one process may load the TPU's library)."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, TINY_TRAFFIC, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver

CONFIG, CELL, FAMILY = "jamba2-3b-serve", "jamba-prompt-heavy", "jamba"
# The ten per-layer lists two accepted tests pin to the cells they had.
PINNED = ("queue_wait_mean_ms", "prefill_mean_ms", "admit_batch_mean",
          "admit_stall_mean_ms", "decode_rows_active_pct",
          "paged_decode_kernel_us", "flash_fwd_kernel_ms",
          "flash_bwd_kernel_ms", "stream_lag_mean_ms",
          "stream_tokens_per_item")
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"prefill_dev_ms": ("ms", "device_trace", "jitted prefill and decode"),
       "ssm_scan_kernel_ms": ("ms", "device_trace", "kernels"),
       "ssm_scan_hbm_pct": ("%", "device_trace", "kernels"),
       "ssm_kernels_pct": ("%", "device_trace", "kernels")}


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
        CONFIG, "prompt-heavy", 1)
    assert len(cell["why"]) <= 200
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "tpot_p95_ms", "out_tok_per_s", "setup_s"}
    layer = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert layer == set(SHARED) | set(NEW)
    for name in PINNED:
        assert CELL not in m.per_layer[name]["workloads"]
    # new entries were put after those the benchmark had (not pinned to the
    # end: the next cell goes after these)
    names = lambda group: [x["name"] for x in m.data[group]]
    assert names("workloads").index(CELL) > names("workloads").index(
        "sdar-decode-heavy")
    assert names("configs").index(CONFIG) > names("configs").index(
        "sdar-30b-a3b-serve")
    at = names("per_layer").index("prefill_dev_ms")
    assert names("per_layer")[at:at + 4] == list(NEW)
    assert at > names("per_layer").index("experts_touched_pct")


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["source"], entry["layer"]) == NEW[metric]
    assert entry["moves"] == "out_tok_per_s"
    assert entry["workloads"] == [CELL]
    assert callable(m.reader(metric))
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {x["layer"] for x in m.data["per_layer"][:29]}


def test_traffic_is_what_the_cell_was_sized_for(m):
    from benchmark import loadgen

    traffic = m.traffic("prompt-heavy")
    assert (traffic["kind"], traffic["clients"], traffic["rounds"]) == (
        "serve_closed", 8, 24)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 1024,
                                     "max": 2048}
    assert traffic["output_len"] == {"dist": "uniform", "min": 64,
                                     "max": 128}
    assert traffic["engine_config"] == {"max_seqs": 8, "page_size": 64,
                                        "max_pages_per_seq": 36}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert (traffic["max_ongoing_requests"], traffic["drain_s"]) == (64, 60.0)
    # every prompt in the 2,048 bucket, and with its answer inside the pages
    assert loadgen.buckets_used(traffic, [32, 128, 512, 2048]) == [2048]
    reqs = loadgen.requests(traffic, 65536, 2 ** 31 + 5, 40.0)
    assert len(reqs) == 8 * 24
    assert all(1024 <= len(r.prompt) <= 2048 and 64 <= r.max_tokens <= 128
               and len(r.prompt) + r.max_tokens <= 36 * 64 for r in reqs)
    assert serve_driver.warm_spec(traffic)["prompt_lens"] == {"2048": 2043}


# -- the configuration against its source ------------------------------------
def test_configuration_is_the_published_one_uncut(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert m.configs[CONFIG]["reduced"] == [] and cfg["reduced"] == {}
    assert cfg["deployment"].startswith("one chip holds the whole model")
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["vocab_size"]) == (
        28, 2560, 8192, 65536)
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["attn_layer_period"], cfg["attn_layer_offset"]) == (
        20, 1, 14, 7)
    for key in ("head_dim", "layer_order", "block", "attention", "mixer",
                "init", "weights", "head"):
        assert cfg["assumed"][key], key
    assert cfg["run"]["max_seq_len"] == 2304
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]
    assert cfg["memory_analysis"]["prefill_2048x8"]["peak_gib"] < 14.75


def test_catalog_row_is_the_published_block(cfg):
    """Where the catalog of public architectures is installed, every key of
    its row's `config` stands in the file under the same key, as published."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value and cfg["published"][key] == value, key
    assert row["layers"] == cfg["num_hidden_layers"]


def test_the_rule_refuses_a_cut_this_file_does_not_state(tmp_path):
    root = tiny_root(tmp_path)
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = 14
    with open(os.path.join(root, "benchmark", "configs", "cut.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "cut", "source": "tests", "why": "tests",
                            "file": "benchmark/configs/cut.json",
                            "reduced": []})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    bad = mf.published_problems(mf.Manifest(root), "cut")
    assert any("num_hidden_layers is 14" in b for b in bad)


# -- the family's counts -------------------------------------------------------
def test_parameter_count_and_scan_bytes(m, cfg):
    family = m.family(FAMILY)
    mixer = family.mixer_params(cfg)
    assert mixer == {"in_proj": 2560 * 10240, "conv1d": 5120 * 4 + 5120,
                     "x_proj": 5120 * 192, "dt_proj": 160 * 5120 + 5120,
                     "A_log": 5120 * 16, "D": 5120, "out_proj": 5120 * 2560,
                     "norms": 192}
    assert sum(mixer.values()) == 41_241_792            # 41.24M
    assert (family.mamba_layers(cfg), family.attention_layers(cfg)) == (26, 2)
    mlp, norms = 3 * 2560 * 8192, 2 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    by_hand = (26 * (41_241_792 + mlp + norms) + 2 * (attention + mlp + norms)
               + 65536 * 2560 + 2560)
    assert family.parameters(cfg) == by_hand == 3_029_337_472     # 3.03B
    # what multiplies: no convolution, A, D, biases or norms; the head is
    # the embedding, counted once
    assert family.matmul_params(cfg) == (
        26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + mlp)
        + 2 * (attention + mlp) + 65536 * 2560)
    assert family.attention_flops_per_token(cfg, 2048) == \
        2 * (2 * 2 * 20 * 128 * 2048) * 0.5
    assert family.state_bytes(cfg, 8) == 8 * 16 * 5120 * 4
    # a position: x, z, out in bf16 and dt in float32 over 5,120 channels,
    # B and C of 16 float32 each
    assert family.ssm_scan_bytes(cfg, 1) == 5120 * 10 + 128 == 51_328
    assert family.ssm_scan_bytes(cfg, 8 * 2048) == 840_957_952
    kw = family.model_kwargs(cfg)
    assert (kw["num_layers"], kw["head_dim"], kw["max_seq_len"]) == (
        28, 128, 2304)


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.jamba` (the parent): an error from
    `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.jamba"):
        family.model_kwargs(cfg)
    with pytest.raises(RuntimeError, match="ray_tpu.models.jamba"):
        run.context(m, m.cell(CELL), 1, 1.0, False)


@pytest.mark.parametrize("key,value,says", [
    ("tie_word_embeddings", False, "tied head"),
    ("num_experts", 16, "dense MLP"),
    ("mamba_conv_bias", False, "convolution bias"),
    ("num_logits_to_keep", None, "one position"),
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


def _trace(n=3, kernel_us=4000.0, tokens=12000):
    """`n` prefill dispatches of 8 prompts (`tokens` prompt tokens together)
    and per dispatch two `ssm_scan` calls of `kernel_us` each; a fusion that
    borrows the kernel's name does not count, nor another family's kernel."""
    host, ops = [_ev("bench.window", 0, 1e6)], []
    for i in range(n):
        t = 100_000 * i
        host.append(_ev("ray_tpu.engine.prefill_dispatch", t, 50, bucket=2048,
                        nb=8, tokens=tokens, cached_tokens=0, rich=0,
                        want_lp=0, new_program=0, state_rows=208,
                        scan_positions=26 * 8 * 2048, head_rows=8))
        ops += [_ev(f"%ssm_scan.{i} = (bf16[8,2048,5120]{{2,1,0}}, f32[8,16,"
                    "5120]{2,1,0}) custom-call(%lens, %x)", t + 10,
                    kernel_us),
                _ev(f"%ssm_scan.{100 + i} = (bf16[8,2048,5120]{{2,1,0}}, "
                    "f32[8,16,5120]{2,1,0}) custom-call(%lens, %x)",
                    t + 5000, kernel_us),
                _ev(f"%ssm_scan_fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 10_000, 900),
                _ev(f"%paged_decode.{i} = bf16[8,20,1,128]{{3,2,1,0}} "
                    "custom-call(%pt, %q)", t + 12_000, 50)]
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

    # three launches of the prefill program, 80 ms each; the device was
    # busy 0.5 s of the slice
    modules = {"jit_prefill(7)": {"seconds": 3 * 0.080, "count": 3},
               "jit_decode(123)": {"seconds": 0.2, "count": 20}}
    yield {"traces": [{"path": str(path), "window_s": 1.0, "busy_s": 0.5,
                       "devices": 1, "modules": modules}],
           "config": cfg, "family": m.family(FAMILY),
           "traffic": m.traffic("prompt-heavy"),
           "peaks": m.peaks("TPU v5 lite"), "rewrite": rewrite}
    program_trace._read.cache_clear()


def _nothing(read, obs):
    # a program without the kernel or the counters (the parent), a run
    # without a trace, a run without a chip
    assert read(dict(obs, traces=[{"path": "/nonexistent/x.pb"}])) is None
    assert read(dict(obs, traces=[])) is None
    assert read({}) is None and read({"seconds": 1.0}) is None


def test_kernel_time_reader(m, obs):
    read = m.reader("ssm_scan_kernel_ms")
    assert read(obs) == pytest.approx(4.0)
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_roofline_share_reader(m, obs):
    read = m.reader("ssm_scan_hbm_pct")
    least_us = 12000 * 51_328 / 819e9 * 1e6      # 752 us at the peak
    assert read(obs) == pytest.approx(100 * least_us / 4000.0)
    # a call at the peak reads 100, and no call can read more
    obs["rewrite"](3, least_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("llama"))) is None
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_kernels_share_reader(m, obs):
    read = m.reader("ssm_kernels_pct")
    # six calls of 4 ms in a slice whose device was busy 0.5 s
    assert read(obs) == pytest.approx(100 * 6 * 0.004 / 0.5)
    obs["rewrite"](2)
    assert read(obs) is None
    obs["rewrite"](3)
    assert read(dict(obs, traces=[dict(obs["traces"][0], busy_s=0.0)])) is None
    _nothing(read, obs)


def test_prefill_time_reader(m, obs):
    read = m.reader("prefill_dev_ms")
    # 80 ms a launch over 8 prompts a dispatch
    assert read(obs) == pytest.approx(10.0)
    assert read(dict(obs, traces=[dict(obs["traces"][0], modules={
        "jit_decode(1)": {"seconds": 1.0, "count": 1}})])) is None
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_JAMBA = {
    "family": FAMILY, "source": "JambaConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "attn_layer_period": 4, "attn_layer_offset": 2,
    "num_attention_heads": 5, "num_key_value_heads": 1, "head_dim": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_experts": 1, "num_experts_per_tok": 1, "num_logits_to_keep": 1,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "sliding_window": None, "tie_word_embeddings": True,
    "published": {"hidden_size": 64, "num_hidden_layers": 4},
    "reduced": {}, "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.05 at the rehearsal's seed
    "check": {"logprob_tol": 0.25},
}


def test_bench_check_reads_the_familys_reference(tmp_path, monkeypatch):
    """`BenchServer` builds the family from `llm_config["family"]`, and
    `bench_check` compares its engine (paged prefill with the scan, the head
    on one position, then the decode path) with `references/jamba.py` on the
    same bf16 weights."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs", "tiny-jamba.json"),
              "w") as f:
        json.dump(TINY_JAMBA, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-jamba", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-jamba.json", "reduced": []})
    add_cell(data, "jamba-closed", "tiny-jamba", "tiny-closed", "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    assert TINY_TRAFFIC["tiny-closed"]["kind"] == "serve_closed"
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("jamba-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    server = BenchServer(config)
    try:
        assert type(server.server.model).__name__ == "JambaModel"
        assert server.server.engine.prefix_cache is None
        out = server.bench_check(
            serve_driver.check_prompt(512, seed), serve_driver.CHECK_STEPS)
        cache = server.stats()["cache"]
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    assert (cache["kv_layers"], cache["state_layers"]) == (1, 3)
