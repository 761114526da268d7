"""The `granite_hybrid` family in the benchmark: its configuration against the
published config and the rule (depth and the experts held are reduced, no
width is), its parameter, byte and operation counts, its four readers on a
hand-made trace, and the harness's own reference check at a tiny size on the
CPU. The cell's whole programs are compiled for a described v5e in
tests/test_tpu_compile.py (one file holds every such compile: only one
process may load the TPU's library)."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, TINY_TRAFFIC, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver

CONFIG, CELL, FAMILY = ("granite-4.0-h-small-serve", "granite-prompt-heavy",
                        "granite_hybrid")
# Lists accepted tests pin to the cells they had: the ten of PERF.md 7.9, and
# those of the Jamba and SDAR cells.
PINNED = ("queue_wait_mean_ms", "prefill_mean_ms", "admit_batch_mean",
          "admit_stall_mean_ms", "decode_rows_active_pct",
          "paged_decode_kernel_us", "flash_fwd_kernel_ms",
          "flash_bwd_kernel_ms", "stream_lag_mean_ms",
          "stream_tokens_per_item", "prefill_dev_ms", "ssm_scan_kernel_ms",
          "ssm_scan_hbm_pct", "ssm_kernels_pct", "moe_gmm_kernel_us",
          "moe_gmm_hbm_pct", "experts_touched_pct",
          "denoise_passes_per_token")
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"ssd_scan_kernel_ms": ("ms", "device_trace", "kernels"),
       "ssd_scan_roofline_pct": ("%", "device_trace", "kernels"),
       "moe_kernels_pct": ("%", "device_trace", "kernels"),
       "expert_rows_held_pct": ("%", "program_counter", "kernels")}


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
    assert len(m.configs[CONFIG]["why"]) <= 200
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "tpot_p95_ms", "out_tok_per_s", "setup_s"}
    layer = {x["name"] for x in m.metrics_for(CELL, "per_layer")}
    assert layer == set(SHARED) | set(NEW)
    for name in PINNED:
        assert CELL not in m.per_layer[name]["workloads"]
    # new entries were put after those the benchmark had: after Jamba's (not
    # pinned to the end: the next cell goes after these)
    names = lambda group: [x["name"] for x in m.data[group]]
    assert names("workloads").index(CELL) > names("workloads").index(
        "jamba-prompt-heavy")
    assert names("configs").index(CONFIG) > names("configs").index(
        "jamba2-3b-serve")
    at = names("per_layer").index("ssd_scan_kernel_ms")
    assert names("per_layer")[at:at + 4] == list(NEW)
    assert at > names("per_layer").index("ssm_kernels_pct")
    for group, shared in (("end_to_end", ("tpot_p95_ms", "out_tok_per_s")),
                          ("per_layer", SHARED)):
        for name in shared:
            cells = getattr(m, group)[name]["workloads"]
            assert cells.index(CELL) > cells.index("jamba-prompt-heavy")


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["source"], entry["layer"]) == NEW[metric]
    assert entry["moves"] == "out_tok_per_s"
    assert entry["workloads"] == [CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert callable(m.reader(metric))
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {x["layer"] for x in m.data["per_layer"][:29]}


def test_traffic_is_jambas_file_unedited(m):
    from benchmark import loadgen

    assert m.cell("jamba-prompt-heavy")["traffic"] == m.cell(CELL)["traffic"]
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
    reqs = loadgen.requests(traffic, 100352, 2 ** 31 + 5, 40.0)
    assert len(reqs) == 8 * 24
    assert all(1024 <= len(r.prompt) <= 2048 and 64 <= r.max_tokens <= 128
               and len(r.prompt) + r.max_tokens <= 36 * 64 for r in reqs)
    assert serve_driver.warm_spec(traffic)["prompt_lens"] == {"2048": 2043}


# -- the configuration against its source ------------------------------------
def test_configuration_cuts_depth_and_experts_held_and_no_width(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert m.configs[CONFIG]["reduced"] == ["num_hidden_layers",
                                            "num_local_experts"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_local_experts"}
    assert "36 of the 72 routed experts" in cfg["deployment"]
    assert "HALF the rows" in cfg["deployment"]
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"],
            cfg["num_local_experts"], cfg["published"]["num_local_experts"]
            ) == (10, 40, 36, 72)
    # one whole period: 9 Mamba-2 layers and the attention layer
    assert len(cfg["layer_types"]) == 40
    run_types = m.family(FAMILY).layer_types(cfg)
    assert run_types == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["layer_types"] == run_types * 4
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["vocab_size"],
            cfg["num_experts_per_tok"]) == (4096, 768, 1536, 100352, 10)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_chunk_size"],
            cfg["mamba_n_groups"]) == (128, 64, 128, 4, 256, 1)
    assert (cfg["embedding_multiplier"], cfg["attention_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"]) == (
        12, 0.0078125, 0.22, 16)
    for key in ("head_dim", "intermediate_size", "block", "attention",
                "mixer", "experts", "init", "weights", "head"):
        assert cfg["assumed"][key], key
    assert cfg["run"]["max_seq_len"] == 2304
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]
    assert cfg["memory_analysis"]["prefill_2048x8"]["peak_gib"] < 14.75
    assert cfg["memory_analysis"]["decode"]["peak_gib"] > 0.25 * 15.75


def test_catalog_row_is_the_published_block(m, cfg):
    """Where the catalog of public architectures is installed, every key of
    its row's `config` stands in the file under the same key, as published,
    but for the two the manifest lists as reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
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
    config["shared_intermediate_size"] = 768
    with open(os.path.join(root, "benchmark", "configs", "cut.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "cut", "source": "tests", "why": "tests",
                            "file": "benchmark/configs/cut.json",
                            "reduced": ["num_hidden_layers",
                                        "num_local_experts"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    bad = mf.published_problems(mf.Manifest(root), "cut")
    assert any("shared_intermediate_size is 768" in b for b in bad)


# -- the family's counts -------------------------------------------------------
def test_parameter_count_and_scan_counts(m, cfg):
    family = m.family(FAMILY)
    mixer = family.mixer_params(cfg)
    assert mixer == {"in_proj": 4096 * (8192 + 8448 + 128),
                     "conv1d": 8448 * 4 + 8448, "A_log": 128, "D": 128,
                     "dt_bias": 128, "norm": 8192, "out_proj": 8192 * 4096}
    assert sum(mixer.values()) == 102_286_976           # 102.3M
    assert (family.mamba_layers(cfg), family.attention_layers(cfg)) == (9, 1)
    expert = 4096 * 1536 + 768 * 4096
    assert family.expert_params(cfg) == expert == 9_437_184
    shared = 4096 * 3072 + 1536 * 4096
    assert family.shared_params(cfg) == shared == 18_874_368
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    rest = 4096 * 72 + 36 * expert + shared + 2 * 4096
    by_hand = (9 * (102_286_976 + rest) + (attention + rest)
               + 100352 * 4096 + 4096)
    assert family.parameters(cfg) == by_hand == 4_962_732_672    # 4.96B
    assert 2 * by_hand / 2 ** 30 == pytest.approx(9.244, abs=1e-3)   # GiB
    # what multiplies on this chip: of a token's ten experts the five that
    # a router without favourites sends here
    ffn = 4096 * 72 + 5 * expert + shared
    assert family.matmul_params(cfg) == (
        9 * (4096 * 16768 + 8192 * 4096 + ffn) + (attention + ffn)
        + 100352 * 4096)
    assert family.attention_flops_per_token(cfg, 2048) == \
        1 * (2 * 2 * 32 * 128 * 2048) * 0.5
    # a slot's state: [128, 64, 128] float32 = 4 MiB a layer
    assert family.state_bytes(cfg, 1) == 4 * 2 ** 20
    # a position: x and y in bf16 over 8,192 channels, B and C of 128 bf16,
    # dt and the running sum in float32 a head
    assert family.ssd_scan_bytes(cfg, 1) == 8192 * 4 + 512 + 1024 == 34_304
    assert family.ssd_scan_bytes(cfg, 8 * 2048) == 562_036_736
    # the causal half of a chunk's square, the state in and out
    assert family.ssd_scan_flops(cfg, 1) == int(2 * (
        128.5 * 128 + 128.5 * 8192 + 2 * 128 * 8192))
    assert family.ssd_scan_flops(cfg, 8 * 2048) == pytest.approx(
        103.75e9, rel=1e-3)
    kw = family.model_kwargs(cfg)
    assert (kw["num_experts"], kw["experts_held"], kw["head_dim"],
            kw["max_seq_len"], len(kw["layer_types"])) == (
        72, [0, 36], 128, 2304, 10)


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.granite_hybrid` (the parent): an error
    from `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.granite_hybrid"):
        family.model_kwargs(cfg)
    with pytest.raises(RuntimeError, match="ray_tpu.models.granite_hybrid"):
        run.context(m, m.cell(CELL), 1, 1.0, False)


@pytest.mark.parametrize("key,value,says", [
    ("tie_word_embeddings", False, "tied head"),
    ("position_embedding_type", "rope", "without positional encoding"),
    ("mamba_conv_bias", False, "convolution bias"),
    ("mamba_n_groups", 8, "one group"),
    ("mamba_expand", 4, "mamba_expand"),
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


def _trace(n=3, kernel_us=4000.0, tokens=12000, held=(58_000, 390)):
    """`n` prefill dispatches of 8 prompts (`tokens` prompt tokens together)
    with two `ssd_scan` and two `moe_gmm` calls each, and `n` decode windows'
    `emit` spans; `held` = the rows held of 120,000 routed a prefill and of
    800 a window. A fusion that borrows a kernel's name does not count, nor
    another family's kernel."""
    host, ops = [_ev("bench.window", 0, 1e6)], []
    for i in range(n):
        t = 100_000 * i
        host.append(_ev("ray_tpu.engine.prefill_dispatch", t, 50, bucket=2048,
                        nb=8, tokens=tokens, cached_tokens=0, rich=0,
                        want_lp=0, new_program=0, state_rows=72,
                        scan_positions=9 * 8 * 2048, head_rows=8,
                        experts_touched=360, expert_load_max=3000,
                        expert_rows_held=held[0],
                        expert_rows_routed=120_000))
        host.append(_ev("ray_tpu.engine.emit", t + 60_000, 20, tokens=64,
                        finished=0, skipped=0, experts_touched=2500,
                        expert_load_max=60, expert_rows_held=held[1],
                        expert_rows_routed=800))
        ops += [_ev(f"%ssd_scan.{i} = (bf16[8,2048,8192]{{2,1,0}}, f32[8,"
                    "8192,128]{2,1,0}) custom-call(%lens, %x)", t + 10,
                    kernel_us),
                _ev(f"%ssd_scan.{100 + i} = (bf16[8,2048,8192]{{2,1,0}}, "
                    "f32[8,8192,128]{2,1,0}) custom-call(%lens, %x)",
                    t + 5000, kernel_us),
                _ev(f"%ssd_scan_fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 10_000, 900),
                _ev(f"%moe_gmm.{i} = bf16[168448,1536]{{1,0}} "
                    "custom-call(%te, %tu, %lhs, %rhs)", t + 12_000, 2500),
                _ev(f"%moe_gmm.{200 + i} = bf16[168448,4096]{{1,0}} "
                    "custom-call(%te, %tu, %lhs, %rhs)", t + 16_000, 1500),
                _ev(f"%moe_gmm_fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 19_000, 700),
                _ev(f"%paged_decode.{i} = bf16[8,32,1,128]{{3,2,1,0}} "
                    "custom-call(%pt, %q)", t + 20_000, 50)]
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
    read = m.reader("ssd_scan_kernel_ms")
    assert read(obs) == pytest.approx(4.0)
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_roofline_share_reader_cannot_pass_100(m, obs):
    read = m.reader("ssd_scan_roofline_pct")
    family = m.family(FAMILY)
    by_bytes = 12000 * 34_304 / 819e9 * 1e6          # 503 us at the peak
    by_flops = family.ssd_scan_flops(obs["config"], 12000) / 197e12 * 1e6
    assert by_flops == pytest.approx(385.7, abs=0.1) and by_flops < by_bytes
    assert read(obs) == pytest.approx(100 * by_bytes / 4000.0)
    # a call at the larger of the two bounds reads 100, and none reads more
    obs["rewrite"](3, by_bytes)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # where the matmul peak is the nearer bound, that one is read
    assert read(dict(obs, peaks=dict(obs["peaks"], bf16_flops_per_s=98e12))
                ) == pytest.approx(100 * by_flops * 197 / 98 / by_bytes)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("jamba"))) is None
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_expert_kernels_share_reader(m, obs):
    read = m.reader("moe_kernels_pct")
    # three dispatches of 2.5 + 1.5 ms in a slice whose device was busy 0.5 s
    assert read(obs) == pytest.approx(100 * 3 * 0.004 / 0.5)
    obs["rewrite"](2)        # four calls
    assert read(obs) is None
    obs["rewrite"](3)
    assert read(dict(obs, traces=[dict(obs["traces"][0], busy_s=0.0)])) is None
    _nothing(read, obs)


def test_rows_held_share_reader(m, obs):
    read = m.reader("expert_rows_held_pct")
    assert read(obs) == pytest.approx(
        100 * 3 * (58_000 + 390) / (3 * 120_800))
    # a layer that computed every expert as its own reads 100
    obs["rewrite"](3, held=(120_000, 800))
    assert read(obs) == pytest.approx(100.0)
    obs["rewrite"](2)        # four spans: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_GRANITE = {
    "family": FAMILY,
    "source": "GraniteHybridConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 16,
    "shared_intermediate_size": 32, "num_local_experts": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "embedding_multiplier": 12, "attention_multiplier": 0.125,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "position_embedding_type": "nope", "rope_scaling": None,
    "normalization_function": "rmsnorm", "hidden_act": "silu",
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True,
    "published": {"hidden_size": 64, "num_hidden_layers": 4,
                  "num_local_experts": 8},
    "reduced": {"num_hidden_layers": "3 of 4", "num_local_experts": "4 of 8"},
    "deployment": "two chips share each layer: 4 of 8 experts here",
    "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.03 at the rehearsal's seed
    "check": {"logprob_tol": 0.25},
}


def test_bench_check_reads_the_familys_reference(tmp_path, monkeypatch):
    """`BenchServer` builds the family from `llm_config["family"]`, holding
    4 of the router's 8 experts, and `bench_check` compares its engine (paged
    prefill with the scan, the head on one position, then the decode path)
    with `references/granite_hybrid.py` on the same bf16 weights and the same
    share."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-granite.json"), "w") as f:
        json.dump(TINY_GRANITE, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-granite", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-granite.json",
        "reduced": ["num_hidden_layers", "num_local_experts"]})
    add_cell(data, "granite-closed", "tiny-granite", "tiny-closed",
             "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    assert TINY_TRAFFIC["tiny-closed"]["kind"] == "serve_closed"
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("granite-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    assert config["model_config"]["experts_held"] == [0, 4]
    assert config["model_config"]["num_experts"] == 8
    server = BenchServer(config)
    try:
        model = server.server.model
        assert type(model).__name__ == "GraniteHybridModel"
        assert model.cfg.layer_types == ("mamba", "attention", "mamba")
        assert server.server.params["layers_0"]["block_sparse_moe"][
            "gate_up"].shape[0] == 4
        assert server.server.engine.prefix_cache is None
        out = server.bench_check(
            serve_driver.check_prompt(512, seed), serve_driver.CHECK_STEPS)
        cache = server.stats()["cache"]
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    assert (cache["kv_layers"], cache["state_layers"]) == (1, 2)
