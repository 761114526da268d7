"""The `sarvam_mla` family in the benchmark: its configuration against the
published config and the rule (depth, the experts held and the vocabulary are
reduced, no width is), its parameter, byte and operation counts, the new
traffic file's numbers, its five readers on a hand-made trace, and the
harness's own reference check at a tiny size on the CPU. The cell's whole
programs are compiled for a described v5e in tests/test_tpu_compile.py (one
file holds every such compile: only one process may load the TPU's library).
The manifest's lists are asked whether they hold the cell, never where or
with what else."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver

CONFIG, CELL, FAMILY, TRAFFIC = ("sarvam-105b-serve", "sarvam-long-decode",
                                 "sarvam_mla", "long-decode")
# The lists every serving cell is in, which this cell joined.
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"mla_decode_kernel_us": ("us", "lower", "tpot_p95_ms"),
       "mla_decode_roofline_pct": ("%", "higher", "tpot_p95_ms"),
       "mla_kernels_pct": ("%", "lower", "out_tok_per_s"),
       "mla_flash_kernel_ms": ("ms", "lower", "out_tok_per_s"),
       "mla_flash_mxu_pct": ("%", "higher", "out_tok_per_s")}


@pytest.fixture(scope="module")
def m():
    return mf.Manifest(REPO)


@pytest.fixture(scope="module")
def cfg(m):
    return m.config(CONFIG)


# -- the manifest's entries --------------------------------------------------
def test_manifest_is_clean_and_holds_the_cell_where_it_reports(m):
    assert mf.check(m) == []
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 0 < len(cell["why"]) <= 200
    entry = m.configs[CONFIG]
    assert 0 < len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_experts",
                                     "vocab_size"}
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "tpot_p95_ms", "out_tok_per_s", "setup_s"}
    for name in ("tpot_p95_ms", "out_tok_per_s"):
        assert CELL in m.end_to_end[name]["workloads"]
    for name in SHARED + tuple(NEW):
        assert CELL in m.per_layer[name]["workloads"], name
    assert {x["name"] for x in m.metrics_for(CELL, "per_layer")} == set(
        SHARED) | set(NEW)
    # one use of the pair, and a cell of one chip
    assert [w["name"] for w in m.data["workloads"]
            if (w["config"], w["traffic"]) == (CONFIG, TRAFFIC)] == [CELL]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["better"], entry["moves"]) == NEW[metric]
    assert entry["source"] == "device_trace"
    assert entry["layer"] == "kernels" == m.per_layer[
        "paged_decode_kernel_us"]["layer"]
    assert CELL in entry["workloads"]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert callable(m.reader(metric))


def test_traffic_file_holds_the_issues_numbers(m):
    from benchmark import loadgen
    from ray_tpu.llm._internal.engine import EngineConfig

    traffic = m.traffic(TRAFFIC)
    assert (traffic["kind"], traffic["clients"], traffic["rounds"]) == (
        "serve_closed", 16, 8)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 2048,
                                     "max": 4096}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512,
                                     "max": 1024}
    assert traffic["engine_config"] == {
        "max_seqs": 16, "page_size": 64, "max_pages_per_seq": 80,
        "prefill_buckets": [4096]}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert (traffic["max_ongoing_requests"], traffic["drain_s"]) == (64, 90.0)
    assert "arrivals" not in traffic and "prefix" not in traffic
    # every prompt in the 4,096 bucket; with its answer and the window a
    # decode program may overshoot by, inside the slot's 5,120 positions; the
    # ids drawn from this chip's slice of the vocabulary
    ec = EngineConfig(**traffic["engine_config"])
    assert loadgen.buckets_used(traffic, list(ec.prefill_buckets)) == [4096]
    reqs = loadgen.requests(traffic, 65536, 2 ** 31 + 5, 40.0)
    assert len(reqs) == 16 * 8
    assert all(2048 <= len(r.prompt) <= 4096 and 512 <= r.max_tokens <= 1024
               and max(r.prompt) < 65536 for r in reqs)
    assert max(len(r.prompt) + r.max_tokens + ec.decode_steps - 1
               for r in reqs) <= 80 * 64 == 5120
    assert serve_driver.warm_spec(traffic)["prompt_lens"] == {"4096": 4086}
    assert serve_driver.warm_spec(traffic)["max_nb"] == 16


# -- the configuration against its source ------------------------------------
def test_configuration_cuts_depth_experts_held_and_vocabulary_and_no_width(
        m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    assert "5,461,041,920" in cfg["reduced"]["num_hidden_layers"]
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert [(cfg[k], cfg["published"][k]) for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            (6, 32), (32, 128), (65536, 262144)]
    # the guide's floors: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts"] >= 8
    assert 8 * cfg["vocab_size"] >= cfg["published"]["vocab_size"]
    for said in ("four chips of one v5e host", "expert parallelism",
                 "experts 0-31", "ids 0-65,535", "6, 6, 5, 5, 5, 5",
                 "a quarter", "final norm", "idle share"):
        assert said in cfg["deployment"], said
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_head_dim"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["first_k_dense_replace"],
            cfg["routed_scaling_factor"]) == (
        4096, 64, 192, 128, 64, 128, 512, 576, 16384, 2048, 8, 1, 1, 2.5)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
    for key in ("scoring_func", "norm_topk_prob", "n_group", "use_qk_norm",
                "rotary_pairing", "mtp_head", "weights", "init", "head"):
        assert cfg["assumed"][key], key
    assert cfg["run"]["max_seq_len"] == 5120
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]
    memory = cfg["memory_analysis"]
    assert 0.25 * 15.75 < memory["decode"]["peak_gib"] < memory[
        "prefill_4096x1"]["peak_gib"] < memory["prefill_4096x16"][
            "peak_gib"] < 15.0


def test_catalog_row_is_the_published_block(m, cfg):
    """Where the catalog of public architectures is installed, every key of
    its row's `config` stands in the file under the same key, as published,
    but for those the manifest lists as reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "sarvam-105b")
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
    config["kv_lora_rank"] = 256
    with open(os.path.join(root, "benchmark", "configs", "cut.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "cut", "source": "tests", "why": "tests",
                            "file": "benchmark/configs/cut.json",
                            "reduced": ["num_hidden_layers", "num_experts",
                                        "vocab_size"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    bad = mf.published_problems(mf.Manifest(root), "cut")
    assert any("kv_lora_rank is 256" in b for b in bad)


# -- the family's counts -------------------------------------------------------
def test_parameter_count_is_the_issues_table(m, cfg):
    family = m.family(FAMILY)
    attention = (4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256
                 + 64 * 128 * 4096 + 512 + 192)
    assert family.attention_params(cfg) == attention == 94_634_688
    expert = 3 * 4096 * 2048
    assert family.expert_params(cfg) == expert == 25_165_824
    assert 32 * expert == 805_306_368 and 128 * expert == 3_221_225_472
    dense = attention + 3 * 4096 * 16384 + 8192
    assert family.dense_layer_params(cfg) == dense == 295_969_472
    layer = attention + 4096 * 128 + 128 + 33 * expert + 8192
    assert family.expert_layer_params(cfg) == layer == 925_639_488
    assert family.expert_layer_params(cfg, held=128) == 3_341_558_592
    vocabulary = 2 * 65536 * 4096 + 4096
    assert vocabulary == 536_875_008
    assert family.parameters(cfg) == dense + 5 * layer + vocabulary \
        == 5_461_041_920
    assert 2 * family.parameters(cfg) / 2 ** 30 == pytest.approx(10.172,
                                                                 abs=1e-3)
    whole = family.parameters(dict(cfg, num_hidden_layers=32, num_experts=128,
                                   vocab_size=262144))
    assert whole == dense + 31 * 3_341_558_592 + 2 * 262144 * 4096 + 4096
    assert whole == pytest.approx(106.03e9, rel=1e-4)
    # the depth rule's other side: four expert layers
    assert 2 * family.parameters(dict(cfg, num_hidden_layers=5)) / 1e9 \
        == pytest.approx(9.07, abs=0.01)
    # what multiplies a token here: a quarter of its 8 experts on average
    active = attention - 704 + 4096 * 128 + (2 + 1) * expert
    assert family.matmul_params(cfg) == (
        attention - 704 + 3 * 4096 * 16384 + 5 * active + 65536 * 4096)
    pair = 2 * 64 * (192 + 128)
    assert family.attention_flops_per_token(cfg, 4096) == pair * 6 * 2048
    kw = family.model_kwargs(cfg)
    assert (kw["num_experts"], kw["experts_held"], kw["num_experts_per_tok"],
            kw["num_layers"], kw["vocab_size"], kw["max_seq_len"]) == (
        128, [0, 32], 8, 6, 65536, 5120)
    assert (kw["rope_theta"], kw["yarn_factor"], kw["yarn_beta_fast"],
            kw["yarn_original_max_position_embeddings"], kw["yarn_mscale"],
            kw["yarn_mscale_all_dim"], kw["routed_scaling_factor"]) == (
        10000.0, 40.0, 32.0, 4096, 1.0, 1.0, 2.5)
    model = family.model(kw)
    assert model.cfg.latent_width == 576 and model.cfg.q_head_dim == 192


def test_kernel_counts_are_floors(m, cfg):
    family = m.family(FAMILY)
    # a token's row: 576 values, 1,152 bytes, 1,280 as the device holds it;
    # the keys and values it stands for are 35.6 times that
    assert family.latent_token_bytes(cfg, laid_out=False) == 1152
    assert family.latent_token_bytes(cfg) == 1280
    assert 64 * (192 + 128) * 2 / 1152 == pytest.approx(35.6, abs=0.1)
    # 16 slots of 5,120 positions, six layers
    assert 6 * 16 * 5120 * 1152 / 2 ** 30 == pytest.approx(0.527, abs=1e-3)
    assert 6 * 16 * 5120 * 1280 / 2 ** 30 == pytest.approx(0.586, abs=1e-3)
    assert family.mla_decode_bytes(cfg, 60_000) == 60_000 * 1280
    assert family.mla_decode_flops(cfg, 60_000) == 64 * 60_000 * 1088 * 2
    # operations a byte: under the chip's 240, so both floors are reckoned
    assert family.mla_decode_flops(cfg, 1) / 1152 == pytest.approx(
        120.9, abs=0.1)
    per_pair = 2 * 64 * (192 + 128)
    assert family.mla_flash_flops(cfg, 3000, 1) == per_pair * 3000 * 3001 / 2
    assert family.mla_flash_flops(cfg, 16 * 3000, 16) == \
        family.mla_flash_flops(cfg, 3000, 1)       # a call is one row
    # equal rows are the fewest pairs of any split: never over the truth
    exact = lambda n: n * (n + 1) / 2
    assert 2 * family.mla_flash_flops(cfg, 2048 + 4096, 2) <= per_pair * (
        exact(2048) + exact(4096))
    # a prompt of 4,096 through six layers: 2.1 of its TFLOP are attention's
    assert 6 * family.mla_flash_flops(cfg, 4096, 1) / 1e12 == pytest.approx(
        2.06, abs=0.01)


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.sarvam_mla` (the parent): an error from
    `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.sarvam_mla"):
        family.model_kwargs(cfg)
    with pytest.raises(RuntimeError, match="ray_tpu.models.sarvam_mla"):
        run.context(m, m.cell(CELL), 1, 1.0, False)


@pytest.mark.parametrize("key,value,says", [
    ("tie_word_embeddings", True, "untied head"),
    ("hidden_act", "gelu", "SiLU"),
    ("use_qk_norm", False, "use_qk_norm"),
    ("q_lora_rank", 1536, "no compressed query"),
    ("q_head_dim", 128, "nope \\+ rope"),
    ("moe_router_enable_expert_bias", False, "bias on the choice"),
    ("rope_scaling", {"type": "linear", "factor": 40}, "deepseek_yarn"),
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


def _trace(n=3, decode_us=150.0, flash_us=6000.0, context=60_000,
           tokens=3072, nb=1, steps=8):
    """`n` prefill dispatches of `nb` prompts (`tokens` prompt tokens
    together) with two `mla_flash` calls a row, and `2 n` decode windows of
    `steps` token steps with two `mla_decode` calls each, whose rows hold
    `context` tokens. A fusion that borrows a kernel's name does not count,
    nor the other families' kernels."""
    host, ops = [_ev("bench.window", 0, 1e6)], []
    for i in range(n):
        t = 100_000 * i
        host.append(_ev("ray_tpu.engine.prefill_dispatch", t, 50, bucket=4096,
                        nb=nb, tokens=tokens, cached_tokens=0, head_rows=nb))
        for j in range(2 * nb):
            ops.append(_ev(f"%mla_flash.{i}{j} = bf16[1,64,4096,128]"
                           "{3,2,1,0} custom-call(%q, %k, %v)",
                           t + 10 + 7000 * j, flash_us))
        ops += [_ev(f"%mla_flash_fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 10, 900),
                _ev(f"%flash_fwd.{i} = bf16[1,32,4096,128]{{3,2,1,0}} "
                    "custom-call(%q, %k, %v)", t + 10, 2500)]
        for j in range(2):
            at = t + 20_000 + 30_000 * j
            host.append(_ev("ray_tpu.engine.dispatch_decode", at, 30,
                            active=16, max_seqs=16, steps=steps,
                            context_tokens=context))
            ops += [_ev(f"%mla_decode.{4 * i + 2 * j} = bf16[16,64,512]"
                        "{2,1,0} custom-call(%pt, %lens, %q)", at + 100,
                        decode_us),
                    _ev(f"%mla_decode.{4 * i + 2 * j + 1} = bf16[16,64,512]"
                        "{2,1,0} custom-call(%pt, %lens, %q)", at + 300,
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
    ("mla_decode_kernel_us", 150.0), ("mla_flash_kernel_ms", 6.0)])
def test_kernel_time_readers(m, obs, metric, value):
    read = m.reader(metric)
    assert read(obs) == pytest.approx(value)
    obs["rewrite"](1)        # two and four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_decode_share_reader_takes_the_larger_floor_and_cannot_pass_100(
        m, obs):
    read = m.reader("mla_decode_roofline_pct")
    bytes_us = 60_000 * 1280 / 819e9 * 1e6           # 93.8 us at the peak
    flops_us = 64 * 60_000 * 1088 * 2 / 197e12 * 1e6  # 42.4 us
    assert bytes_us > flops_us
    assert read(obs) == pytest.approx(100 * bytes_us / 150.0)
    # a call at the bound reads 100, and none reads more
    obs["rewrite"](3, decode_us=bytes_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # where the operations bound it (a chip of a quarter the matmul rate),
    # they are the floor
    slow = dict(obs["peaks"], bf16_flops_per_s=197e12 / 4)
    obs["rewrite"](3)
    assert read(dict(obs, peaks=slow)) == pytest.approx(
        100 * 4 * flops_us / 150.0)
    # the spans weigh by their token steps: a window of no steps counts none
    obs["rewrite"](3, steps=0)
    assert read(obs) is None
    obs["rewrite"](3)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("mellum"))) is None
    obs["rewrite"](1)        # two spans, four calls
    assert read(obs) is None
    _nothing(read, obs)


def test_flash_share_reader_cannot_pass_100(m, obs):
    read = m.reader("mla_flash_mxu_pct")
    family = m.family(FAMILY)
    least_us = family.mla_flash_flops(obs["config"], 3072, 1) / 197e12 * 1e6
    assert least_us == pytest.approx(981.4, abs=0.1)
    assert read(obs) == pytest.approx(100 * least_us / 6000.0)
    obs["rewrite"](3, flash_us=least_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # a wave of 16 prompts is 16 rows' calls, each of one row's pairs
    obs["rewrite"](3, tokens=16 * 3072, nb=16)
    assert read(obs) == pytest.approx(100 * least_us / 6000.0)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("granite_hybrid"))) is None
    obs["rewrite"](2)        # four calls: nothing to average
    assert read(obs) is None
    _nothing(read, obs)


def test_share_of_the_busy_time_reader(m, obs):
    read = m.reader("mla_kernels_pct")
    # 6 calls of 6 ms and 12 of 150 us in half a second of busy time
    assert read(obs) == pytest.approx(100 * (6 * 6e-3 + 12 * 150e-6) / 0.5)
    obs["rewrite"](1, nb=0)  # four calls in all
    assert read(obs) is None
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_SARVAM = {
    "family": FAMILY,
    "source": "SarvamMlaConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_attention_heads": 4, "q_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "head_dim": 40, "hidden_act": "silu",
    "use_qk_norm": True, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "rope_theta": 10000, "default_theta": 10000,
    "rope_scaling": {"type": "deepseek_yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16},
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "published": {"hidden_size": 64, "num_hidden_layers": 8,
                  "num_experts": 16},
    "deployment": "two chips share each layer's 16 experts (tests only)",
    "reduced": {"num_hidden_layers": "3 of 8", "num_experts": "8 of 16"},
    "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.03-0.08 at the rehearsal's
    # seeds
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
    `bench_check` compares its engine (a prefill in the published form over
    the call's own keys that fills the latent pool, then decode steps in the
    absorbed form over it, half the router's experts held) with
    `references/sarvam_mla.py` given the same share, on the same bf16
    weights."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-sarvam.json"), "w") as f:
        json.dump(TINY_SARVAM, f)
    with open(os.path.join(root, "benchmark", "workloads",
                           "tiny-context.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-sarvam", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-sarvam.json",
        "reduced": ["num_hidden_layers", "num_experts"]})
    add_cell(data, "sarvam-closed", "tiny-sarvam", "tiny-context",
             "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("sarvam-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    assert config["model_config"]["num_experts"] == 16
    assert config["model_config"]["experts_held"] == [0, 8]
    server = BenchServer(config)
    try:
        model = server.server.model
        assert type(model).__name__ == "SarvamMlaModel"
        assert model.latent_layer_ids == (0, 1, 2)
        assert server.server.engine.prefix_cache is None
        out = server.bench_check(
            serve_driver.check_prompt(512, seed), serve_driver.CHECK_STEPS)
        cache = server.stats()["cache"]
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    assert (cache["kv_layers"], cache["latent_layers"]) == (0, 3)
    # one pool a layer: (4 x 24 + 1) pages of 8 rows of 40 values on 128
    # lanes of bf16
    assert cache["latent_bytes"] == 3 * 97 * 8 * 128 * 2
