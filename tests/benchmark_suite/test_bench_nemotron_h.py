"""The `nemotron_h` family in the benchmark: its configuration against the
published config and the rule (depth and the experts held are reduced, no
width is), its parameter and byte counts against the issue's table, the
traffic file it shares unedited, its four readers on a hand-made trace, and
the harness's own reference check at a tiny size on the CPU. The cell's
whole programs are compiled for a described v5e in tests/test_tpu_compile.py
(one file holds every such compile: only one process may load the TPU's
library). The manifest's lists are asked whether they hold the cell, never
where or with what else."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver

CONFIG, CELL, FAMILY, TRAFFIC = ("nemotron-3-super-120b-a12b-serve",
                                 "nemotron-decode-heavy", "nemotron_h",
                                 "decode-heavy")
# The lists every serving cell is in, which this cell joined.
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
# metric -> (unit, source, layer)
NEW = {"latent_moe_kernel_us": ("us", "device_trace", "kernels"),
       "latent_moe_hbm_pct": ("%", "device_trace", "kernels"),
       "latent_experts_touched_pct": ("%", "program_counter", "kernels"),
       "decode_step_hbm_pct": ("%", "device_trace",
                               "jitted prefill and decode")}
PEAK = 819e9


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
    assert entry["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert set(entry["reduced"]) == {"num_hidden_layers", "n_routed_experts"}
    assert {x["name"] for x in m.metrics_for(CELL, "end_to_end")} == {
        "tpot_p95_ms", "out_tok_per_s", "setup_s"}
    for name in ("tpot_p95_ms", "out_tok_per_s"):
        assert CELL in m.end_to_end[name]["workloads"]
    for name in SHARED + tuple(NEW):
        assert CELL in m.per_layer[name]["workloads"], name
    assert {x["name"] for x in m.metrics_for(CELL, "per_layer")} == set(
        SHARED) | set(NEW)
    # one use of the pair, a cell of one chip, and no second cell of the
    # configuration
    assert [w["name"] for w in m.data["workloads"]
            if w["config"] == CONFIG] == [CELL]
    # the three other cells on this traffic differ from it by the model alone
    assert sum(w["traffic"] == TRAFFIC for w in m.data["workloads"]) == 4


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_has_its_entry_and_reader(m, metric):
    entry = m.per_layer[metric]
    assert (entry["unit"], entry["source"], entry["layer"]) == NEW[metric]
    assert entry["moves"] == "tpot_p95_ms"
    assert entry["better"] == ("lower" if metric.endswith("_us")
                               else "higher")
    # a layer the manifest already names, letter for letter
    assert entry["layer"] in {m.per_layer["paged_decode_kernel_us"]["layer"],
                              m.per_layer["decode_dev_ms"]["layer"]}
    assert CELL in entry["workloads"]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert callable(m.reader(metric))


def test_traffic_file_is_the_one_three_cells_already_run(m):
    from benchmark import loadgen
    from ray_tpu.llm._internal.engine import EngineConfig

    traffic = m.traffic(TRAFFIC)
    assert (traffic["kind"], traffic["clients"], traffic["rounds"]) == (
        "serve_closed", 16, 8)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 40, "max": 128}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512,
                                     "max": 1024}
    assert traffic["engine_config"] == {
        "max_seqs": 16, "page_size": 64, "max_pages_per_seq": 20}
    assert traffic["sampling"] == {"temperature": 0.0}
    ec = EngineConfig(**traffic["engine_config"])
    assert loadgen.buckets_used(traffic, list(ec.prefill_buckets)) == [128]
    reqs = loadgen.requests(traffic, 131072, 2 ** 31 + 5, 40.0)
    assert len(reqs) == 16 * 8
    assert all(40 <= len(r.prompt) <= 128 and 512 <= r.max_tokens <= 1024
               and max(r.prompt) < 131072 for r in reqs)
    assert max(len(r.prompt) + r.max_tokens + ec.decode_steps - 1
               for r in reqs) <= 20 * 64 == 1280
    assert serve_driver.warm_spec(traffic)["max_nb"] == 16


# -- the configuration against its source ------------------------------------
def test_configuration_cuts_depth_and_the_experts_held_and_no_width(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts"}
    assert "5,453,470,080" in cfg["reduced"]["n_routed_experts"]
    assert "MEMEMEM*EME" in cfg["reduced"]["num_hidden_layers"]
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"]
            ) == (11, 88)
    assert (cfg["n_routed_experts"], cfg["published"]["n_routed_experts"]
            ) == (128, 512)
    # every width, the router's top-22, the groups and the whole vocabulary
    assert {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "chunk_size", "expand",
        "moe_intermediate_size", "moe_latent_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "routed_scaling_factor", "vocab_size", "num_logits_to_keep")} == {
        "hidden_size": 4096, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 128,
        "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
        "conv_kernel": 4, "chunk_size": 128, "expand": 2,
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "num_experts_per_tok": 22, "routed_scaling_factor": 5,
        "vocab_size": 131072, "num_logits_to_keep": 1}
    pattern = cfg["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("*"),
            pattern.count("E")) == (88, 40, 8, 40)
    assert "a quarter" in cfg["deployment"] and "512" in cfg["deployment"]
    for key in ("block", "attention", "mixer", "experts", "init", "weights",
                "head", "mtp"):
        assert cfg["assumed"][key], key
    assert 0 < cfg["check"]["logprob_tol"] and cfg["check"]["why"]
    assert cfg["memory_analysis"]["decode"]["peak_gib"] >= 0.6 * 15.75
    assert cfg["run"]["max_seq_len"] == 20 * 64


def test_model_arguments_are_the_published_keys_cut_as_stated(m, cfg):
    family = m.family(FAMILY)
    kw = family.model_kwargs(cfg)
    assert kw["hybrid_override_pattern"] == "MEMEMEM*EME" == family.pattern(
        cfg)
    assert (kw["num_experts"], kw["experts_held"],
            kw["num_experts_per_tok"]) == (512, [0, 128], 22)
    assert (kw["mamba_n_heads"], kw["mamba_d_head"], kw["mamba_d_state"],
            kw["mamba_n_groups"], kw["mamba_chunk_size"]) == (
        128, 64, 128, 8, 128)
    assert (kw["moe_latent_size"], kw["moe_intermediate_size"],
            kw["shared_intermediate_size"]) == (1024, 2688, 5376)
    model = family.model(kw)
    assert model.cacheless_layer_ids == (1, 3, 5, 8, 10)
    assert model.state_layer_ids == (0, 2, 4, 6, 9)
    assert (family.blocks(cfg, "M"), family.blocks(cfg, "*"),
            family.blocks(cfg, "E")) == (5, 1, 5)


# -- the counts against the issue's table ------------------------------------
def test_parameters_are_the_issues_table(m, cfg):
    family = m.family(FAMILY)
    assert sum(family.mixer_params(cfg).values()) + 4096 == 109_640_064
    assert family._attention_proj(cfg) + 4096 == 35_655_680
    assert family.expert_params(cfg) == 1024 * 2688 * 2 == 5_505_024
    assert sum(family.expert_block_rest(cfg).values()) + 4096 == 54_530_560
    assert 2 * 131072 * 4096 + 4096 == 1_073_745_920
    assert family.parameters(cfg) == 5_453_470_080
    assert family.parameters(cfg) * 2 / 2 ** 30 == pytest.approx(10.16,
                                                                 abs=0.005)
    # the reading checked by count: the whole published model is the name's
    # 120B-A12B
    whole = dict(cfg["published"])
    assert family.parameters(whole) == pytest.approx(120.67e9, rel=1e-4)
    active = (family.matmul_params(whole) + 4096 * 131072   # + the embedding
              + 40 * (sum(family.mixer_params(whole).values())
                      - family.mixer_params(whole)["in_proj"]
                      - family.mixer_params(whole)["out_proj"]))
    assert active == pytest.approx(12.77e9, rel=2e-3)
    # a period with every expert, and with half of them, does not fit
    period = dict(cfg, n_routed_experts=512)
    assert family.parameters(period) * 2 / 2 ** 30 == pytest.approx(
        29.8, abs=0.05)
    assert family.parameters(dict(cfg, n_routed_experts=256)) * 2 / 2 ** 30 \
        == pytest.approx(16.7, abs=0.05)


def test_byte_counts_of_the_kernel_and_of_the_step(m, cfg):
    family = m.family(FAMILY)
    # a slot of a Mamba-2 block: 4 MiB of state and 61,440 bytes of tail
    assert family.state_slot_bytes(cfg) == 4 * 2 ** 20 + 61_440
    # one expert is 11 MB of weights; 88 held assignments at 16 rows
    one = family.moe_gmm_bytes(cfg, 16, 1) - family.moe_gmm_bytes(cfg, 16, 0)
    assert one == 2 * 5_505_024
    assert family.moe_gmm_bytes(cfg, 16, 0) == 2 * 88 * 2 * (1024 + 2688)
    # the step at 16 rows and 65 touched experts a block: 7.0 GB, of which
    # the touched experts are 51%, the states 10%
    step = family.decode_step_bytes(cfg, 16, 65)
    assert step == pytest.approx(7.05e9, rel=2e-3)
    experts = 5 * 65 * 2 * 5_505_024
    assert experts / step == pytest.approx(0.51, abs=0.01)
    assert (family.decode_step_bytes(cfg, 16, 65)
            - family.decode_step_bytes(cfg, 16, 0)) == experts
    states = 5 * 16 * 2 * family.state_slot_bytes(cfg)
    assert states / step == pytest.approx(0.10, abs=0.01)
    assert step / PEAK * 1e3 == pytest.approx(8.6, abs=0.05)   # ms
    # cached K/V: 1 KiB a token on the one attention block
    assert (family.decode_step_bytes(cfg, 16, 65, kv_tokens=1000) - step
            ) == 1000 * 1024
    assert family.attention_flops_per_token(cfg, 1000) == 2 * 32 * 128 * 1000


def test_a_program_without_the_family_fails_at_once(m, cfg, monkeypatch):
    """A tree without `ray_tpu.models.nemotron_h` (the parent): an error
    from `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="ray_tpu.models.nemotron_h"):
        family.model_kwargs(cfg)
    with pytest.raises(RuntimeError, match="ray_tpu.models.nemotron_h"):
        run.context(m, m.cell(CELL), 1, 1.0, False)


@pytest.mark.parametrize("key,value,says", [
    ("tie_word_embeddings", True, "untied head"),
    ("mlp_hidden_act", "silu", "relu\\^2 experts"),
    ("use_conv_bias", False, "a convolution bias"),
    ("attention_bias", True, "no bias on any projection"),
    ("n_group", 4, "one group of them"),
    ("norm_topk_prob", False, "renormalised top-k"),
    ("expand", 4, "expand \\* hidden_size"),
    ("intermediate_size", 1344, "one width for the routed experts"),
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


def _trace(n=6, call_us=200.0, touched=64, steps=8, blocks=5, counters=True):
    """`n` decode windows of `steps` token steps with two `moe_gmm` calls an
    expert block (`blocks`) and step, each inside its `jit_decode` launch,
    whose `emit` spans say the `touched` experts of a block and forward
    summed over the window; one prefill's calls outside any decode launch,
    which do not count; nor does a fusion that borrows the kernel's name."""
    host, ops, mods = [_ev("bench.window", 0, 1e7)], [], []
    for i in range(n):
        t = 1_000_000 * i
        mods.append(_ev("jit_decode(123)", t, 900_000))
        host.append(_ev("ray_tpu.engine.dispatch_decode", t, 40, active=16,
                        max_seqs=16, steps=steps, state_rows=16 * 5))
        stats = dict(tokens=16 * steps, finished=0, skipped=0)
        if counters:
            stats.update(experts_touched=touched * blocks * steps,
                         expert_load_max=3 * blocks * steps,
                         expert_rows_held=88 * blocks * steps,
                         expert_rows_routed=352 * blocks * steps,
                         expert_tiles=touched * blocks * steps)
        host.append(_ev("ray_tpu.engine.emit", t + 950_000, 30, **stats))
        for c in range(2 * blocks * steps):
            out = "bf16[2096,2688]" if c % 2 == 0 else "bf16[2096,1024]"
            ops.append(_ev(f"%moe_gmm.{i * 1000 + c} = {out}{{1,0}} "
                           "custom-call(%te, %tu, %rows, %w)",
                           t + 100 + 1000 * c, call_us))
        ops.append(_ev(f"%moe_gmm_fusion.{i} = f32[8]{{0}} fusion(%x)",
                       t + 50, 900))
    mods.append(_ev("jit_prefill(7)", 1_000_000 * n, 50_000))
    ops += [_ev(f"%moe_gmm.{9000 + c} = bf16[61312,2688]{{1,0}} "
                "custom-call(%te, %tu, %rows, %w)",
                1_000_000 * n + 100 + 2000 * c, 1500.0) for c in range(10)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="llm-engine", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=mods)])])


@pytest.fixture
def obs(m, cfg, monkeypatch, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    traces = {str(path): _trace()}
    monkeypatch.setattr(program_trace.xplane, "load", traces.__getitem__)
    program_trace._read.cache_clear()
    made = {"traces": [{
        "path": str(path), "window_s": 10.0, "busy_s": 5.4, "devices": 1,
        "modules": {"jit_decode": {"count": 6, "seconds": 6 * 0.09},
                    "jit_prefill": {"count": 1, "seconds": 0.05}}}],
        "config": cfg, "family": m.family(FAMILY),
        "traffic": m.traffic(TRAFFIC), "peaks": m.peaks("TPU v5 lite"),
        "replicas": [{"decode_steps": 8}]}

    def rewrite(*args, **kw):
        traces[str(path)] = _trace(*args, **kw)
        program_trace._read.cache_clear()

    made["rewrite"] = rewrite
    yield made
    program_trace._read.cache_clear()


def _nothing(read, obs):
    # a program without the kernel or the counters (the parent), a run
    # without a trace, a run without a chip
    assert read(dict(obs, traces=[{"path": "/nonexistent/x.pb"}])) is None
    assert read(dict(obs, traces=[])) is None
    assert read({}) is None and read({"seconds": 1.0}) is None


def test_kernel_time_reader_is_a_call_of_the_decode_program(m, obs):
    read = m.reader("latent_moe_kernel_us")
    assert read(obs) == pytest.approx(200.0)     # not the prefill's 1,500
    # half of what `moe_gmm_kernel_us` reads, a layer's two calls together
    assert m.reader("moe_gmm_kernel_us")(obs) == pytest.approx(400.0)
    obs["rewrite"](n=1, steps=1, blocks=2)       # four calls
    assert read(obs) is None
    _nothing(read, obs)


def test_touched_share_reader_is_over_held_blocks_and_forwards(m, obs):
    read = m.reader("latent_experts_touched_pct")
    assert read(obs) == pytest.approx(100 * 64 / 128)
    # windows of half the steps say half the sum over half the forwards
    obs["rewrite"](touched=32, steps=4)
    assert read(obs) == pytest.approx(100 * 32 / 128)
    obs["rewrite"](touched=128)
    assert read(obs) == pytest.approx(100.0)
    # a program that sows no expert load (the counters absent), a family
    # that does not say its expert blocks
    obs["rewrite"](counters=False)
    assert read(obs) is None
    obs["rewrite"]()
    assert read(dict(obs, family=m.family("granite_hybrid"))) is None
    assert read(dict(obs, config=dict(obs["config"], n_routed_experts=0))
                ) is None
    _nothing(read, obs)


def test_kernel_share_reader_cannot_pass_100(m, obs):
    read = m.reader("latent_moe_hbm_pct")
    family = m.family(FAMILY)
    least_us = family.moe_gmm_bytes(obs["config"], 16, 64) / PEAK * 1e6
    assert least_us == pytest.approx(64 * 11_010_048 / PEAK * 1e6, rel=2e-3)
    # a block's two calls of 200 us against 862 us of weights at the peak:
    # a kernel faster than the peak would read over 100, so the fixture's is
    # slowed to the floor
    obs["rewrite"](call_us=least_us / 2)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    obs["rewrite"](call_us=least_us)
    assert read(obs) == pytest.approx(50.0)
    # fewer experts touched: fewer bytes over the same time
    obs["rewrite"](call_us=least_us, touched=32)
    assert read(obs) == pytest.approx(
        50.0 * family.moe_gmm_bytes(obs["config"], 16, 32)
        / family.moe_gmm_bytes(obs["config"], 16, 64))
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("granite_hybrid"))) is None
    obs["rewrite"](counters=False)
    assert read(obs) is None
    _nothing(read, obs)


def test_step_share_reader_is_the_steps_bytes_over_a_token_steps_time(m, obs):
    read = m.reader("decode_step_hbm_pct")
    family = m.family(FAMILY)
    least_ms = family.decode_step_bytes(obs["config"], 16, 64) / PEAK * 1e3
    assert least_ms == pytest.approx(8.54, abs=0.02)
    # launches of 90 ms over 8 token steps: 11.25 ms a step
    assert m.reader("decode_dev_ms")(obs) == pytest.approx(11.25)
    assert read(obs) == pytest.approx(100 * least_ms / 11.25)
    assert 70 < read(obs) < 80
    # the same launches over windows of 4 steps: 22.5 ms a step, half the
    # share, where `decode_dev_ms` still divides by `decode_steps`
    obs["rewrite"](steps=4)
    assert read(obs) == pytest.approx(100 * least_ms / 22.5)
    # a step at the bound reads 100, and none reads more
    obs["rewrite"]()
    fast = [dict(obs["traces"][0], modules={
        "jit_decode": {"count": 6, "seconds": 6 * 8 * least_ms / 1e3}})]
    assert read(dict(obs, traces=fast)) == pytest.approx(100.0)
    assert read(dict(obs, traces=[dict(obs["traces"][0], modules={})])
                ) is None
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("granite_hybrid"))) is None
    obs["rewrite"](counters=False)
    assert read(obs) is None
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_PATTERN = "ME*EM" + "MEMEM"
TINY_NEMOTRON = {
    "family": FAMILY,
    "source": "NemotronHConfig.tiny's widths (tests only)",
    "attention_bias": False, "chunk_size": 16, "conv_kernel": 4, "expand": 2,
    "head_dim": 16, "hidden_size": 64,
    "hybrid_override_pattern": TINY_PATTERN, "intermediate_size": 16,
    "layer_norm_epsilon": 1e-5, "mamba_head_dim": 16,
    "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 512,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 16, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_group": 1, "n_groups": 2,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 5, "num_key_value_heads": 2,
    "routed_scaling_factor": 5, "ssm_state_size": 16,
    "tie_word_embeddings": False, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "vocab_size": 512,
    "published": {"hidden_size": 64, "num_hidden_layers": 10,
                  "n_routed_experts": 8,
                  "hybrid_override_pattern": TINY_PATTERN},
    "deployment": "two chips share each block's 8 experts, two stages of "
                  "five blocks (tests only)",
    "reduced": {"num_hidden_layers": "5 of 10", "n_routed_experts": "4 of 8"},
    "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.02-0.06 at the rehearsal's
    # seeds
    "check": {"logprob_tol": 0.25},
}
TINY_TRAFFIC = {
    "kind": "serve_closed", "clients": 3, "rounds": 4,
    "prompt_len": {"dist": "uniform", "min": 70, "max": 120},
    "output_len": {"dist": "uniform", "min": 20, "max": 40},
    "engine_config": {"max_seqs": 4, "page_size": 16, "max_pages_per_seq": 12,
                      "prefill_buckets": [128]},
    "max_ongoing_requests": 16, "drain_s": 60.0}


def test_bench_check_reads_the_familys_reference(tmp_path, monkeypatch):
    """`BenchServer` builds the family from `llm_config["family"]` and
    `bench_check` compares its engine (a prefill through the grouped scan
    and the latent experts that fills the states and the one layer's pages;
    then decode steps on the pools, with nothing kept for an expert block)
    with `references/nemotron_h.py` on the same bf16 weights and the same
    half of the experts."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-nemotron.json"), "w") as f:
        json.dump(TINY_NEMOTRON, f)
    with open(os.path.join(root, "benchmark", "workloads",
                           "tiny-decode.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-nemotron", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-nemotron.json",
        "reduced": ["num_hidden_layers", "n_routed_experts"]})
    add_cell(data, "nemotron-closed", "tiny-nemotron", "tiny-decode",
             "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("nemotron-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    assert config["model_config"]["hybrid_override_pattern"] == "ME*EM"
    assert config["model_config"]["experts_held"] == [0, 4]
    assert config["model_config"]["num_experts"] == 8
    server = BenchServer(config)
    try:
        model = server.server.model
        assert type(model).__name__ == "NemotronHModel"
        assert model.cacheless_layer_ids == (1, 3)
        assert server.server.engine.prefix_cache is None
        out = server.bench_check(
            serve_driver.check_prompt(512, seed), serve_driver.CHECK_STEPS)
        stats = server.stats()
    finally:
        server.server._running = False
    assert out["positions"] == serve_driver.CHECK_STEPS
    assert out["max_logprob_gap"] <= 0.25, out["max_logprob_gap"]
    cache = stats["cache"]
    assert (cache["kv_layers"], cache["state_layers"],
            cache["cacheless_layers"]) == (1, 2, 2)
    # (4 x 12 + 1) pages: 16 rows of 32 values on 128 lanes of bf16, K and V
    assert cache["kv_bytes"] == 2 * 49 * 16 * 128 * 2
    load = stats["expert_load"]
    # half the experts held under a seeded router: about half the rows
    assert 0 < load["expert_rows_held"] < load["expert_rows_routed"]
    assert 0 < load["experts_touched"] <= load["expert_tiles"]
