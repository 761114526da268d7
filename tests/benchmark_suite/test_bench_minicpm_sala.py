"""The `minicpm_sala` family in the benchmark: its configuration against the
published config and the rule (depth and the layers' mixers are reduced, no
width is), its parameter, byte and operation counts, the new traffic file's
numbers, its six readers on a hand-made trace, and the harness's own
reference check at a tiny size on the CPU. The cell's whole programs are
compiled for a described v5e in tests/test_tpu_compile.py (one file holds
every such compile: only one process may load the TPU's library). The
manifest's lists are asked whether they hold the cell, never where or with
what else."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench_helpers import REPO, add_cell, tiny_root
from benchmark import holder, manifest as mf, program_trace, run, serve_driver

CONFIG, CELL, FAMILY, TRAFFIC = ("minicpm-sala-serve", "sala-long-context",
                                 "minicpm_sala", "long-context")
# The lists every serving cell is in, which this cell joined.
SHARED = ("slots_busy_mean", "compiles_in_window", "decode_dev_ms",
          "device_idle_pct.serve", "hbm_peak_gib.serve")
NEW = {"sparse_decode_kernel_us": ("us", "lower", "tpot_p95_ms"),
       "sparse_decode_roofline_pct": ("%", "higher", "tpot_p95_ms"),
       "kv_pages_selected_pct": ("%", "lower", "tpot_p95_ms"),
       "sparse_flash_kernel_ms": ("ms", "lower", "out_tok_per_s"),
       "sparse_flash_mxu_pct": ("%", "higher", "out_tok_per_s"),
       "sparse_kernels_pct": ("%", "lower", "out_tok_per_s")}
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


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
    assert set(entry["reduced"]) == {"num_hidden_layers", "mixer_types"}
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
    assert entry["source"] == ("program_counter" if metric
                               == "kv_pages_selected_pct" else "device_trace")
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
        "serve_closed", 8, 6)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 8192,
                                     "max": 16384}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512,
                                     "max": 1024}
    assert traffic["engine_config"] == {
        "max_seqs": 8, "page_size": 64, "max_pages_per_seq": 272,
        "prefill_buckets": [16384]}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert (traffic["max_ongoing_requests"], traffic["drain_s"]) == (64, 120.0)
    assert "arrivals" not in traffic and "prefix" not in traffic
    # every prompt in the 16,384 bucket and at or past dense_len; with its
    # answer and the window a decode program may overshoot by, inside the
    # slot's 17,408 positions
    ec = EngineConfig(**traffic["engine_config"])
    assert loadgen.buckets_used(traffic, list(ec.prefill_buckets)) == [16384]
    reqs = loadgen.requests(traffic, 73448, 2 ** 31 + 5, 40.0)
    assert len(reqs) == 8 * 6
    assert all(8192 <= len(r.prompt) <= 16384 and 512 <= r.max_tokens <= 1024
               and max(r.prompt) < 73448 for r in reqs)
    assert max(len(r.prompt) + r.max_tokens + ec.decode_steps - 1
               for r in reqs) <= 272 * 64 == 17408
    assert serve_driver.warm_spec(traffic)["max_nb"] == 8
    assert list(serve_driver.warm_spec(traffic)["prompt_lens"]) == ["16384"]


# -- the configuration against its source ------------------------------------
def test_configuration_cuts_depth_and_the_mixers_and_no_width(m, cfg):
    assert mf.published_problems(m, CONFIG) == []
    assert set(cfg["reduced"]) == {"num_hidden_layers", "mixer_types"}
    assert "5,039,448,064" in cfg["reduced"]["num_hidden_layers"]
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    published = cfg["published"]["mixer_types"]
    assert len(published) == 32 == cfg["published"]["num_hidden_layers"]
    assert [i for i, kind in enumerate(published) if kind == SPARSE] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    # published layers 9-24: of the four runs of 16 at the published 1 : 3,
    # the one that begins with a sparse layer, as the model does
    assert cfg["mixer_types"] == published[9:25] and cfg[
        "num_hidden_layers"] == 16
    assert cfg["mixer_types"].count(SPARSE) == 4 and cfg[
        "mixer_types"].count(LIGHTNING) == 12
    runs = [start for start in range(17)
            if published[start:start + 16].count(SPARSE) == 4]
    assert runs == [7, 8, 9, 14]
    assert [start for start in runs if published[start] == SPARSE] == [9]
    for said in ("two pipeline stages", "layers 9-24", "embedding",
                 "final norm", "idle share"):
        assert said in cfg["deployment"], said
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["lightning_nh"], cfg["lightning_nkv"],
            cfg["lightning_head_dim"], cfg["vocab_size"], cfg["scale_emb"],
            cfg["scale_depth"], cfg["dim_model_base"], cfg["rope_theta"]) == (
        4096, 16384, 32, 2, 128, 32, 32, 128, 73448, 12, 1.4, 256, 10000)
    assert (cfg["attn_use_rope"], cfg["lightning_use_rope"], cfg["qk_norm"],
            cfg["use_output_gate"], cfg["use_output_norm"],
            cfg["attn_use_output_gate"], cfg["tie_word_embeddings"],
            cfg["lightning_scale"]) == (
        False, True, True, True, True, True, False, "1/sqrt(d)")
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    for key in ("sparse_config", "dense_switch", "compressed_scores",
                "lightning_decay", "qk_norm", "output_norm_and_gates",
                "rotary_pairing", "residual_multiplier", "mup_denominator",
                "head", "weights", "init"):
        assert cfg["assumed"][key], key
    assert "MiniCPM4-8B" in cfg["assumed"]["sparse_config"]
    assert cfg["run"]["max_seq_len"] == 17408
    assert cfg["check"]["logprob_tol"] > 0 and cfg["check"]["why"]
    memory = cfg["memory_analysis"]
    assert 0.25 * 15.75 < memory["decode"]["peak_gib"] < memory[
        "prefill_16384x1"]["peak_gib"] < memory["prefill_16384x8"][
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
                   if r["name"] == "MiniCPM-SALA")
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
    config["lightning_head_dim"] = 64
    with open(os.path.join(root, "benchmark", "configs", "cut.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "cut", "source": "tests", "why": "tests",
                            "file": "benchmark/configs/cut.json",
                            "reduced": ["num_hidden_layers", "mixer_types"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    bad = mf.published_problems(mf.Manifest(root), "cut")
    assert any("lightning_head_dim is 64" in b for b in bad)


# -- the family's counts -------------------------------------------------------
def test_parameter_count_is_the_issues_table(m, cfg):
    family = m.family(FAMILY)
    assert family.lightning_mixer_params(cfg) == 5 * 4096 * 4096 \
        == 83_886_080
    assert family.sparse_mixer_params(cfg) == (
        3 * 4096 * 4096 + 2 * 4096 * 256) == 52_428_800
    assert family.mlp_params(cfg) == 3 * 4096 * 16384 == 201_326_592
    assert family.lightning_layer_params(cfg) == (
        83_886_080 + 201_326_592 + 2 * 4096 + 256 + 4096) == 285_225_216
    assert family.sparse_layer_params(cfg) == (
        52_428_800 + 201_326_592 + 2 * 4096 + 256) == 253_763_840
    assert family.vocabulary_params(cfg) == 2 * 73448 * 4096 + 4096 \
        == 601_690_112
    assert (family.lightning_layers(cfg), family.sparse_layers(cfg)) == (12, 4)
    assert family.parameters(cfg) == (12 * 285_225_216 + 4 * 253_763_840
                                      + 601_690_112) == 5_039_448_064
    assert 2 * family.parameters(cfg) / 2 ** 30 == pytest.approx(9.387,
                                                                 abs=1e-3)
    whole = dict(cfg, num_hidden_layers=32,
                 mixer_types=cfg["published"]["mixer_types"])
    assert family.parameters(whole) == (24 * 285_225_216 + 8 * 253_763_840
                                        + 601_690_112) == 9_477_206_016
    # the depth rule's other side: published layers 9-20
    assert 2 * family.parameters(dict(
        cfg, mixer_types=cfg["mixer_types"][:12])) / 2 ** 30 \
        == pytest.approx(7.32, abs=0.01)
    assert family.matmul_params(cfg) == (
        12 * (83_886_080 + 201_326_592) + 4 * (52_428_800 + 201_326_592)
        + 73448 * 4096)
    kw = family.model_kwargs(cfg)
    assert (kw["depth"], len(kw["mixer_types"]), kw["vocab_size"],
            kw["max_seq_len"], kw["num_kv_heads"], kw["lightning_heads"]) == (
        32, 16, 73448, 17408, 2, 32)
    assert (kw["kernel_size"], kw["kernel_stride"], kw["block_size"],
            kw["init_blocks"], kw["window_size"], kw["topk"],
            kw["dense_len"]) == (32, 16, 64, 1, 2048, 64, 8192)
    model = family.model(kw)
    assert model.cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert len(model.index_layer_ids) == 4 and len(
        model.state_layer_ids) == 12
    # the program's own tree holds that many, to the parameter
    import jax

    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    import math

    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == 5_039_448_064


def test_cache_and_kernel_counts(m, cfg):
    family = m.family(FAMILY)
    # a token: 1 KiB of keys and values a sparse layer; a page's index 4 KiB
    # a layer; a slot's states 2 MiB a lightning layer
    assert family.kv_token_bytes(cfg) == 4 * 1024
    assert family.index_page_bytes(cfg) == 4 * 4 * 256 * 4 == 4 * 4096
    assert family.state_slot_bytes(cfg) == 12 * 2 * 2 ** 20
    slots, positions = 8, 17408
    assert slots * positions * family.kv_token_bytes(cfg) / 2 ** 30 \
        == pytest.approx(0.531, abs=1e-3)
    assert slots * 272 * family.index_page_bytes(cfg) / 2 ** 20 \
        == pytest.approx(34.0)
    assert slots * family.state_slot_bytes(cfg) / 2 ** 30 == 0.1875
    # the same tokens under 16 layers of an 8-KV-head attention: 8.5 GiB
    assert slots * positions * 16 * 2 * 8 * 128 * 2 / 2 ** 30 == 8.5
    # what a query attends to: everything under dense_len, 64 blocks past it
    assert [family.chosen_keys(cfg, t) for t in (0, 8190, 8191, 8192, 16383)
            ] == [1, 8191, 63 * 64 + 64, 63 * 64 + 1, 63 * 64 + 64]
    # a call of the decode kernel over 8 rows' two heads' 64 pages each
    pages = 8 * 2 * 64
    assert family.sparse_decode_bytes(cfg, pages) == pages * 64 * 128 * 4 \
        == 2 ** 25
    assert family.sparse_decode_flops(cfg, pages) == pages * 64 * 16 * 128 * 4
    # 16 operations a byte, under the chip's 240: memory-bound
    assert family.sparse_decode_flops(cfg, 1) / family.sparse_decode_bytes(
        cfg, 1) == 16.0
    per_pair = 4 * 32 * 128
    assert family.sparse_flash_flops(cfg, 3000, 1) == per_pair * 3000 * 3001 / 2
    n = 12288
    pairs = 8191 * 8192 // 2 + sum(63 * 64 + t % 64 + 1
                                   for t in range(8191, n))
    assert family.sparse_flash_flops(cfg, n, 1) == per_pair * pairs
    assert family.sparse_flash_flops(cfg, 8 * n, 8) == \
        family.sparse_flash_flops(cfg, n, 1)       # a call is one row
    # at 16,384 the selection reads a quarter of what a full walk would
    assert pairs / (n * (n + 1) / 2) == pytest.approx(0.664, abs=0.01)
    full = family.sparse_flash_flops(cfg, 16384, 1) / per_pair
    assert full / (16384 * 16385 / 2) == pytest.approx(0.498, abs=0.01)
    assert family.attention_flops_per_token(cfg, 16384, causal=False) == (
        4 * 4.0 * 32 * 128 * 4096 + 12 * 4.0 * 32 * 128 * 128)


def test_family_file_fails_at_once_without_the_programs_model(m, cfg,
                                                              monkeypatch):
    """A tree without `ray_tpu.models.minicpm_sala` (the parent): an error
    from `model_kwargs`, which `run.context` calls before any cluster."""
    import importlib.util

    family = m.family(FAMILY)
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(RuntimeError, match="unknown model family "
                       "'minicpm_sala'"):
        family.model_kwargs(cfg)
    with pytest.raises(RuntimeError, match="ray_tpu.models.minicpm_sala"):
        run.context(m, m.cell(CELL), 1, 1.0, False)


@pytest.mark.parametrize("key,value,says", [
    ("tie_word_embeddings", True, "untied head"),
    ("hidden_act", "gelu", "SiLU"),
    ("qk_norm", False, "a norm over each head"),
    ("attn_use_rope", True, "without rotary"),
    ("lightning_use_rope", False, "without rotary"),
    ("use_output_norm", False, "both mixers gated"),
    ("attn_use_output_gate", False, "both mixers gated"),
    ("lightning_nkv", 8, "a lightning key head a query head"),
    ("num_hidden_layers", 12, "does not name num_hidden_layers"),
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


def _trace(n=3, decode_us=80.0, flash_us=60_000.0, selected=1024,
           visible=3200, tokens=12288, nb=1, steps=8, layers=4):
    """`n` prefill dispatches of `nb` prompts (`tokens` prompt tokens
    together) with `layers` `sparse_flash` calls a row, and `2 n` decode
    windows of `steps` token steps with `layers` `sparse_decode` calls each,
    whose `emit` spans say the `selected` and `visible` pages of a call
    summed over the window's calls. A fusion that borrows a kernel's name
    does not count, nor the other families' kernels."""
    host, ops = [_ev("bench.window", 0, 1e6)], []
    for i in range(n):
        t = 100_000 * i
        host.append(_ev("ray_tpu.engine.prefill_dispatch", t, 50,
                        bucket=16384, nb=nb, tokens=tokens, cached_tokens=0,
                        head_rows=nb))
        for j in range(layers * nb):
            ops.append(_ev(f"%sparse_flash.{i}{j} = bf16[1,32,128,16384]"
                           "{3,2,1,0} custom-call(%any, %q, %k, %v, %c)",
                           t + 10 + 70_000 * j, flash_us))
        ops += [_ev(f"%sparse_flash_fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 10, 900),
                _ev(f"%swa_flash.{i} = bf16[1,32,4096,128]{{3,2,1,0}} "
                    "custom-call(%q, %k, %v)", t + 10, 2500)]
        for j in range(2):
            at = t + 20_000 + 30_000 * j
            calls = steps * layers
            host.append(_ev("ray_tpu.engine.emit", at + 900, 30,
                            tokens=8 * steps, finished=0, skipped=0,
                            pages_selected=selected * calls,
                            pages_visible=visible * calls,
                            select_calls=calls))
            ops += [_ev(f"%sparse_decode.{layers * (2 * i + j) + c} = "
                        "bf16[8,2,16,128]{3,2,1,0} custom-call(%pages, "
                        "%counts, %used, %q)", at + 100 + 100 * c, decode_us)
                    for c in range(layers)]
            ops.append(_ev(f"%paged_decode.{2 * i + j} = bf16[8,1,32,128]"
                           "{3,2,1,0} custom-call(%pt, %lens, %q)", at + 700,
                           90))
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


@pytest.mark.parametrize("metric,value,few", [
    ("sparse_decode_kernel_us", 80.0, dict(n=1, layers=2)),
    ("sparse_flash_kernel_ms", 60.0, dict(n=1, nb=0))])
def test_kernel_time_readers(m, obs, metric, value, few):
    read = m.reader(metric)
    assert read(obs) == pytest.approx(value)
    obs["rewrite"](**few)    # four decode calls, or no flash call
    assert read(obs) is None
    _nothing(read, obs)


def test_one_flash_call_is_enough_to_read(m, obs):
    obs["rewrite"](n=1, layers=1)
    assert m.reader("sparse_flash_kernel_ms")(obs) == pytest.approx(60.0)


def test_decode_share_reader_takes_the_larger_floor_and_cannot_pass_100(
        m, obs):
    read = m.reader("sparse_decode_roofline_pct")
    bytes_us = 1024 * 64 * 128 * 4 / 819e9 * 1e6       # 41.0 us at the peak
    flops_us = 1024 * 64 * 16 * 128 * 4 / 197e12 * 1e6  # 2.7 us
    assert bytes_us > flops_us
    assert read(obs) == pytest.approx(100 * bytes_us / 80.0)
    # a call at the bound reads 100, and none reads more
    obs["rewrite"](3, decode_us=bytes_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # where the operations bound it (a chip of a hundredth the matmul rate),
    # they are the floor
    slow = dict(obs["peaks"], bf16_flops_per_s=197e12 / 100)
    obs["rewrite"](3)
    assert read(dict(obs, peaks=slow)) == pytest.approx(
        100 * 100 * flops_us / 80.0)
    # the pages are a call's: a window of twice the steps says twice the
    # pages over twice the calls
    obs["rewrite"](3, steps=16)
    assert read(obs) == pytest.approx(100 * bytes_us / 80.0)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("mellum"))) is None
    obs["rewrite"](1, layers=2)   # four calls
    assert read(obs) is None
    _nothing(read, obs)


def test_pages_share_reader_is_selected_over_visible(m, obs):
    read = m.reader("kv_pages_selected_pct")
    assert read(obs) == pytest.approx(100 * 1024 / 3200)
    obs["rewrite"](3, selected=500, visible=500)   # rows under dense_len
    assert read(obs) == pytest.approx(100.0)
    _nothing(read, obs)


def test_flash_share_reader_cannot_pass_100(m, obs):
    read = m.reader("sparse_flash_mxu_pct")
    family = m.family(FAMILY)
    least_us = family.sparse_flash_flops(obs["config"], 12288, 1) \
        / 197e12 * 1e6
    assert least_us == pytest.approx(4175.2, abs=0.5)
    assert read(obs) == pytest.approx(100 * least_us / 60_000.0)
    obs["rewrite"](3, flash_us=least_us)
    assert read(obs) == pytest.approx(100.0) and read(obs) <= 100.0 + 1e-9
    # a wave of 8 prompts is 8 rows' calls, each of one row's pairs
    obs["rewrite"](3, tokens=8 * 12288, nb=8)
    assert read(obs) == pytest.approx(100 * least_us / 60_000.0)
    assert read(dict(obs, peaks=None)) is None
    assert read(dict(obs, family=m.family("granite_hybrid"))) is None
    _nothing(read, obs)


def test_share_of_the_busy_time_reader(m, obs):
    read = m.reader("sparse_kernels_pct")
    # 12 calls of 60 ms and 24 of 80 us in a second of busy time
    assert read(dict(obs, traces=[dict(obs["traces"][0], busy_s=1.0)])) \
        == pytest.approx(100 * (12 * 60e-3 + 24 * 80e-6) / 1.0)
    _nothing(read, obs)


# -- the harness's own check, at a tiny size on the CPU ----------------------
TINY_SALA = {
    "family": FAMILY,
    "source": "MiniCPMSalaConfig.tiny's widths (tests only)",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4,
    "mixer_types": [LIGHTNING, SPARSE, LIGHTNING, LIGHTNING],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "attention_bias": False, "hidden_act": "silu", "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 32,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 16,
                      "init_blocks": 1, "window_size": 32, "topk": 4,
                      "dense_len": 64},
    "published": {"hidden_size": 64, "num_hidden_layers": 8,
                  "mixer_types": [LIGHTNING, SPARSE, LIGHTNING, LIGHTNING]
                  * 2},
    "deployment": "two stages of four layers (tests only)",
    "reduced": {"num_hidden_layers": "4 of 8", "mixer_types": "the first 4"},
    "run": {"max_seq_len": 512, "model_kwargs": {}},
    # bf16 weights and activations on the CPU: 0.02-0.05 at the rehearsal's
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
    `bench_check` compares its engine (a prefill over the call's own keys
    through the chunkwise scan, the selection and the block-sparse flash
    forward, that fills the states, the pages and the index; then decode
    steps that choose pages and walk them) with `references/minicpm_sala.py`
    on the same bf16 weights. The check's 100-token prompt is past this tiny
    selection's `dense_len` of 64: here it does reach the sparse branch."""
    from benchmark.replica import BenchServer

    root = tiny_root(tmp_path)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-sala.json"), "w") as f:
        json.dump(TINY_SALA, f)
    with open(os.path.join(root, "benchmark", "workloads",
                           "tiny-context.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-sala", "source": "tests", "why": "tests",
        "file": "benchmark/configs/tiny-sala.json",
        "reduced": ["num_hidden_layers", "mixer_types"]})
    add_cell(data, "sala-closed", "tiny-sala", "tiny-context", "tiny-closed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    assert mf.check(manifest) == []
    monkeypatch.setattr(holder, "cache_everything", lambda: None)
    seed = 2 ** 31 + 7
    ctx = run.context(manifest, manifest.cell("sala-closed"), seed, 1.0,
                      False)
    config = serve_driver.llm_config(ctx)
    assert config["family"] == FAMILY
    assert config["model_config"]["depth"] == 8
    assert config["model_config"]["mixer_types"] == TINY_SALA["mixer_types"]
    server = BenchServer(config)
    try:
        model = server.server.model
        assert type(model).__name__ == "MiniCPMSalaModel"
        assert model.index_layer_ids == (1,)
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
            cache["index_layers"]) == (1, 3, 1)
    # (4 x 12 + 1) pages: 16 rows of 32 values on 128 lanes of bf16, K and V;
    # four segment means a page, float32
    assert cache["kv_bytes"] == 2 * 49 * 16 * 128 * 2
    assert cache["index_bytes"] == 49 * 4 * 128 * 4
    assert cache["state_bytes"] == 3 * 4 * 4 * 16 * 128 * 4
    load = stats["expert_load"]
    assert 0 < load["pages_selected"] < load["pages_visible"]
