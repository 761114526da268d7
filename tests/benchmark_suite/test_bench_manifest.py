"""BENCHMARK.json and every file it names load and agree; a later PR adds a
cell, a configuration and a per-layer metric as files, editing none."""

import json
import os
import re

import pytest

from bench_helpers import REPO, tiny_root
from benchmark import loadgen, manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return mf.Manifest(REPO)


def test_manifest_and_files_agree(m):
    assert mf.check(m) == []


def test_contract_shape(m):
    d = m.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert d["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= d["run_seconds"] <= 51
    n = len(d["workloads"])
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= n <= 24
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, n // 4)
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/configs/")
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in d[g]]
    assert all(NAME.match(x) for x in names)
    metrics = d["end_to_end"] + d["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["source"] in SOURCES
        assert x["better"] in ("lower", "higher")
    for x in d["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in d["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert x["moves"] in m.end_to_end
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536


def test_configurations_keep_the_published_widths(m):
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32768, "rope_theta": 1e6,
                 "rms_norm_eps": 1e-5, "sliding_window": None,
                 "tie_word_embeddings": False,
                 "max_position_embeddings": 32768}
    for name, entry in m.configs.items():
        cfg = m.config(name)
        for key, value in published.items():
            assert cfg[key] == value, (name, key)
        assert entry["reduced"] == ["num_hidden_layers"]
        assert cfg["num_hidden_layers"] < 32
        kw = m.family(cfg["family"]).model_kwargs(cfg)
        assert kw["head_dim"] == 128 and kw["num_layers"] == 8
        assert m.family("llama").matmul_params(cfg) == \
            8 * 218_103_808 + 4096 * 32768


def test_every_cell_has_traffic_the_generator_reads(m):
    for name, cell in m.cells.items():
        traffic = m.traffic(cell["traffic"])
        assert traffic["kind"] in ("serve_open", "serve_closed", "train")
        if traffic["kind"] != "train":
            reqs = loadgen.requests(traffic, 32768, 1, 5)
            context = (traffic["engine_config"]["page_size"]
                       * traffic["engine_config"]["max_pages_per_seq"])
            # the engine refuses a request that could outgrow its pages
            assert all(len(r.prompt) + r.max_tokens + 7 <= context
                       for r in reqs), name
        e2e = {x["name"] for x in m.metrics_for(name, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_every_reader_returns_nothing_when_it_finds_nothing(m):
    for name in m.per_layer:
        assert m.reader(name)({"seconds": 1.0}) is None, name
    assert m.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(mf.ManifestError):
        m.peaks("TPU v9")


def test_a_later_pr_adds_cell_config_and_metric_as_files(tmp_path):
    root = tiny_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    # three new files ...
    with open(os.path.join(bench, "configs", "other.json"), "w") as f:
        json.dump(dict(json.load(open(os.path.join(
            bench, "configs", "tiny-llama.json"))), vocab_size=1024), f)
    with open(os.path.join(bench, "workloads", "bursty.json"), "w") as f:
        json.dump({"kind": "serve_open",
                   "arrivals": {"process": "bursts", "rate_per_s": 4,
                                "burst": 2},
                   "prompt_len": {"dist": "fixed", "value": 12},
                   "output_len": {"dist": "fixed", "value": 9},
                   "engine_config": {"max_seqs": 2, "page_size": 8,
                                     "max_pages_per_seq": 8}}, f)
    with open(os.path.join(bench, "layer_metrics", "queue_peak.py"),
              "w") as f:
        f.write("def read(obs):\n"
                "    s = obs.get('stats_samples')\n"
                "    return max(x['waiting'] for x in s) if s else None\n")
    # ... and three new entries; nothing that was there is edited.
    data["configs"].append({"name": "other", "source": "tests",
                            "file": "benchmark/configs/other.json",
                            "reduced": [], "why": "tests"})
    data["workloads"].append({"name": "other.bursty", "config": "other",
                              "traffic": "bursty", "chips": 1,
                              "why": "tests"})
    data["per_layer"].append({
        "name": "queue_peak", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "setup_s", "workloads": ["other.bursty"]})
    for x in data["end_to_end"]:   # the serving metrics take the cell in
        if x["name"] in ("ttft_p95_ms", "tpot_p95_ms", "out_tok_per_s"):
            x["workloads"].append("other.bursty")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    m = mf.Manifest(root)
    assert mf.check(m) == []
    cell = m.cell("other.bursty")
    cfg = m.config(cell["config"])
    assert m.family(cfg["family"]).model_kwargs(cfg)["vocab_size"] == 1024
    reqs = loadgen.requests(m.traffic(cell["traffic"]), 1024, 1, 5)
    assert len(reqs) == 20 and reqs[0].due_s == reqs[1].due_s
    names = [x["name"] for x in m.metrics_for("other.bursty", "per_layer")]
    assert names == ["queue_peak"]
    obs = {"stats_samples": [{"t": 0.1, "running": 1, "waiting": 3}],
           "seconds": 1.0}
    assert m.layer_values("other.bursty", obs) == {
        "queue_peak": {"value": 3.0, "unit": "count"}}
    with pytest.raises(mf.ManifestError):
        m.cell("no-such-cell")
