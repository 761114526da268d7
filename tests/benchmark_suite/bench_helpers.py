"""Shared by the benchmark's tests: a tiny copy of the benchmark's data in
a temporary root (LlamaConfig.tiny's widths), and the outside relaxation of
the device check the rehearsals need (as tests/test_chip_smoke.py does)."""

import json
import os
import shutil
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "family": "llama", "source": "LlamaConfig.tiny (tests only)",
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 512, "max_position_embeddings": 512, "rope_theta": 1e6,
    "rms_norm_eps": 1e-5, "sliding_window": None,
    "tie_word_embeddings": False, "reduced": {},
    "run": {"max_seq_len": 512,
            "model_kwargs": {"attention_impl": "reference", "remat": False}},
    "check": {"logprob_tol": 0.25, "loss_rtol": 0.02},
}
TINY_TRAFFIC = {
    "tiny-open": {
        "kind": "serve_open",
        "arrivals": {"process": "exponential", "rate_per_s": 6.0},
        "prompt_len": {"dist": "uniform", "min": 10, "max": 60},
        "output_len": {"dist": "uniform", "min": 9, "max": 20},
        "engine_config": {"max_seqs": 4, "page_size": 8,
                          "max_pages_per_seq": 16},
        "max_ongoing_requests": 16, "drain_s": 60.0},
    "tiny-closed": {
        "kind": "serve_closed", "clients": 3, "rounds": 4,
        "prompt_len": {"dist": "uniform", "min": 10, "max": 30},
        "output_len": {"dist": "uniform", "min": 20, "max": 40},
        "engine_config": {"max_seqs": 4, "page_size": 8,
                          "max_pages_per_seq": 16},
        "max_ongoing_requests": 16, "drain_s": 60.0},
    "tiny-train": {"kind": "train", "batch": 2, "seq": 64,
                   "learning_rate": 0.01},
}


def tiny_root(tmp_path) -> str:
    """A root holding the real manifest's metrics and readers, with tiny
    configurations and traffic in place of the real ones."""
    root = str(tmp_path / "root")
    bench = os.path.join(root, "benchmark")
    os.makedirs(bench)
    for name in ("layer_metrics", "families"):
        shutil.copytree(os.path.join(REPO, "benchmark", name),
                        os.path.join(bench, name))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"), bench)
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(bench, "configs", "tiny-llama.json"), "w") as f:
        json.dump(TINY, f)
    manifest["configs"] = [{"name": "tiny-llama", "source": TINY["source"],
                            "file": "benchmark/configs/tiny-llama.json",
                            "reduced": [], "why": "tests"}]
    cells = {"tiny-open": "chat-steady", "tiny-closed": "decode-heavy",
             "tiny-train": "train-2k"}
    manifest["workloads"] = []
    for name, traffic in TINY_TRAFFIC.items():
        with open(os.path.join(bench, "workloads", name + ".json"),
                  "w") as f:
            json.dump(traffic, f)
        manifest["workloads"].append({
            "name": name, "config": "tiny-llama", "traffic": name, "chips": 1,
            "why": "tests"})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "workloads" in m:
                m["workloads"] = [t for t, real in cells.items()
                                  if real in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


# A leased worker's TPU platform becomes virtual CPU devices, one per chip.
SITECUSTOMIZE = textwrap.dedent("""
    import os

    if "RAY_TPU_WORKER_ID" in os.environ:
        chips = os.environ.get("TPU_VISIBLE_CHIPS", "")
        if os.environ.get("JAX_PLATFORMS", "").startswith("tpu"):
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count="
                + str(len(chips.split(","))))
""")

# The command has no CPU mode; the rehearsal overrides its constant.
DRIVER = textwrap.dedent("""
    import sys

    from benchmark import run

    run.PLATFORM = "cpu"
    sys.exit(run.main(sys.argv[2:], root=sys.argv[1]))
""")


def rehearsal_env(tmp_path, chips: int = 1):
    (tmp_path / "sitecustomize.py").write_text(SITECUSTOMIZE)
    (tmp_path / "driver.py").write_text(DRIVER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(PYTHONPATH=os.pathsep.join([str(tmp_path), REPO]),
               JAX_PLATFORMS="tpu,cpu",
               TPU_VISIBLE_CHIPS=",".join(map(str, range(chips))))
    return env, str(tmp_path / "driver.py")
