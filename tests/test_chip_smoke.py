"""chip_smoke.py off the chip: it must refuse, and its control flow must hold.

The script has no CPU mode. The rehearsals (slow) run it end to end at
LlamaConfig.tiny on the CPU by relaxing the device check from OUTSIDE: a
driver overrides the script's constants, and a sitecustomize on PYTHONPATH
turns each leased worker's TPU platform into as many virtual CPU devices as
the lease has chips."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(cmd, env, timeout):
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=timeout)


def _refused(proc):
    assert proc.returncode != 0, proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout, proc.stdout


def test_refuses_under_cpu_platform():
    _refused(_run([sys.executable, SCRIPT],
                  dict(os.environ, JAX_PLATFORMS="cpu"), 120))


def test_refuses_without_chips():
    from ray_tpu._private.accelerators import _count_tpu_chips

    if _count_tpu_chips():
        pytest.skip("this host has TPU chips")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS")}
    _refused(_run([sys.executable, SCRIPT], env, 300))


_DRIVER = textwrap.dedent("""
    import sys

    import jax.numpy as jnp

    import chip_smoke as cs

    # LlamaConfig.tiny's widths, with KV heads that four chips divide.
    tiny = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                num_layers=2, num_heads=4, num_kv_heads=4, head_dim=32,
                dtype=jnp.float32)
    cs.PLATFORM = "cpu"
    cs.SERVE.update(
        model="tiny-kv4", prompt_len=40, max_tokens=8,
        model_config=dict(tiny, max_seq_len=512, remat=False,
                          attention_impl="reference"),
        engine_config={"max_seqs": 4, "page_size": 8,
                       "max_pages_per_seq": 16})
    cs.TRAIN.update(model_config=dict(tiny, max_seq_len=128),
                    batch=2, seq=128, steps=3)
    sys.exit(cs.main(sys.argv[1:]))
""")

_SITECUSTOMIZE = textwrap.dedent("""
    import os

    if "RAY_TPU_WORKER_ID" in os.environ:
        chips = os.environ.get("TPU_VISIBLE_CHIPS", "")
        if os.environ.get("JAX_PLATFORMS", "").startswith("tpu"):
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count="
                + str(len(chips.split(","))))
""")


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_tiny_cpu(tmp_path, chips):
    (tmp_path / "sitecustomize.py").write_text(_SITECUSTOMIZE)
    (tmp_path / "driver.py").write_text(_DRIVER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(
        PYTHONPATH=os.pathsep.join([str(tmp_path), REPO]),
        JAX_PLATFORMS="tpu,cpu",
        TPU_VISIBLE_CHIPS=",".join(map(str, range(chips))))
    proc = _run([sys.executable, str(tmp_path / "driver.py"),
                 "--chips", str(chips)], env, 1500)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-6000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
