"""Kernels: device time of one call of the sparse decode kernel (one sparse
layer of one token step: every row's two KV heads each walking the pages its
group chose, or a short row's own pages), from the `XLA Ops` events of
custom-calls whose instruction name holds `sparse_decode`. The scan of the
device planes is `ssm_scan_kernel_ms.py`'s, asked for this kernel's name; the
other `sparse_*` readers take their times from here."""

import os

from benchmark import program_trace
from benchmark.manifest import _load_py

KERNEL = "sparse_decode"
# The span that carries what the decode program counted of its selections.
SPAN = "ray_tpu.engine.emit"


def custom_calls(obs, holds):
    """(device seconds, calls) over the traced slice of the custom-calls
    whose instruction name holds `holds`."""
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "ssm_scan_kernel_ms.py"),
                    "_bench_metric_ssm_scan_kernel_ms").totals(obs, holds)


def seconds_per_call(obs, kernel=KERNEL, least=program_trace.MIN_EVENTS):
    """Mean device seconds of a call of `kernel`, or None with fewer than
    `least` calls (a program without the kernel)."""
    seconds, calls = custom_calls(obs, kernel)
    return seconds / calls if calls >= least else None


def pages(obs):
    """(pages selected, pages visible, selections) summed over the slice's
    `emit` spans that carry them, or None where none does (a program whose
    decode steps choose no pages)."""
    stats = [e["stats"] for e in program_trace.events(obs, SPAN)
             if "pages_selected" in e["stats"]
             and "pages_visible" in e["stats"]
             and "select_calls" in e["stats"]]
    total = lambda key: sum(float(s[key]) for s in stats)
    if not stats or not total("select_calls"):
        return None
    return (total("pages_selected"), total("pages_visible"),
            total("select_calls"))


def read(obs):
    s = seconds_per_call(obs)
    return None if s is None else s * 1e6
