"""Runtime, the stream's producer side: the share of the replica's handler
threads' streaming time spent asleep because the owner held more than
`generator_backpressure_num_objects` unconsumed items, 100 x sum of
`paused_ms` over sum of `body_ms + serialize_ms + report_ms + paused_ms` (a
stream's whole length on its thread) of the serve requests'
`ray_tpu.stream.sent` marks in the traced slice."""

import os

from benchmark.manifest import _load_py

PARTS = ("body_ms", "serialize_ms", "report_ms", "paused_ms")


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    stats = _load_py(os.path.join(here, "stream_report_mean_ms.py"),
                     "_bench_metric_stream_report_mean_ms").served(obs)
    if stats is None or any(p not in s for s in stats for p in PARTS):
        return None
    whole = sum(float(s[p]) for s in stats for p in PARTS)
    return (100.0 * sum(float(s["paused_ms"]) for s in stats) / whole
            if whole else None)
