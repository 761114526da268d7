"""Runtime, the stream's owner side: mean time an item lay in the owner's
store (the proxy's worker) before the proxy asked for it, the consumer
having been late: sum of `held_ms` over sum of `items` of the serve
requests' `ray_tpu.stream.sent` marks in the traced slice. The owner counts
it on its own clock and its replies to the producer carry it."""

import os

from benchmark.manifest import _load_py


def read(obs):
    here = os.path.dirname(os.path.abspath(__file__))
    return _load_py(os.path.join(here, "stream_report_mean_ms.py"),
                    "_bench_metric_stream_report_mean_ms").per_item(
                        obs, "held_ms")
