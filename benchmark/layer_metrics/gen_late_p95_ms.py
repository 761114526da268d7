"""Load generator: how late requests were sent, send time minus due time."""
from benchmark import metrics


def read(obs):
    late = metrics.gen_late_ms(obs.get("recs", []))
    return metrics.percentile(late, 95) if late else None
