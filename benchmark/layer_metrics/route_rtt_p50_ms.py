"""Runtime (HTTP proxy, handle/router, replica actor): a GET /v1/models
probe once a second during the window, the path of a request without the
engine."""
from benchmark import metrics


def read(obs):
    probes = obs.get("probe_ms") or []
    return metrics.percentile(probes, 50) if probes else None
