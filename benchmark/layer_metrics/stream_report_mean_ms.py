"""Runtime, the stream's producer side: mean time an item's blocking round
trip to the stream's owner took (`report_generator_item`, the replica's
handler thread waiting for the proxy's worker to have stored the item), sum
of `report_ms` over sum of `items` of the serve requests'
`ray_tpu.stream.sent` marks in the traced slice."""

from benchmark import program_trace


def served(obs):
    """Stats of the `ray_tpu.stream.sent` marks that carry a request's id
    (any other streaming task leaves one without), or None under the
    readers' floor."""
    stats = [e["stats"] for e in program_trace.events(
        obs, "ray_tpu.stream.sent") if e["stats"].get("rid")]
    return stats if len(stats) >= program_trace.MIN_EVENTS else None


def per_item(obs, stat):
    """Sum of `stat` over sum of `items`, over those marks."""
    stats = served(obs)
    if stats is None or any(stat not in s for s in stats):
        return None
    items = sum(float(s.get("items", 0)) for s in stats)
    return sum(float(s[stat]) for s in stats) / items if items else None


def read(obs):
    return per_item(obs, "report_ms")
