"""Kernels: the share of the router's assignments (token x chosen expert)
that fell on experts this chip holds, and so got a row of `moe_gmm`: the
program's `expert_rows_held` over its `expert_rows_routed`, both summed over
layers and forwards, on the `ray_tpu.engine.emit` spans (decode windows) and
the `ray_tpu.engine.prefill_dispatch` spans (prefills) of the slice. A chip
that holds 36 of 72 experts under a seeded router reads about 50; a reading
of 100 where the configuration holds a share means the layer computed experts
that are not its own. None where the program reports no such counter."""

from benchmark import program_trace

SPANS = ("ray_tpu.engine.emit", "ray_tpu.engine.prefill_dispatch")


def read(obs):
    stats = [e["stats"] for name in SPANS
             for e in program_trace.events(obs, name)
             if "expert_rows_held" in e["stats"]
             and "expert_rows_routed" in e["stats"]]
    routed = sum(float(s["expert_rows_routed"]) for s in stats)
    if len(stats) < program_trace.MIN_EVENTS or not routed:
        return None
    return 100.0 * sum(float(s["expert_rows_held"]) for s in stats) / routed
