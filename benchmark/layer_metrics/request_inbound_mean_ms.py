"""Runtime, a request's way in: mean of `pre_ms + dispatch_ms` over the
program's `ray_tpu.request.arrived` marks in the traced slice. `pre_ms` is
the proxy's own account (request read off the socket to the call handed to
the runtime: parse, executor hop, replica pick), `dispatch_ms` the replica
worker's (receipt of the actor call to the handler's first line); each is a
duration of one process's clock."""

from benchmark import program_trace


def read(obs):
    values = [float(s["pre_ms"]) + float(s["dispatch_ms"])
              for s in (e["stats"] for e in program_trace.events(
                  obs, "ray_tpu.request.arrived"))
              if s.get("rid") and "pre_ms" in s and "dispatch_ms" in s]
    if len(values) < program_trace.MIN_EVENTS:
        return None
    return sum(values) / len(values)
