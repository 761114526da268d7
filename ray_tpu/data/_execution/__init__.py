"""Op-DAG streaming execution for ray_tpu.data.

Reference: python/ray/data/_internal/execution — StreamingExecutor
(streaming_executor.py:52, scheduling loop at :277-352), physical
operators (operators/), and the default actor-pool autoscaler
(autoscaler/default_autoscaler.py).

Redesign notes (why this is not the generator chain it replaces):

* Every logical stage becomes a **physical operator** with bounded
  input/output block-ref queues. All operators run *concurrently*: a
  slow sink backpressures upstream through its queue bounds instead of
  serializing the whole pipeline behind one pull.
* A central scheduling loop picks, each tick, the runnable operator
  with the smallest output queue whose launch fits its
  ``ResourceManager`` reservation + shared-pool borrow
  (data/planner.py) — output-queue-aware scheduling keeps the pipeline
  balanced instead of letting a fast producer flood the store.
* ``ExecutionBudget.store_bytes`` is enforced here: the bytes resident
  in operator queues are accounted against the budget and launches are
  gated on headroom, so peak object-store usage is bounded even with a
  deliberately slow consumer.
* Actor-pool map operators autoscale per dataset: sustained input-queue
  depth grows the pool, an empty queue drains it back (idle-first,
  never under a running task), with the hysteresis/cooldown/bounded-
  step discipline proven in serve/_autoscaling.py.
"""

from ray_tpu.data._execution.interfaces import PhysicalOperator, RefBundle
from ray_tpu.data._execution.streaming_executor import (
    StreamingExecutor,
    execute_plan,
    recent_execution_summaries,
)

__all__ = [
    "PhysicalOperator",
    "RefBundle",
    "StreamingExecutor",
    "execute_plan",
    "recent_execution_summaries",
]
