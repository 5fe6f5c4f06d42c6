"""repro.serve — request-level serving over the compiled pipeline.

PR 5 made one *input stream* fast; this package makes many *users*
fast.  A :class:`ModelServer` replica fronts a ``CompiledModel`` with:

* :class:`AdmissionQueue` — a bounded priority queue (reject /
  backpressure policies) so heavy traffic sheds at the door instead of
  growing an unbounded buffer;
* :class:`BatchedModel` — cross-request batch packing by vmapping the
  fused segment executors over a slot axis, one AOT-compiled executable
  per batch shape, per-request outputs bit-exact with sequential
  ``CompiledModel.run``;
* priority/deadline-aware rounds served in the queue's Smith pop
  order, which :func:`repro.pipeline.schedule.schedule_stream` and the
  existing ``PipelineSchedule.validate()`` prove when
  ``stats()["last_round"]`` is read, not every round;
* per-request spans on the ``serve:<replica>`` lane plus ``serve.*``
  metrics, with replica stats in ``report_dict()["serve"]``;
* service objectives (PR 9): pass :class:`repro.obs.SloSpec` lists to
  ``ModelServer(slo=[...])`` for rolling burn-rate evaluation, turn on
  ``shed_expired=True`` to resolve already-expired requests with
  :class:`DeadlineExceededError` instead of running them, and arm the
  flight recorder (``MATCH_FLIGHT=path``) for automatic incident dumps
  on :class:`QueueFullError` / SLO breach.

The LM token-serving loop (continuous batching over prefill/decode)
lives in :mod:`repro.serving`; this package serves whole-graph
requests (one inference per request) over any compiled target.
"""

from .batching import BatchedModel
from .engine import ModelServer, ServeDrainWarning
from .queue import (
    AdmissionQueue,
    DeadlineExceededError,
    QueueFullError,
    ServeHandle,
    ServeRequest,
)

__all__ = [
    "AdmissionQueue",
    "BatchedModel",
    "DeadlineExceededError",
    "ModelServer",
    "QueueFullError",
    "ServeDrainWarning",
    "ServeHandle",
    "ServeRequest",
]
