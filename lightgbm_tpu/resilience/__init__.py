"""Fault tolerance for training and serving (docs/RESILIENCE.md).

Four legs, one failure-handling contract across both halves of the
stack:

- ``checkpoint`` — crash-consistent (tmp + os.replace) training
  checkpoints behind ``snapshot_freq``, consumed by engine.train's
  ``resume=auto`` / ``resume_from=`` params; the resumed model
  bit-matches an uninterrupted run.
- ``faultinject`` — deterministic, config/env-driven fault plans
  (raise/kill/delay at named host-side sites); zero overhead when
  disarmed, statically audited to never reach traced code.
- ``errors`` — the typed failure vocabulary (DeadlineExceeded,
  QueueOverflow, ShutdownError, InjectedFault, CheckpointError) the
  serving degradation paths raise and the HTTP transport maps to
  status codes.
- ``backoff`` + ``heartbeat`` — the single retry-with-backoff helper
  (fleet scrape, gateway attempts, cluster join) and per-worker
  heartbeat files with worker-death detection for run_distributed.
"""

from .backoff import backoff_delay, delays, retry_call
from .errors import (
    CheckpointError,
    DeadlineExceeded,
    InjectedFault,
    QueueOverflow,
    ResilienceError,
    ShutdownError,
)
from .faultinject import FaultPlan, arm, configure, disarm, fault_point
from .heartbeat import HeartbeatWriter, health_report, read_heartbeats

__all__ = [
    "CheckpointError",
    "DeadlineExceeded",
    "FaultPlan",
    "HeartbeatWriter",
    "InjectedFault",
    "QueueOverflow",
    "ResilienceError",
    "ShutdownError",
    "arm",
    "backoff_delay",
    "configure",
    "delays",
    "disarm",
    "fault_point",
    "health_report",
    "read_heartbeats",
    "retry_call",
]
