"""Secure inference serving: warm model registry + dynamic micro-batching.

The single-request user path pays trace + compile + self-check-ladder
cost per call and runs batches of one; a batch pays a call's fixed
cost once for all its rows (how much that buys on a chip is not
measured yet: ROADMAP S7).  This subsystem
closes that gap for serving traffic:

- :mod:`registry` — traces a predictor once per (model, fixedpoint
  dtype), compiles each batch bucket through the existing pipeline, and
  drives the validated-jit ladder to steady state at REGISTRATION time,
  so requests never pay trace/compile/ladder cost;
- :mod:`batcher` — per-model bounded queues; the scheduler coalesces
  pending requests up to ``max_batch`` rows or ``max_wait_ms``
  (whichever first), pads to power-of-two buckets (no recompiles),
  evaluates once, scatters per-row results to callers, and enforces
  deadlines + typed ``ServerOverloadedError`` backpressure;
- :mod:`server` — the in-process :class:`InferenceServer` API (the
  ``blitzen`` CLI daemon wraps it with an HTTP front end);
- :mod:`metrics` — queue depth, batch-size histogram, batch-fill ratio,
  p50/p99 request latency, deadline misses, plus the warm-path
  acceptance counters (no re-trace / no ladder re-run after warmup);
- :mod:`snapshot` — durable warm-state snapshots (traced computations,
  resolved plan states, lowered graphs, kernel verdicts, AOT bucket
  artifacts — executed outright on restore — fixed-keys probe digests)
  so a replica cold-starts warm in seconds; the fleet layer above this
  package is ``bin/blitzen`` (graceful drain, ``/readyz``) +
  ``bin/donner`` (the routing front door) — DEVELOP.md "Fleet serving";
- :mod:`controlplane` — the continuous train -> canary -> promote /
  auto-rollback loop over the fleet (DEVELOP.md "Continuous training
  loop").

Knobs: ``MOOSE_TPU_SERVE_MAX_BATCH`` / ``MOOSE_TPU_SERVE_MAX_WAIT_MS``
/ ``MOOSE_TPU_SERVE_QUEUE`` / ``MOOSE_TPU_SERVE_DEADLINE_MS`` (see
:mod:`config`), ``MOOSE_TPU_SNAPSHOT_DIR`` / ``MOOSE_TPU_SNAPSHOT_AOT``
/ ``MOOSE_TPU_SNAPSHOT_AOT_EXEC`` (see :mod:`snapshot`),
``MOOSE_TPU_CANARY_*`` (see :mod:`controlplane`).
"""

from .config import ServingConfig
from .metrics import ServingMetrics
from .registry import (
    ModelRegistry,
    RegisteredModel,
    bucket_for,
    power_of_two_buckets,
)
from .batcher import ModelQueue
from .controlplane import (
    CanaryConfig,
    ControlPlane,
    HttpFleetClient,
    LocalFleetClient,
    SessionGenerationProducer,
)
from .server import InferenceServer
from .snapshot import (
    current_snapshot_path,
    restore_registry,
    save_snapshot,
)

__all__ = [
    "CanaryConfig",
    "ControlPlane",
    "HttpFleetClient",
    "InferenceServer",
    "LocalFleetClient",
    "ModelQueue",
    "ModelRegistry",
    "RegisteredModel",
    "ServingConfig",
    "ServingMetrics",
    "SessionGenerationProducer",
    "bucket_for",
    "current_snapshot_path",
    "power_of_two_buckets",
    "restore_registry",
    "save_snapshot",
]
