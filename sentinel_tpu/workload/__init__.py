"""`sentinel_tpu.workload` — seeded workload engine + closed-loop live
autotuner (ROADMAP item 3).

Three layers, importable independently:

* :mod:`~sentinel_tpu.workload.shapes` — pure-arithmetic traffic shapes
  (diurnal, flash crowd, Zipf churn, hot-param flood, shard skew);
* :mod:`~sentinel_tpu.workload.generator` — the seeded deterministic
  offered-event stream plus drivers for the real adapters and the
  client, and the queueing service model that turns real verdicts into
  modeled request latencies;
* :mod:`~sentinel_tpu.workload.tuner` /
  :mod:`~sentinel_tpu.workload.operating_point` — the SLO-burn-driven
  autotuner that retunes the shared ``OperatingPoint`` LIVE, guarded by
  the PR-15 instruments (expected-retrace journal, HBM ledger).
"""

from sentinel_tpu.workload.generator import (
    OfferedEvent,
    ServiceBackend,
    ServiceModel,
    TrafficGenerator,
    drive_asgi,
    drive_client,
    drive_gateway,
    drive_grpc,
    drive_streaming,
)
from sentinel_tpu.workload.operating_point import (
    ENGINE_FIELDS,
    OperatingPoint,
    sim_default_op,
)
from sentinel_tpu.workload.shapes import (
    Constant,
    Diurnal,
    FlashCrowd,
    HotParamFlood,
    SkewedKeys,
    WorkloadSpec,
    ZipfKeys,
    flash_crowd_2x,
)
from sentinel_tpu.workload.tuner import (
    AutoTuner,
    LoopResult,
    TunerConfig,
    run_closed_loop,
    workload_slos,
)

__all__ = [
    "AutoTuner",
    "Constant",
    "Diurnal",
    "ENGINE_FIELDS",
    "FlashCrowd",
    "HotParamFlood",
    "LoopResult",
    "OfferedEvent",
    "OperatingPoint",
    "ServiceBackend",
    "ServiceModel",
    "SkewedKeys",
    "TrafficGenerator",
    "TunerConfig",
    "WorkloadSpec",
    "ZipfKeys",
    "drive_asgi",
    "drive_client",
    "drive_gateway",
    "drive_grpc",
    "drive_streaming",
    "flash_crowd_2x",
    "run_closed_loop",
    "sim_default_op",
    "workload_slos",
]
