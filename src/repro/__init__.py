"""BDDT-SCC reproduction: task-parallel dataflow runtime + multi-pod JAX
LM framework.  See README.md / DESIGN.md / EXPERIMENTS.md.

The canonical import surface (docs/API.md) — batch programs::

    from repro import RuntimeConfig, TaskRuntime, task, wait_on

and serving loops::

    from repro.serve import ServeConfig, Session

Deeper modules (``repro.core.*``, ``repro.obs``, ``repro.ckpt``) stay
importable for extension work, but examples, benchmarks and docs only
use the names re-exported here.
"""

from .core import (AccessMode, BlockArray, DEP_MANAGERS, DEP_PUMPS,
                   EXECUTORS, ExecutorKind, DepManagerKind, DepPumpKind,
                   Executor, In, InOut, KERNEL_BACKENDS, KernelBackend,
                   Out, PLACEMENTS, PlacementKind, Region, RuntimeConfig,
                   RuntimeStats, SCHEDULING_POLICIES, STATS_SCHEMA,
                   SchedulingPolicy, TaskFuture, TaskRuntime,
                   current_runtime, task, wait_on)

__version__ = "1.0.0"

__all__ = [
    # entry points
    "TaskRuntime", "task", "wait_on", "current_runtime",
    # data + footprints
    "BlockArray", "Region", "AccessMode", "In", "Out", "InOut",
    # configuration + results
    "RuntimeConfig", "RuntimeStats", "STATS_SCHEMA", "TaskFuture",
    # typed configuration choices
    "ExecutorKind", "DepManagerKind", "DepPumpKind", "SchedulingPolicy",
    "PlacementKind", "KernelBackend", "EXECUTORS", "DEP_MANAGERS",
    "DEP_PUMPS", "SCHEDULING_POLICIES", "PLACEMENTS", "KERNEL_BACKENDS",
    # extension surface
    "Executor",
    "__version__",
]
