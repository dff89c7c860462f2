"""Pallas wave kernels: one fused ``pl.pallas_call`` per grouped wave.

The paper's §3.2 performance argument is that a wave's tasks should run
out of fast on-chip memory (the per-core MPBs) instead of round-tripping
every operand through shared DRAM.  The staged executor already fuses a
wavefront's identical tile tasks into one ``vmap(fn)`` program; this
module goes one level down: an eligible group lowers into a *single*
Pallas kernel whose grid axis is the task axis — ``grid=(n_tasks,)`` —
and whose ``BlockSpec``s map each task's block footprint onto the stacked
tile storage.  Grid step ``t`` sees exactly task ``t``'s operand tiles in
kernel-local memory (the modern analogue of staging through the MPB), the
task body runs unchanged on the per-task views, and outputs are written
back through the output ``BlockSpec``s — tile loads/stores happen in
on-chip memory instead of one HBM round trip per vmap lane.

Selection is ``RuntimeConfig.kernel_backend``: ``"xla"`` (the default) is
the group program (``vmap`` in one jit), ``"pallas"`` tries this lowering per
group and *automatically falls back* to the XLA path for groups it cannot
take — :func:`eligibility` names the structural reasons (single-task
group, non-rectangular footprint, mixed dtypes, grid overflow, per-step
blocks over the VMEM budget, ...), and :func:`compile_wave_kernel` names
a body the TPU compiler refuses (``"compile_refused"``: ``trsm``'s
``triangular_solve``, Jacobi's scatter and the FFT bodies have no Mosaic
lowering).  The executor counts each fallback by reason in
``RuntimeStats.kernel_fallbacks``/``kernel_fallback_reasons`` and emits a
``kernel_dispatch`` event carrying backend, reason and the compiler's
first error line.  Only lowering and compiling are guarded: the call of a
compiled kernel runs unguarded, so a fault on the device propagates.

Bit-exactness contract — interpreter only: under the Pallas interpreter
(``interpret=True``, the CPU test matrix) the built kernel, always
wrapped in ``jax.jit``, traces the task body into the same XLA ops per
task as the ``jit(vmap(fn))`` reference, so results are bitwise equal to
the staged path (pinned by the differential fuzz harness); *eager*
execution is excluded because CPU eager dot products differ from
compiled ones in the last ulp.  On the TPU, Mosaic compiles the body by
its own rules, and the two paths agree to the precision of the body's
ops, not bit for bit.

On hardware without a Pallas backend (the CPU test matrix), the kernel
runs under ``pl.pallas_call(..., interpret=True)`` — forced by the
``REPRO_PALLAS_INTERPRET=1`` env flag in CI and auto-enabled whenever the
default jax backend is not TPU (:func:`interpret_mode`).
"""
from __future__ import annotations

import os
from typing import Callable, Sequence

import jax
import numpy as np

from .graph import TaskDescriptor, normalize_outputs

__all__ = ["MAX_GRID_TASKS", "VMEM_BUDGET_BYTES", "WaveKernelError",
           "group_signature", "eligibility", "interpret_mode",
           "infer_out_structs", "build_wave_kernel", "compile_wave_kernel"]

# One pallas grid dimension per fused wave: groups larger than this take
# the XLA fallback ("grid_overflow").  The real bound is the compiler's
# grid-dimension limit (2^16 programs on current TPU lowerings); tests
# monkeypatch this down to exercise the overflow path cheaply.
MAX_GRID_TASKS = 65536

# Scoped VMEM a fused grid step may fill: the TPU compiler's default
# scoped-VMEM limit on v5e (16 MiB; the chip has 128 MiB of VMEM in all).
# The pipeline double-buffers every per-step input and output block, so a
# group whose 2 x (input + output) block bytes exceed this is refused by
# the compiler ("RESOURCE_EXHAUSTED ... vmem": 8 gemm tasks on 1024^2 f32
# tiles need 32 MiB) and is named "vmem_budget" before it gets there.
VMEM_BUDGET_BYTES = 16 * 2**20


class WaveKernelError(RuntimeError):
    """A group passed eligibility but its kernel could not be built: the
    body does not trace (``reason="lowering_failed"``) or the compiler
    refused the kernel (``"compile_refused"``).  ``detail`` is the first
    line of the underlying error.  The caller takes the XLA fallback under
    ``reason``, where a genuine task-body error resurfaces unchanged."""

    def __init__(self, reason: str, detail: str):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason
        self.detail = detail


def _first_line(e: BaseException, limit: int = 300) -> str:
    text = str(e).strip()
    line = text.splitlines()[0] if text else type(e).__name__
    return line[:limit]


def group_signature(td: TaskDescriptor) -> tuple:
    """The wave-grouping key: function identity plus the *structure* of
    the footprint and the firstprivate values (shapes/dtypes, never the
    values themselves) — tasks that differ only in region contents or
    index values share one batched dispatch.

    Lives here (not on the executor) because it is the contract shared by
    three consumers that must never drift: the staged executor's group
    builder, this module's eligibility check (which assumes a group is
    structurally homogeneous and so inspects only ``group[0]``), and the
    DES's fused-wave predictor (``sim.py``)."""
    parts: list = [td.fn]
    for m in td.args:
        parts.append((type(m).__name__, m.region.shape,
                      str(m.region.array.dtype)))
    for v in td.values:
        # structure only, no device transfer on the dispatch critical
        # path; the canonical dtype (what jnp.asarray will stage the
        # value to) is the key, so a Python float and an np.float32
        # from different spawn sites still share one dispatch
        dt = jax.dtypes.canonicalize_dtype(np.result_type(v))
        parts.append(("firstprivate", np.shape(v), str(dt)))
    return tuple(parts)


def eligibility(group: Sequence[TaskDescriptor]) -> str | None:
    """Can this group lower into one fused pallas grid?  ``None`` means
    eligible; otherwise the named fallback reason recorded in
    ``RuntimeStats.kernel_fallbacks`` and the ``kernel_dispatch`` event.

    Groups come pre-homogenized by :func:`group_signature`, so structure
    checks read ``group[0]`` only.  Reasons:

    * ``"single_task"``    — a 1-task group; a fused grid buys nothing
      over the plain jitted call and TPU grids dislike degenerate dims.
    * ``"grid_overflow"``  — more tasks than :data:`MAX_GRID_TASKS`.
    * ``"non_rectangular"``— a footprint region that is not a rank-2
      rectangle of tiles; the BlockSpec tiling implemented here covers
      the paper's gemm/jacobi bodies (2-D static block footprints).
    * ``"mixed_dtype"``    — operand/output regions disagree on dtype;
      one fused kernel would need per-operand memory spaces the TPU
      lowering does not give us.
    * ``"nonscalar_firstprivate"`` — an index parameter that is not a
      scalar; scalars ride the grid as ``(n,)`` SMEM vectors, arrays would
      need their own footprint analysis.
    * ``"vmem_budget"``    — one grid step's input and output blocks,
      double-buffered, exceed :data:`VMEM_BUDGET_BYTES`.
    """
    if len(group) == 1:
        return "single_task"
    if len(group) > MAX_GRID_TASKS:
        return "grid_overflow"
    td = group[0]
    dtypes = set()
    for m in td.args:
        spec = m.region.footprint_spec()
        if spec.rank != 2:
            return "non_rectangular"
        dtypes.add(spec.dtype)
    if len(dtypes) > 1:
        return "mixed_dtype"
    for v in td.values:
        if np.shape(v) != ():
            return "nonscalar_firstprivate"
    step_bytes = sum(m.region.nbytes for m in td.args if m.READS) + \
        sum(m.region.nbytes for m in td.args if m.WRITES)
    if 2 * step_bytes > VMEM_BUDGET_BYTES:
        return "vmem_budget"
    return None


def interpret_mode() -> bool:
    """Run the kernel under the Pallas interpreter?  Forced on by
    ``REPRO_PALLAS_INTERPRET=1`` (the CI CPU matrix), auto-enabled off
    TPU where no Pallas lowering exists.  The bit-exactness contract
    with the vmap path holds under the interpreter only."""
    if os.environ.get("REPRO_PALLAS_INTERPRET", "") == "1":
        return True
    return jax.default_backend() != "tpu"


def infer_out_structs(fn: Callable, in_structs: Sequence[jax.ShapeDtypeStruct],
                      n_out: int, label: str) -> list[jax.ShapeDtypeStruct]:
    """Abstractly trace one task's body on its per-task operand structure
    to learn the output shapes/dtypes the fused kernel must declare.
    Tracing the *body* (not the region metadata) means a body whose
    result dtype differs from its output region's dtype still lowers to
    exactly what the vmap path computes — the region store converts on
    commit, identically on both paths."""
    try:
        out = jax.eval_shape(fn, *in_structs)
    except Exception as e:             # untraceable body -> XLA fallback
        raise WaveKernelError("lowering_failed",
                              f"{label}: {_first_line(e)}") from e
    outs = normalize_outputs(out, n_out, label)
    structs = []
    for o in outs:
        if not hasattr(o, "shape") or not hasattr(o, "dtype"):
            raise WaveKernelError("lowering_failed",
                                  f"{label}: non-array output {type(o)}")
        structs.append(jax.ShapeDtypeStruct(tuple(o.shape), o.dtype))
    return structs


def _task_spec(elt_shape: tuple, pl, pltpu):
    """The BlockSpec mapping grid step ``t`` onto task ``t``'s slice of a
    stacked operand: block ``(1, *elt_shape)`` at block index ``(t, 0, 0)``
    — each grid step sees exactly its own task's tiles in kernel-local
    memory.  Scalars (firstprivate indices) stack to an ``(n,)`` vector
    that sits whole in SMEM; step ``t`` reads element ``t`` (a rank-1
    ``(1,)`` VMEM block is not a tiling the TPU compiler accepts)."""
    if elt_shape == ():
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    zeros = (0,) * len(elt_shape)
    return pl.BlockSpec((1, *elt_shape), lambda t, _z=zeros: (t, *_z))


def build_wave_kernel(fn: Callable, n_tasks: int,
                      in_structs: Sequence[jax.ShapeDtypeStruct],
                      out_structs: Sequence[jax.ShapeDtypeStruct],
                      *, interpret: bool, label: str = "") -> Callable:
    """Lower one eligible group into a jitted fused dispatch.

    Returns ``call(*stacked_ins)`` where every stacked operand/result has
    the task axis first (the staged stacking order: READS args then
    firstprivate values); like a task body it returns a bare array for
    one output and a tuple for several.  Inside the kernel, grid step
    ``t`` drops the unit task axis (``ref[0]``) or reads its scalar
    (``ref[t]``), runs the unchanged task body on its per-task tile
    views, and writes each output back through its own BlockSpec — one
    ``pallas_call`` replaces ``n_tasks`` logical dispatches."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_in = len(in_structs)
    n_out = len(out_structs)
    scalar = [tuple(s.shape) == () for s in in_structs]

    def kernel(*refs):
        t = pl.program_id(0)
        ins = [r[t] if sc else r[0] for r, sc in zip(refs[:n_in], scalar)]
        res = normalize_outputs(fn(*ins), n_out, label)
        for o, v in zip(refs[n_in:], res):
            o[0] = v

    out_specs = [_task_spec(tuple(s.shape), pl, pltpu) for s in out_structs]
    out_shape = [jax.ShapeDtypeStruct((n_tasks, *s.shape), s.dtype)
                 for s in out_structs]
    call = pl.pallas_call(
        kernel,
        grid=(n_tasks,),
        in_specs=[_task_spec(tuple(s.shape), pl, pltpu) for s in in_structs],
        out_specs=out_specs[0] if n_out == 1 else tuple(out_specs),
        out_shape=out_shape[0] if n_out == 1 else tuple(out_shape),
        interpret=interpret,
    )
    return jax.jit(call)


def compile_wave_kernel(fn: Callable, stacked: Sequence, n_out: int, *,
                        interpret: bool, label: str = "") -> Callable:
    """Build and compile, ahead of time, the fused kernel for one group
    whose stacked operands are ``stacked``.  Returns the compiled
    executable; raises :class:`WaveKernelError` when the body does not
    trace (``"lowering_failed"``) or when lowering or compiling it is
    refused (``"compile_refused"`` — an op without a Mosaic lowering,
    blocks over the VMEM limit, ...).  Nothing here runs the kernel: the
    caller's call of the result is a device call like any other, and its
    errors are not fallbacks."""
    in_structs = [jax.ShapeDtypeStruct(tuple(x.shape[1:]), x.dtype)
                  for x in stacked]
    out_structs = infer_out_structs(fn, in_structs, n_out, label)
    n_tasks = int(stacked[0].shape[0])
    try:
        jitted = build_wave_kernel(fn, n_tasks, in_structs, out_structs,
                                   interpret=interpret, label=label)
        return jitted.lower(*stacked).compile()
    except Exception as e:
        raise WaveKernelError("compile_refused",
                              f"{label}: {_first_line(e)}") from e
