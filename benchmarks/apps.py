"""The paper's five applications as *real* task-graph programs on the
runtime (the DES in ``paper_suite`` simulates SCC timing; these execute
the same dataflow with actual JAX kernels and verify numerics).

Each app's kernels are declared once with ``@task`` footprints and called
naturally inside the runtime scope — the OmpSs front-end the paper
describes.  Index-parameterized kernels (fft's tile transpose, jacobi's
halo stencil) take their offsets as ``firstprivate`` value parameters, so
one shared function covers every tile and the staged executor batches a
whole wavefront into a single vmap dispatch.  Sizes are parameters —
tests use laptop-scale instances; the DES workloads carry the paper's
§4.2 sizes.  ``seed`` makes each app's input data (the default 0 keeps
the data every test and baseline was written against).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import TaskRuntime, task
from repro.kernels.black_scholes import ops as bs_ops
from repro.kernels.cholesky import ops as chol_ops
from repro.kernels.jacobi import ref as jac_ref
from repro.kernels.matmul import ops as mm_ops


# ---------------------------------------------------------------------------
@task(in_=("spot", "strike", "t", "rate", "vol"), out=("call", "put"))
def _price(spot, strike, t, rate, vol, call=None, put=None):
    return bs_ops.black_scholes(spot, strike, t, rate, vol)


def black_scholes_app(rt: TaskRuntime, n_options: int = 8192,
                      task_options: int = 512, verify: bool = True,
                      seed: int = 0):
    """Independent pricing tasks — embarrassingly parallel (§4.2)."""
    rng = np.random.default_rng(seed)
    cols = {
        "spot": rng.uniform(10, 200, n_options).astype(np.float32),
        "strike": rng.uniform(10, 200, n_options).astype(np.float32),
        "t": rng.uniform(0.1, 2.0, n_options).astype(np.float32),
        "rate": np.full(n_options, 0.03, np.float32),
        "vol": rng.uniform(0.1, 0.6, n_options).astype(np.float32),
    }
    with rt.scope():
        arrays = {k: rt.from_array(v, (task_options,), name=k)
                  for k, v in cols.items()}
        call = rt.zeros((n_options,), (task_options,), name="call")
        put = rt.zeros((n_options,), (task_options,), name="put")

        futures = [
            _price(arrays["spot"][i], arrays["strike"][i], arrays["t"][i],
                   arrays["rate"][i], arrays["vol"][i], call[i], put[i])
            for i in range(n_options // task_options)]
        if verify:
            # independent tasks: every future resolves without a barrier
            rt.wait_all(futures)
        else:
            # same synchronization surface without result() — the
            # timing-only sim executor never computes task values
            rt.wait_on(call, put)
    if not verify:
        return call, put
    want_c, want_p = bs_ops.black_scholes(
        *[jnp.asarray(cols[k])
          for k in ("spot", "strike", "t", "rate", "vol")])
    np.testing.assert_allclose(np.asarray(call.gather()),
                               np.asarray(want_c), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(put.gather()),
                               np.asarray(want_p), rtol=1e-5, atol=1e-3)
    return call, put


# ---------------------------------------------------------------------------
@task(inout="c", in_=("x", "y"))
def _gemm(c, x, y):
    return mm_ops.matmul(x, y, c)


def matmul_app(rt: TaskRuntime, n: int = 256, tile: int = 64,
               verify: bool = True, seed: int = 0):
    g = n // tile
    rng = np.random.default_rng(seed + 1)
    a = rng.standard_normal((n, n), dtype=np.float32)
    b = rng.standard_normal((n, n), dtype=np.float32)
    with rt.scope():
        A = rt.from_array(a, (tile, tile), name="A")
        B = rt.from_array(b, (tile, tile), name="B")
        C = rt.zeros((n, n), (tile, tile), name="C")

        for i in range(g):
            for j in range(g):
                for k in range(g):
                    _gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
    if verify:
        np.testing.assert_allclose(np.asarray(C.gather()), a @ b,
                                   rtol=2e-4, atol=2e-4)
    return C


# ---------------------------------------------------------------------------
@task(in_=("re", "im"), out=("re_out", "im_out"))
def _row_fft(re, im, re_out=None, im_out=None):
    out = jnp.fft.fft(re + 1j * im, axis=1)
    return out.real.astype(jnp.float32), out.imag.astype(jnp.float32)


def fft2d_app(rt: TaskRuntime, n: int = 256, row_block: int = 32,
              tile: int = 32, verify: bool = True, seed: int = 0):
    """2-D FFT exactly as the paper structures it: row-FFT tasks on
    32-row blocks, 32x32 tiled transpose tasks, row-FFT tasks again.
    Complex data as separate re/im planes."""
    rng = np.random.default_rng(seed + 2)
    x = (rng.standard_normal((n, n)) +
         1j * rng.standard_normal((n, n))).astype(np.complex64)

    with rt.scope():
        Re = rt.from_array(x.real.astype(np.float32), (row_block, n),
                           name="Re")
        Im = rt.from_array(x.imag.astype(np.float32), (row_block, n),
                           name="Im")
        Re1 = rt.zeros((n, n), (row_block, n), name="Re1")
        Im1 = rt.zeros((n, n), (row_block, n), name="Im1")
        ReT = rt.zeros((n, n), (tile, tile), name="ReT")
        ImT = rt.zeros((n, n), (tile, tile), name="ImT")
        Re2 = rt.zeros((n, n), (row_block, n), name="Re2")
        Im2 = rt.zeros((n, n), (row_block, n), name="Im2")

        g = n // row_block
        for r in range(g):
            _row_fft(Re[r, 0], Im[r, 0], Re1[r, 0], Im1[r, 0])
        assert row_block == tile, \
            "paper's §4.2 uses 32-row blocks + 32x32 tiles"
        gt = n // tile

        # one shared TaskFn for every tile: the (row, col) offsets are
        # firstprivate values carried in the descriptor, so a wavefront
        # of transpose tasks shares one batched vmap dispatch on the
        # staged executor instead of jit-compiling per tile
        @task(in_=("re_block", "im_block"), out=("re_t", "im_t"),
              firstprivate=("r0", "c0"))
        def transpose_tile(re_block, im_block, r0, c0,
                           re_t=None, im_t=None):
            re = jax.lax.dynamic_slice(re_block, (r0, c0), (tile, tile))
            im = jax.lax.dynamic_slice(im_block, (r0, c0), (tile, tile))
            return re.T, im.T

        for i in range(gt):
            for j in range(gt):
                # source tile (i, j) lives in row-block i*tile//row_block
                rb = (i * tile) // row_block
                r0 = i * tile - rb * row_block
                transpose_tile(Re1[rb, 0], Im1[rb, 0], r0, j * tile,
                               ReT[j, i], ImT[j, i])
        for r in range(g):
            # row r of the transposed matrix spans tile-rows of ReT
            t0 = (r * row_block) // tile
            t1 = ((r + 1) * row_block - 1) // tile
            _row_fft(ReT[t0:t1 + 1, :], ImT[t0:t1 + 1, :],
                     Re2[r, 0], Im2[r, 0])
        rt.barrier()
    if verify:
        got = np.asarray(Re2.gather()) + 1j * np.asarray(Im2.gather())
        want = np.fft.fft2(x).T   # pipeline output stays transposed
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-1)
    return Re2, Im2


# ---------------------------------------------------------------------------
def jacobi_app(rt: TaskRuntime, n: int = 256, tile: int = 64,
               iters: int = 4, verify: bool = True, seed: int = 0):
    """Tiled 5-point Jacobi: each task reads its tile plus the available
    neighbour tiles (one footprint region) and writes its tile — the halo
    dependencies the paper's stencil workloads exhibit."""
    rng = np.random.default_rng(seed + 3)
    x0 = rng.standard_normal((n, n)).astype(np.float32)
    g = n // tile
    with rt.scope():
        bufs = [rt.from_array(x0, (tile, tile), name="J0"),
                rt.zeros((n, n), (tile, tile), name="J1")]

        # one shared TaskFn: the tile's offset inside its halo is a
        # firstprivate value, so tasks group by halo *shape* only
        # (corner/edge/interior) and each group batches into one vmap
        # dispatch on the staged executor
        @task(in_="halo", out="dest", firstprivate=("r0", "c0"))
        def stencil(halo, r0, c0, dest=None):
            full = jac_ref.jacobi_step(halo)
            return jax.lax.dynamic_slice(full, (r0, c0), (tile, tile))

        for it in range(iters):
            s, d = bufs[it % 2], bufs[(it + 1) % 2]
            for i in range(g):
                for j in range(g):
                    i0, i1 = max(i - 1, 0), min(i + 2, g)
                    j0, j1 = max(j - 1, 0), min(j + 2, g)
                    stencil(s[i0:i1, j0:j1], (i - i0) * tile,
                            (j - j0) * tile, d[i, j])
        rt.barrier()
    if verify:
        want = np.asarray(jac_ref.jacobi(jnp.asarray(x0), iters=iters))
        got = np.asarray(bufs[iters % 2].gather())
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    return bufs[iters % 2]


# ---------------------------------------------------------------------------
@task(inout="a")
def _potrf(a):
    return chol_ops.potrf(a)


@task(in_="l", inout="a")
def _trsm(l, a):
    return chol_ops.trsm(l, a)


@task(inout="c", in_=("x", "y"))
def _update(c, x, y):
    return chol_ops.update(c, x, y)


def cholesky_app(rt: TaskRuntime, n: int = 256, tile: int = 64,
                 verify: bool = True, seed: int = 0):
    g = n // tile
    rng = np.random.default_rng(seed + 4)
    m = rng.standard_normal((n, n)).astype(np.float32)
    spd = m @ m.T + n * np.eye(n, dtype=np.float32)
    with rt.scope():
        A = rt.from_array(spd, (tile, tile), name="Chol")

        for k in range(g):
            _potrf(A[k, k])
            for i in range(k + 1, g):
                _trsm(A[k, k], A[i, k])
            for i in range(k + 1, g):
                for j in range(k + 1, i + 1):
                    _update(A[i, j], A[i, k], A[j, k])
        rt.barrier()
    if verify:
        got = np.tril(np.asarray(A.gather()))
        want = np.asarray(jnp.linalg.cholesky(jnp.asarray(spd)))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    return A


APPS = {
    "black_scholes": black_scholes_app,
    "matmul": matmul_app,
    "fft": fft2d_app,
    "jacobi": jacobi_app,
    "cholesky": cholesky_app,
}


def run_app(name: str, executor: str = "staged", *,
            verify: bool | None = None, app_kwargs: dict | None = None,
            **config_overrides):
    """Run one paper app on a fresh runtime and return its RuntimeStats.

    Every app self-verifies its numerics against the reference kernel, so
    a returned stats object means the run was correct — this is what the
    report tables and the executor-comparison tests call.  For
    ``executor="sharded"`` install a mesh first (``repro.dist.use_mesh``)
    to exercise the per-owner dispatch; without one the executor falls
    back to single-device staged dispatch and still reports home traffic.

    ``verify=None`` means "verify unless the executor cannot": the
    timing-only ``"sim"`` executor never computes task values, so its runs
    skip the numeric check (and its stats carry ``predicted_total_s``).
    ``app_kwargs`` forwards problem sizes to the app (the benchmark
    suites shrink them for smoke runs).
    """
    from repro import RuntimeConfig

    if verify is None:
        verify = executor != "sim"
    config_overrides.setdefault("n_workers", 4)
    rt = TaskRuntime(RuntimeConfig(executor=executor, **config_overrides))
    try:
        APPS[name](rt, verify=verify, **(app_kwargs or {}))
        return rt.stats()
    finally:
        rt.shutdown()
