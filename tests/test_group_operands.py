"""A group program receives each distinct READS tile once.

``StagedExecutor._group_call`` keys every READS region of a group by
array and tile range, materializes each distinct region once, and hands
the group program ``(tiles, values, index)``: the distinct tiles, the
host-stacked firstprivate values, and a static index giving, per READS
position, each task's slot in ``tiles``.  These tests check the operands
the program is called with (not only the counters), that results stay
bit-identical to the sequential oracle, and that the static index adds
no program: every GEMM wave shares one trace.
"""
import numpy as np
import pytest

from chipbench.programs import gemm, potrf
from repro.core import TaskRuntime, task

TILE = 8


def _record_calls(rt, reads: list | None = None) -> list:
    """Record ``(group, (tiles, values, index))`` for every group program
    the runtime's executor dispatches: the operands exactly as the
    program receives them.  With ``reads``, also append per call each
    task's READS values as they stand at the call."""
    ex = rt._exec
    calls = []
    group_call = ex._group_call

    def recording(group, device=None):
        call = group_call(group, device)
        calls.append((list(group), call[1]))
        if reads is not None:
            reads.append([[np.asarray(a.region.materialize())
                           for a in td.args if a.READS] for td in group])
        return call

    ex._group_call = recording
    return calls


def _region_key(region):
    return region.array.array_id, region.ranges


def _gemm_arrays(rt, g: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    n = g * TILE
    return {k: rt.from_array(rng.standard_normal((n, n), dtype=np.float32),
                             (TILE, TILE), name=k) for k in "ABC"}


def _spd(g: int, seed: int = 0) -> np.ndarray:
    n = g * TILE
    m = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    return m @ m.T + n * np.eye(n, dtype=np.float32)


def _solve(executor: str, program, g: int, record: bool = False,
           reads: list | None = None):
    """One solve of ``program`` at grid ``g``: the output, the stats and
    (if ``record``) every group program call."""
    rt = TaskRuntime(executor=executor)
    calls = _record_calls(rt, reads) if record else None
    if program is gemm:
        arrays = _gemm_arrays(rt, g)
    else:
        arrays = {"A": rt.from_array(_spd(g), (TILE, TILE), name="A")}
    with rt.scope():
        program.spawn(arrays, g)
    rt.barrier()
    out = np.asarray(arrays[program.OUTPUT].gather())
    s = rt.stats()
    rt.shutdown()
    return out, s, calls


@pytest.mark.parametrize("g", [2, 4, 8])
def test_gemm_group_program_receives_g2_plus_2g_tiles(g):
    """A GEMM wave ``k`` is one group of ``g^2`` tasks reading
    ``C[i, j]``, ``A[i, k]`` and ``B[k, j]``: its program gets the
    ``g^2`` C tiles, then the ``g`` A tiles, then the ``g`` B tiles, each
    once, and the index points every task at its own three."""
    want, _, _ = _solve("sequential", gemm, g)
    reads = []
    got, _, calls = _solve("staged", gemm, g, record=True, reads=reads)
    np.testing.assert_array_equal(got, want)
    assert len(calls) == g
    for (group, (tiles, values, index)), task_reads in zip(calls, reads):
        assert len(group) == g * g
        assert len(tiles) == g * g + 2 * g
        assert values == ()
        assert index == (
            tuple(range(g * g)),
            tuple(g * g + i for i in range(g) for _ in range(g)),
            tuple(g * g + g + j for _ in range(g) for j in range(g)))
        # every slot holds the tile its task reads there
        for t, task_read in enumerate(task_reads):
            for slots, tile in zip(index, task_read):
                np.testing.assert_array_equal(tiles[slots[t]], tile)


def test_cholesky_grid16_bit_identical_and_diagonal_updates_share_a_slot():
    """The benchmark's Cholesky at grid 16, tile 8: bit-identical to the
    sequential executor.  A diagonal update (``A[i, i] -= A[i, k]
    A[i, k]^T``) reads one tile as ``x`` and ``y``, and it passes once."""
    want, _, _ = _solve("sequential", potrf, 16)
    got, _, calls = _solve("staged", potrf, 16, record=True)
    np.testing.assert_array_equal(got, want)
    diagonal = 0
    for group, (tiles, _, index) in calls:
        keys = {}
        for p, slots in enumerate(index):
            for td, s in zip(group, slots):
                keys.setdefault(s, _region_key(td.args[p].region))
                assert keys[s] == _region_key(td.args[p].region)
        # one slot per distinct region, numbered in first appearance
        assert len(tiles) == len(set(keys.values())) == len(keys)
        assert sorted(keys) == list(range(len(tiles)))
        if group[0].fn is potrf._update.fn:
            for t, td in enumerate(group):
                same = (_region_key(td.args[1].region)
                        == _region_key(td.args[2].region))
                assert same == (index[1][t] == index[2][t])
                diagonal += same
    # grid 16: 120 diagonal updates, all grouped but the last step's,
    # which runs alone
    assert diagonal == sum(range(1, 16)) - 1


@task(inout="c", in_="a")
def _accumulate(c, a):
    return c + a


def test_group_without_repeats_passes_tiles_in_task_order():
    """``C[i] += A[i]``: no region repeats, so the program receives
    every tile once, position-major in task order, with the identity
    index."""
    n = 6
    rng = np.random.default_rng(1)
    a, c = (rng.standard_normal((n * TILE, TILE), dtype=np.float32)
            for _ in range(2))
    rt = TaskRuntime(executor="staged")
    calls = _record_calls(rt)
    with rt.scope():
        A = rt.from_array(a, (TILE, TILE))
        C = rt.from_array(c, (TILE, TILE))
        for i in range(n):
            _accumulate(C[i, 0], A[i, 0])
        rt.barrier()
        got = np.asarray(C.gather())
    rt.shutdown()
    np.testing.assert_array_equal(got, c + a)
    [(group, (tiles, _, index))] = calls
    assert index == (tuple(range(n)), tuple(range(n, 2 * n)))
    want = [c[i * TILE:(i + 1) * TILE] for i in range(n)]
    want += [a[i * TILE:(i + 1) * TILE] for i in range(n)]
    assert len(tiles) == 2 * n
    for tile, w in zip(tiles, want):
        np.testing.assert_array_equal(tile, w)


_traces = []


@task(inout="c", in_=("a", "b"))
def _counted_gemm(c, a, b):
    _traces.append(1)          # runs once per trace of the group program
    return c + a @ b


def test_one_group_program_serves_every_gemm_wave():
    """The index pattern of a GEMM wave does not depend on ``k``: the
    jit traces once for all ``g`` waves and holds one program."""
    g = 4
    rt = TaskRuntime(executor="staged")
    calls = _record_calls(rt)
    A, B, C = _gemm_arrays(rt, g).values()
    del _traces[:]
    with rt.scope():
        for i in range(g):
            for j in range(g):
                for k in range(g):
                    _counted_gemm(C[i, j], A[i, k], B[k, j])
        rt.barrier()
    ex = rt._exec
    rt.shutdown()
    assert len(calls) == g
    assert len({index for _, (_, _, index) in calls}) == 1
    assert len(_traces) == 1
    [program] = ex._vjit.values()
    assert program._cache_size() == 1


@pytest.mark.parametrize("program,counts", [
    (gemm, (12288, 4608, 1)),
    (potrf, (2275, 931, 28)),
], ids=["gemm", "potrf"])
def test_grid16_operand_counters_and_program_count(program, counts):
    """At grid 16: 12288 READS operands and 4608 distinct tiles for GEMM
    (16 groups of 768 and 288), 2275 and 931 for Cholesky.  The distinct
    count is now exactly the tiles handed to group programs.  The group
    programs hold one program for GEMM and 28 for Cholesky's grouped
    shapes, as before the index."""
    operands, distinct, programs = counts
    rt = TaskRuntime(executor="staged")
    calls = _record_calls(rt)
    if program is gemm:
        arrays = _gemm_arrays(rt, 16)
    else:
        arrays = {"A": rt.from_array(_spd(16), (TILE, TILE), name="A")}
    with rt.scope():
        program.spawn(arrays, 16)
    rt.barrier()
    s = rt.stats()
    ex = rt._exec
    rt.shutdown()
    assert (s.group_operand_tiles, s.group_distinct_tiles) == (operands,
                                                               distinct)
    assert sum(len(tiles) for _, (tiles, _, _) in calls) == distinct
    assert sum(len(group) * len(index)
               for group, (_, _, index) in calls) == operands
    assert sum(p._cache_size() for p in ex._vjit.values()) == programs
