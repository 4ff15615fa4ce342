"""The Pallas grid-statistic kernel (``monthly_mean``), interpreted on the
CPU: against ``_map_task_numpy`` on the same drawn columns, against the
float64 restatement of ``bench/reference.py`` on its threefry draws, wave
against per-task bit identity, and the service's ``grid_kernel_tasks``
counter, which says how often the kernel computed a task's partial.

Blocks are small and Netflix-shaped: ragged rows wrap-padded to the
block's power-of-two width, months that run past both ends of the grid,
and draws that land on one column more than once."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from repro.core import scheduler as sch
from repro.core import subsample as ss
from repro.kernels import grid_stat as gs
from repro.kernels import ops
from repro.kernels.subsample_gather import to_lane_layout
from repro.platform import MOMENTS, PlatformService, PlatformSpec, WaveOptions
from repro.platform import compute as pc

GRID = 120
# float32 sums, in kernel order, of a few hundred ratings a bin against
# float64 (the RTOL of tests/test_grid_stat_service.py)
RTOL = 1e-5


def _block(rows: int, longest: int, seed: int):
    """One task's ``(ratings, months)`` block: ``rows`` ragged rows, the
    first ``longest`` long, wrap-padded to the block width (``longest``
    rounded up to a power of two), as the platform builds it."""
    rng = np.random.default_rng(seed)
    samples, months = {}, {}
    for i in range(rows):
        n = int(rng.integers(max(1, longest // 3), longest + 1)
                if i else longest)
        samples[i] = (rng.integers(1, 6, n)
                      + rng.standard_normal(n) / 8).astype(np.float32)
        # months past both ends of the grid: the kernel clips them
        months[i] = rng.integers(-4, GRID + 4, n).astype(np.int32)
    return pc.build_block(samples, months, list(range(rows)),
                          list(range(rows)), rows)


def _kernel(blocks, slots, idx, grid=GRID):
    """The kernel on ``blocks`` (a list of ``(ratings, months)``) stacked
    into a lane-layout arena, for the wave's ``slots`` and draws."""
    data = jnp.asarray(np.stack([to_lane_layout(b) for b, _ in blocks]))
    months = jnp.asarray(np.stack([to_lane_layout(m) for _, m in blocks]))
    run = jax.jit(lambda d, m, s, i: ops.grid_stat_lanes(d, m, s, i,
                                                         grid=grid))
    return np.asarray(run(data, months, jnp.asarray(slots, jnp.int32),
                          jnp.asarray(idx, jnp.int32)))


def _float64(block, months, idx, grid=GRID):
    """Sum and count a bin of the draws ``idx [rows, D]``, in float64."""
    drawn = np.take_along_axis(block, idx, axis=1).astype(np.float64)
    cells = np.clip(np.take_along_axis(months, idx, axis=1), 0, grid - 1)
    return (np.bincount(cells.ravel(), drawn.ravel(), minlength=grid),
            np.bincount(cells.ravel(), minlength=grid).astype(np.float64))


def _assert_partial(got_sum, got_count, want_sum, want_count):
    assert np.array_equal(np.asarray(got_count, np.float64), want_count)
    np.testing.assert_allclose(np.asarray(got_sum, np.float64), want_sum,
                               rtol=0, atol=RTOL * np.max(np.abs(want_sum)))


# -- the kernel against map_task's NumPy path, on the same draws -------------


@pytest.mark.parametrize("rows,longest,draws,draw_size", [
    (8, 256, 8, 32),          # netflix_low's draws, 2 chunks of 128
    (5, 200, 3, 10),          # 30 draws: one chunk, mostly padding
    (11, 96, 2, 100),         # rows prime above the row group: 1 a step
    (6, 1000, 4, 70),         # 1024 wide; 280 draws: a part chunk
])
def test_kernel_matches_map_task_numpy(rows, longest, draws, draw_size):
    """``_map_task_numpy`` draws its columns from NumPy's generator; the
    kernel is handed the same columns, so the partials agree to float32
    rounding, and the counts exactly."""
    wl = dataclasses.replace(ss.NETFLIX_LOW, draws=draws,
                             draw_size=draw_size)
    block, months = _block(rows, longest, seed=rows)
    seed = 1234 + rows
    want = pc._map_task_numpy(block, months, seed, wl)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, block.shape[1], (draws, rows, draw_size))
    idx = np.swapaxes(idx, 0, 1).reshape(rows, -1)
    out = _kernel([(block, months)], [0], idx[None])
    assert np.array_equal(out[0, 1], want["count"])
    np.testing.assert_allclose(out[0, 0], want["sum"], rtol=0,
                               atol=RTOL * np.max(np.abs(want["sum"])))


def test_kernel_counts_a_column_drawn_many_times():
    """Every draw of a row on one column: that column's rating and
    month, as many times as it was drawn."""
    block, months = _block(4, 128, seed=3)
    idx = np.zeros((4, 256), np.int64)
    idx[:, ::2] = 7
    idx[:, 1::2] = np.arange(4)[:, None] * 3 + 100
    out = _kernel([(block, months)], [0], idx[None])
    _assert_partial(out[0, 0], out[0, 1], *_float64(block, months, idx))
    assert out[0, 1].sum() == idx.size


def test_kernel_clips_months_outside_the_grid():
    block, months = _block(3, 128, seed=4)
    months[0, :] = -50                       # every month of a row below
    months[1, :] = GRID + 50                 # ... and above the grid
    idx = np.random.default_rng(4).integers(0, 128, (3, 64))
    out = _kernel([(block, months)], [0], idx[None])
    want_sum, want_count = _float64(block, months, idx)
    _assert_partial(out[0, 0], out[0, 1], want_sum, want_count)
    assert want_count[0] >= 64 and want_count[GRID - 1] >= 64


def test_kernel_months_past_256_take_two_digits():
    grid = 300
    block, months = _block(4, 512, seed=5)
    months = np.random.default_rng(5).integers(-9, grid + 9, months.shape)
    idx = np.random.default_rng(6).integers(0, 512, (4, 200))
    out = _kernel([(block, months.astype(np.int32))], [0], idx[None],
                  grid=grid)
    _assert_partial(out[0, 0], out[0, 1],
                    *_float64(block, months, idx, grid=grid))


def test_kernel_splits_wide_rows_into_column_blocks(monkeypatch):
    """A row wider than ``MAX_BLOCK`` lane rows is read in column
    blocks, one row a step; each draw is binned in its own block only."""
    monkeypatch.setattr(gs, "MAX_BLOCK", 2)
    assert gs.row_blocking(6, 8) == (1, 2)
    block, months = _block(6, 1024, seed=7)
    idx = np.random.default_rng(7).integers(0, 1024, (6, 160))
    out = _kernel([(block, months)], [0], idx[None])
    _assert_partial(out[0, 0], out[0, 1], *_float64(block, months, idx))


def test_wave_reads_each_task_at_its_slot_and_pads_lanes():
    """Three tasks in a wave of five lanes: the slots pick the blocks in
    any order, and the two padding lanes repeat the first slot."""
    blocks = [_block(4, 300, seed=10 + i) for i in range(3)]
    assert {b.shape for b, _ in blocks} == {(4, 512)}
    slots = [2, 0, 1, 2, 2]
    idx = np.random.default_rng(9).integers(0, 512, (5, 4, 96))
    out = _kernel(blocks, slots, idx)
    for lane, slot in enumerate(slots):
        _assert_partial(out[lane, 0], out[lane, 1],
                        *_float64(*blocks[slot], idx[lane]))


# -- the platform's path against the reference's threefry draws ---------------


@pytest.mark.parametrize("workload", [ss.NETFLIX_LOW,
                                      dataclasses.replace(ss.NETFLIX_HIGH,
                                                          draw_size=200)],
                         ids=["netflix_low", "netflix_high_cut"])
def test_task_matches_the_reference_draws(workload):
    """``run_map_task`` on the ``jnp`` engine runs ``monthly_mean`` in the
    kernel, on the indices ``bench/reference.py`` draws for the task's
    seed: ``PRNGKey(seed)``, ``(draws, rows, draw_size)``, threefry."""
    block, months = _block(6, 700, seed=11)
    seed = 2_000_000_011
    rows, width = block.shape
    idx = reference.draw([seed], (workload.draws, rows, workload.draw_size),
                         width)[0]
    idx = np.swapaxes(idx, 0, 1).reshape(rows, -1)
    got = pc.run_map_task(block, months, seed, workload, "jnp")
    _assert_partial(got["sum"], got["count"], *_float64(block, months, idx))


def test_wave_bit_identical_to_its_tasks_alone():
    """A wave with padding lanes against each task as a wave of one."""
    blocks = [_block(8, 256, seed=20 + i) for i in range(5)]
    tasks = [sch.Task(task_id=i, sample_ids=(i,), size_bytes=1.0)
             for i in range(5)]
    arena = pc.BlockArena.pack(tasks, lambda t: blocks[t.task_id][0].shape,
                               lambda t: blocks[t.task_id],
                               *pc.arena_layout("monthly_mean", "jnp"))
    assert len(arena.keys()) == 1
    seeds = np.asarray([5, 6, 7, 8, 9], np.int32)
    wave = pc.run_map_wave(arena, tasks, seeds, ss.NETFLIX_LOW, "jnp",
                           pad_to=8)
    for task, seed, got in zip(tasks, seeds, wave):
        alone = pc.run_map_task(*blocks[task.task_id], int(seed),
                                ss.NETFLIX_LOW, "jnp")
        for key in ("sum", "count"):
            assert np.array_equal(got[key], alone[key]), key


def test_only_monthly_mean_on_jnp_engages_the_kernel():
    assert pc.grid_kernel("monthly_mean", "jnp")
    assert not pc.grid_kernel("alod", "jnp")
    assert not pc.grid_kernel("moments", "pallas")
    assert not pc.grid_kernel("monthly_mean", "numpy")
    assert pc.arena_layout("monthly_mean", "jnp") == (True, True)
    assert pc.arena_layout("alod", "jnp") == (False, True)
    assert pc.arena_layout("moments", "pallas") == (True, False)


# -- the service's counter ------------------------------------------------------


def _dataset(n: int, length: int, seed: int):
    rng = np.random.default_rng(seed)
    samples = {i: rng.standard_normal(length).astype(np.float32)
               for i in range(n)}
    months = {i: rng.integers(0, 12, length).astype(np.int32)
              for i in range(n)}
    return samples, months


@pytest.mark.parametrize("waves", ["on", "off"])
def test_grid_kernel_tasks_counts_a_netflix_jobs_tasks(waves):
    samples, months = _dataset(12, 160, seed=1)
    spec = PlatformSpec(knee_bytes=3 * 160 * 4.0,
                        waves=WaveOptions(wave=waves))
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        ticket = svc.submit(handle, ss.NETFLIX_LOW, seed=3)
        ticket.result(timeout=300)
        stats = svc.stats()
    assert ticket.n_tasks == 4
    assert stats["grid_kernel_tasks"] == ticket.n_tasks


@pytest.mark.parametrize("workload", [
    dataclasses.replace(ss.EAGLET, draws=2, draw_size=16), MOMENTS],
    ids=["alod", "moments"])
def test_grid_kernel_tasks_stays_zero_off_monthly_mean(workload):
    samples, months = _dataset(8, 128, seed=2)
    with PlatformService(PlatformSpec(knee_bytes=2 * 128 * 4.0)) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, workload, seed=3).result(timeout=300)
        stats = svc.stats()
    assert stats["grid_kernel_tasks"] == 0
