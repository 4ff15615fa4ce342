"""Map-task compute engines for the platform driver (thesis §3.1, Fig 1).

The driver resolves ONE engine per job so every backend executes the exact
same per-task computation (this is what makes the threaded and simulated
backends bit-identical for a fixed seed):

  ``pallas``  — the Pallas ``subsample_stats_wave`` kernel (per-row HBM→VMEM
                DMAs + moment accumulation) for the row-subsampling
                ``moments`` statistic; compiled on TPU, interpreted on CPU.
  ``jnp``     — the paper workloads: ``monthly_mean`` in the Pallas
                ``grid_stat_wave`` kernel (the rows read in place, the
                draws selected on the MXU), ``alod`` in the jitted
                ``repro.core.subsample.map_task``.  The statistic picks
                the path (:func:`grid_kernel`); both draw the same
                indices.
  ``numpy``   — pure-NumPy path, only when asked for by name.  Mirrors
                the jnp semantics but draws indices from NumPy's RNG, so
                it is statistically — not bitwise — equivalent to ``jnp``.

Hardware adaptation (DESIGN.md §2): block building pads samples to a
common power-of-two length so one compiled kernel serves every task —
compilation is startup cost (thesis Fig 5), never a per-task cost.

Wave execution (DESIGN.md §7): a *wave* is a batch of same-shape ready
tasks executed in ONE device dispatch.  :class:`BlockArena` packs the
job's padded blocks into a device-resident ``[n_tasks, count, len]`` array
per distinct shape (uploaded once; the kernels' arenas are kept in their
``[n_tasks, count, len/128, 128]`` lane layout);
:func:`run_map_wave` folds per-task seeds in with ``jax.vmap`` / a batched Pallas grid so one compiled kernel
serves the whole wave.  Per-task accumulation order is independent of the
wave partition, so wave and per-task execution are bit-identical for a
fixed seed.

Sharded wave execution (DESIGN.md §11): :class:`ShardedBlockArena`
partitions each shape bucket over a 1-D ``"wave"`` device mesh
(interleaved slot→(device, local-slot) placement, :func:`shard_slot`);
:func:`run_map_wave_sharded` splits a wave into per-device lanes and runs
the SAME per-task math under ``shard_map``, one dispatch for all devices.
Because per-task accumulation never crosses the batch axis and partials
re-enter the reduce tree keyed by task id, sharded results are
bit-identical to single-device execution at every mesh size.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import (Any, Callable, Dict, List, MutableSequence, Optional,
                    Sequence, Tuple)

import numpy as np

from repro.kernels.subsample_gather import to_lane_layout
from repro.platform import telemetry as tel


@dataclasses.dataclass(frozen=True)
class MomentsSpec:
    """Row-subsampling workload whose map task IS the Pallas kernel's
    semantics: each draw gathers ``draw_size`` random *rows* (samples) of
    the task block and accumulates (Σrow, Σrow²)."""

    name: str = "moments"
    statistic: str = "moments"
    draws: int = 8
    draw_size: int = 64
    grid: int = 0             # unused; kept for workload interface parity


MOMENTS = MomentsSpec()


@dataclasses.dataclass
class DispatchStats:
    """Observable device-overhead counters (thesis Fig 5/6 made visible):
    how many device dispatches the map phase issued, how many bytes went
    host→device, and how large each executed wave was.  Per-task execution
    shows ``device_dispatches == n_tasks``; wave execution collapses that
    by roughly the mean wave size."""

    device_dispatches: int = 0
    bytes_uploaded: float = 0.0
    # a list for one-shot jobs; :meth:`bounded` swaps in a capped deque
    wave_sizes: MutableSequence[int] = dataclasses.field(
        default_factory=list)
    # data-plane prefetch pipeline (DESIGN.md §9): how many task fetches
    # were already in flight when their wave executed vs fetched inline
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    # tasks whose partial came from the grid-statistic kernel
    grid_kernel_tasks: int = 0

    @classmethod
    def bounded(cls, max_wave_history: int) -> "DispatchStats":
        """Counters for a long-lived holder (the persistent service):
        dispatches never stop, so only the most recent
        ``max_wave_history`` wave sizes are retained."""
        return cls(wave_sizes=deque(maxlen=max_wave_history))


def wave_supported(engine: str) -> bool:
    """Wave execution batches device dispatches, so it exists only for the
    device engines; numpy and custom map_fns fall back to per-task."""
    return engine in ("pallas", "jnp")


# Auto-wave threshold: waves amortize the fixed per-dispatch tax, which
# only dominates when per-task compute is tiny (the thesis' Fig 5/6 story
# — large tasks amortize their own overhead).  Per-task compute scales
# with the workload's drawn elements; above this many, auto mode stays
# per-task (``wave="on"`` overrides).
WAVE_AUTO_MAX_DRAW = 4096


def wave_profitable(workload) -> bool:
    try:
        return workload.draws * workload.draw_size <= WAVE_AUTO_MAX_DRAW
    except (AttributeError, TypeError):
        return False


def resolve_engine(statistic: str, prefer: str = "auto") -> str:
    """Pick the compute engine once per job (never per task).  Engine
    ``pallas`` is the row-subsample ``moments`` kernel; the grid
    statistics run on engine ``jnp``, which computes ``monthly_mean`` in
    its own Pallas kernel (:func:`grid_kernel`)."""
    if prefer != "auto":
        if prefer == "pallas" and statistic != "moments":
            raise ValueError(
                "engine 'pallas' is the row-subsample 'moments' kernel; "
                f"workload statistic is {statistic!r} — use engine 'jnp' "
                "(or 'auto'), which computes 'monthly_mean' in the Pallas "
                "grid kernel")
        return prefer
    return "pallas" if statistic == "moments" else "jnp"


def grid_kernel(statistic: str, engine: str) -> bool:
    """Whether a task's partial comes from the Pallas grid-statistic
    kernel: ``monthly_mean`` on the ``jnp`` engine.  ``alod`` stays on
    ``map_task``: its per-draw z-score needs each draw's own mean and
    deviation before it bins."""
    return engine == "jnp" and statistic == "monthly_mean"


def arena_layout(statistic: str, engine: str) -> Tuple[bool, bool]:
    """``(lanes, months)`` of a wave arena: the kernels read the lane
    layout, and only the moments kernel has no months plane."""
    if engine == "pallas":
        return True, False
    return grid_kernel(statistic, engine), True


# ---------------------------------------------------------------------------
# Block building — uniform task shapes (thesis §3.2.1 outlier handling)
# ---------------------------------------------------------------------------


def pow2_ceil(n: int) -> int:
    """Round up to a power of two — the padding primitive shared by block
    lengths (:func:`padded_len`) and wave widths.  Kept in sync with
    ``repro.kernels.ops._pow2``."""
    return 1 << (max(n, 1) - 1).bit_length()


def padded_len(longest: int, min_len: int = 0) -> int:
    """The block length ``pad_to_common`` will produce for rows whose
    longest member is ``longest`` — the single source of the padding
    policy (shape keys for warmup/calibration derive from this too)."""
    return pow2_ceil(max(longest, min_len))


def pad_to_common(arrays: List[np.ndarray],
                  min_len: int = 0) -> List[np.ndarray]:
    """Samples are heavy-tailed (§3.2.1 outliers); pad to the block max,
    rounded up to a power of two so jit recompiles stay bounded.
    ``min_len`` forces a job-global length (statistics whose partial shape
    depends on sample length must align across tasks)."""
    n = padded_len(max(a.shape[0] for a in arrays), min_len)
    return [np.pad(a, (0, n - a.shape[0]), mode="wrap")
            if a.shape[0] < n else a for a in arrays]


def partial_pad_len(statistic: str, samples: Dict[int, np.ndarray]) -> int:
    """Job-global pad length: grid statistics (alod/monthly_mean) emit
    fixed-size partials so per-block padding suffices (0); per-column
    statistics (moments) must pad every block to the dataset max."""
    if statistic == "moments":
        return max(a.shape[0] for a in samples.values())
    return 0


def build_block(samples: Dict[int, np.ndarray],
                months: Dict[int, np.ndarray],
                ids: Sequence[int],
                sample_ids: Sequence[int],
                max_count: int,
                pad_len: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Materialize one task's [count, len] block, wrap-padded to the job's
    max task count so one compiled kernel serves the whole job."""
    rows = [samples[ids[i]] for i in sample_ids]
    mrows = [months[ids[i]] for i in sample_ids]
    while len(rows) < max_count:
        rows.append(rows[len(rows) % len(sample_ids)])
        mrows.append(mrows[len(mrows) % len(sample_ids)])
    return (np.stack(pad_to_common(rows, pad_len)),
            np.stack(pad_to_common(mrows, pad_len)))


# ---------------------------------------------------------------------------
# Device-resident block arena (wave execution)
# ---------------------------------------------------------------------------


def _stack_blocks(tasks: Sequence, build: Callable, lanes: bool,
                  with_months: bool
                  ) -> Tuple[np.ndarray, Optional[np.ndarray], int]:
    """Materialize ``build(task)`` for each task straight into one
    preallocated bucket array (no per-task copies held alongside it);
    ``lanes`` stores the blocks in the kernels' lane layout, and
    ``with_months=False`` drops the months plane.  Returns ``(data,
    months, block row length)``."""
    data = months = None
    width = 0
    for i, task in enumerate(tasks):
        block, mo = build(task)
        width = block.shape[-1]
        if lanes:
            block = to_lane_layout(block)
            mo = to_lane_layout(mo) if with_months else None
        if data is None:
            data = np.empty((len(tasks),) + block.shape, block.dtype)
            if with_months:
                months = np.empty((len(tasks),) + mo.shape, mo.dtype)
        data[i] = block
        if months is not None:
            months[i] = mo
    return data, months, width


class BlockArena:
    """The job's padded task blocks, packed per distinct block shape into
    one ``[n_tasks, count, len]`` array and uploaded to the device ONCE.
    For the kernels (``lanes=True``) each bucket is stored in their lane
    layout ``[n_tasks, count, len/128, 128]``, so a wave hands a kernel
    the arena's own rows with no relayout.

    Per-task execution re-uploads every block; the arena replaces that
    with a single upload plus a device-side row gather per wave (the slot
    vector is the only host→device traffic a wave pays).  ``slots`` maps a
    wave of same-shape tasks to rows of its shape bucket.
    """

    def __init__(self):
        self._data: Dict[Any, Any] = {}      # shape key -> stacked blocks
        self._months: Dict[Any, Any] = {}
        self._width: Dict[Any, int] = {}    # shape key -> block row length
        self._slot: Dict[int, Tuple[Any, int]] = {}   # task_id -> (key, row)
        self.nbytes = 0.0

    @classmethod
    def pack(cls, tasks: Sequence, shape_key: Callable, build: Callable,
             lanes: bool = False, months: bool = True) -> "BlockArena":
        """Bucket ``tasks`` by ``shape_key``, materialize each task's
        padded block via ``build(task) -> (block, months)``, stack each
        bucket and upload it once, laid out as :func:`arena_layout`
        says: ``lanes=True`` in the kernels' lane layout, and
        ``months=False`` without the months plane (the moments kernel
        never reads it — packing it would double the upload and skew
        ``bytes_uploaded``)."""
        import jax.numpy as jnp

        arena = cls()
        buckets: Dict[Any, List] = {}
        for task in tasks:
            buckets.setdefault(shape_key(task), []).append(task)
        for key, group in buckets.items():
            arena._put(key, *_stack_blocks(group, build, lanes, months),
                       upload=jnp.asarray)
            for row, task in enumerate(group):
                arena._slot[task.task_id] = (key, row)
        return arena

    def _put(self, key, data: np.ndarray, months: Optional[np.ndarray],
             width: int, *, upload: Callable) -> None:
        self._width[key] = width
        self._data[key] = upload(data)
        self._months[key] = None if months is None else upload(months)
        self.nbytes += float(data.nbytes) + (
            0.0 if months is None else float(months.nbytes))

    def keys(self) -> List[Any]:
        return list(self._data)

    def bucket(self, key) -> Tuple[Any, Any]:
        return self._data[key], self._months[key]

    def bucket_size(self, key) -> int:
        return int(self._data[key].shape[0])

    def width(self, key) -> int:
        """Row length of the bucket's blocks (before any lane padding)."""
        return self._width[key]

    def slots(self, tasks: Sequence) -> Tuple[Any, np.ndarray]:
        """Arena rows for a wave.  Waves are drained same-shape by the
        scheduler, so all tasks must live in one shape bucket."""
        keys = {self._slot[t.task_id][0] for t in tasks}
        assert len(keys) == 1, f"wave spans shape buckets: {keys}"
        key = keys.pop()
        rows = np.asarray([self._slot[t.task_id][1] for t in tasks],
                          np.int32)
        return key, rows


# ---------------------------------------------------------------------------
# Sharded block arena (multi-device wave execution, DESIGN.md §11)
# ---------------------------------------------------------------------------


def shard_slot(index: int, n_dev: int) -> Tuple[int, int]:
    """Interleaved slot→(device, local-slot) indirection: logical bucket
    index ``i`` lives on device ``i % n_dev`` at local slot ``i // n_dev``.

    Interleaving — rather than contiguous blocks per device — is what
    bounds per-device wave occupancy: the scheduler claims waves as
    contiguous FIFO runs of the bucket, and any contiguous run of ``w``
    logical slots touches each device at most ``ceil(w / n_dev)`` times,
    so the per-device kernel width pinned at warmup can never be
    exceeded by a tail or mid-job wave."""
    return index % n_dev, index // n_dev


def unshard_slot(device: int, local: int, n_dev: int) -> int:
    """Inverse of :func:`shard_slot` (exact round-trip for any
    ``0 <= device < n_dev``)."""
    return local * n_dev + device


def shard_wave_width(cap: int, n_dev: int) -> int:
    """Per-device wave width for a bucket whose (mesh-invariant) claim
    cap is ``cap``: the lanes one device contributes to a full wave,
    rounded to a power of two so exactly one kernel shape compiles."""
    return pow2_ceil(-(-max(cap, 1) // max(n_dev, 1)))


class ShardedBlockArena(BlockArena):
    """A :class:`BlockArena` partitioned over a 1-D ``"wave"`` device
    mesh: each shape bucket's rows are permuted so device ``d`` holds the
    contiguous physical rows ``[d * per_dev, (d+1) * per_dev)`` — exactly
    its interleaved logical slots — and uploaded once with
    ``NamedSharding(mesh, P("wave"))``.  Tail rows (bucket size not a
    multiple of the mesh) wrap-copy earlier blocks so every physical row
    is valid data; their outputs are never read.

    The base-class ``_slot`` keeps the *physical* row (so ``slots()``
    and any single-device consumer still work); ``_dev_slot`` adds the
    (device, local-slot) view the sharded dispatch uses."""

    def __init__(self, mesh):
        super().__init__()
        self.mesh = mesh
        self.n_dev = int(mesh.shape["wave"])
        self._dev_slot: Dict[int, Tuple[Any, int, int]] = {}
        self._per_dev: Dict[Any, int] = {}

    @classmethod
    def pack(cls, tasks: Sequence, shape_key: Callable, build: Callable,
             mesh=None, lanes: bool = False,
             months: bool = True) -> "ShardedBlockArena":
        assert mesh is not None, "ShardedBlockArena.pack needs a mesh"
        import jax

        from repro.parallel.sharding import wave_sharding

        arena = cls(mesh)
        n_dev = arena.n_dev
        sharding = wave_sharding(mesh)
        buckets: Dict[Any, List] = {}
        for task in tasks:
            buckets.setdefault(shape_key(task), []).append(task)
        for key, group in buckets.items():
            b = len(group)
            per_dev = -(-b // n_dev)
            # physical order: device-major over the interleaved placement
            order = [unshard_slot(dev, local, n_dev) % b
                     for dev in range(n_dev) for local in range(per_dev)]
            arena._put(key, *_stack_blocks([group[i] for i in order],
                                            build, lanes, months),
                       upload=lambda x: jax.device_put(x, sharding))
            arena._per_dev[key] = per_dev
            for i, task in enumerate(group):
                dev, local = shard_slot(i, n_dev)
                arena._slot[task.task_id] = (key, dev * per_dev + local)
                arena._dev_slot[task.task_id] = (key, dev, local)
        return arena

    def dev_slots(self, tasks: Sequence) -> Tuple[Any, np.ndarray, np.ndarray]:
        """(key, devices, local rows) for a same-shape wave."""
        keys = {self._dev_slot[t.task_id][0] for t in tasks}
        assert len(keys) == 1, f"wave spans shape buckets: {keys}"
        key = keys.pop()
        devs = np.asarray([self._dev_slot[t.task_id][1] for t in tasks],
                          np.int32)
        rows = np.asarray([self._dev_slot[t.task_id][2] for t in tasks],
                          np.int32)
        return key, devs, rows


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------


def run_map_task(block: np.ndarray, months: np.ndarray, seed: int,
                 workload, engine: str,
                 clock: Optional[tel.SpanClock] = None
                 ) -> Dict[str, np.ndarray]:
    """One map task: subsample the block, compute the statistic partial.

    Partials are plain dicts of NumPy arrays so the reduce tree can combine
    them with element-wise addition regardless of engine or backend.
    The device engines time their stages in host spans of ``clock``:
    upload, dispatch, device wait, result fetch.
    """
    if grid_kernel(workload.statistic, engine):
        return _grid_task(block, months, seed, workload,
                          clock or tel.SpanClock())
    if engine == "jnp":
        return _map_task_jnp(block, months, seed, workload,
                             clock or tel.SpanClock())
    if engine == "pallas":
        return _moments_pallas(block, seed, workload,
                               clock or tel.SpanClock())
    if engine == "numpy":
        return _map_task_numpy(block, months, seed, workload)
    raise ValueError(f"unknown engine {engine!r}")


def _wait(clock: tel.SpanClock, uploaded, out):
    """Wait for a dispatch in two spans: its inputs landing on the device
    (more ``platform.upload``: ``jnp.asarray`` returns once a copy is
    enqueued, on a TPU v5e after 1-2 ms of a 1 GiB block's 100 ms, and
    the device starts the dispatch as soon as they land), then its result
    (``platform.device_wait``), so the host copy that follows times the
    copy alone."""
    import jax

    with clock.span("platform.upload"):
        jax.block_until_ready(uploaded)
    with clock.span("platform.device_wait"):
        return jax.block_until_ready(out)


def _map_task_jnp(block: np.ndarray, months: np.ndarray, seed: int,
                  workload, clock: tel.SpanClock) -> Dict[str, np.ndarray]:
    """The jitted ``subsample.map_task`` on one uploaded block."""
    import jax
    import jax.numpy as jnp

    from repro.core import subsample as ss

    with clock.span("platform.upload"):
        data, mo = jnp.asarray(block), jnp.asarray(months)
    with clock.span("platform.dispatch"):
        out = ss.map_task(data, mo, jax.random.PRNGKey(seed),
                          draws=workload.draws,
                          draw_size=workload.draw_size, grid=workload.grid,
                          statistic=workload.statistic)
    out = _wait(clock, (data, mo), out)
    with clock.span("platform.fetch_result"):
        return jax.tree.map(np.asarray, out)


def _moments_pallas(block: np.ndarray, seed: int, workload,
                    clock: tel.SpanClock) -> Dict[str, np.ndarray]:
    """Route the Pallas kernel in as the map-task compute: the random row
    gather + (Σ, Σ²) accumulation happen inside the stats-only
    ``repro.kernels.subsample_gather`` wave kernel, as a wave of one —
    identical math to :func:`run_map_wave`, so per-task and wave execution
    agree to the last bit for the same per-task seed."""
    import jax.numpy as jnp

    n_idx = workload.draws * workload.draw_size
    with clock.span("platform.upload"):
        args = (jnp.asarray(to_lane_layout(block)[None]),
                jnp.asarray(np.zeros(1, np.int32)),
                jnp.asarray(np.asarray([seed], np.int32)))
    with clock.span("platform.dispatch"):
        stats = _wave_fn("moments")(*args, n=n_idx)
    stats = _wait(clock, args, stats)
    with clock.span("platform.fetch_result"):
        return _split_moments(np.asarray(stats), n_idx, block.shape[-1])[0]


def _grid_task(block: np.ndarray, months: np.ndarray, seed: int,
               workload, clock: tel.SpanClock) -> Dict[str, np.ndarray]:
    """``monthly_mean`` of one task through the grid kernel, as a wave
    of one: the same program as :func:`run_map_wave`, so per-task and
    wave execution agree to the last bit for the same per-task seed."""
    import jax
    import jax.numpy as jnp

    with clock.span("platform.upload"):
        args = (jnp.asarray(to_lane_layout(block)[None]),
                jnp.asarray(to_lane_layout(months)[None]),
                jnp.asarray(np.zeros(1, np.int32)),
                jnp.asarray(np.asarray([seed], np.int32)))
    with clock.span("platform.dispatch"):
        out = _wave_fn("grid")(*args, **_grid_static(workload,
                                                     block.shape[-1]))
    out = _wait(clock, args, out)
    clock.grid_kernel_tasks += 1
    with clock.span("platform.fetch_result"):
        return jax.tree.map(lambda a: np.asarray(a)[0], out)


def _split_moments(stats: np.ndarray, n_idx: int,
                   width: int) -> List[Dict[str, np.ndarray]]:
    """``[B, 2, L, 128]`` lane-layout kernel stats → per-task
    reduce-tree partials over the block's ``width`` columns."""
    stats = stats.reshape(len(stats), 2, -1)[:, :, :width]
    return [{"sum": s[0], "sumsq": s[1],
             "count": np.asarray(float(n_idx), np.float32)}
            for s in stats]


def _moments_wave_jit():
    """Module-cached jitted wave pipeline (one compile per arena/wave
    shape, reused across every wave of the job): per-task index
    derivation (vmapped over the folded seeds) → batched stats-only
    Pallas kernel, which reads each task's drawn rows straight out of
    the resident arena at the task's slot."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    @functools.partial(jax.jit, static_argnames=("n",))
    def moments_wave(arena, rows, seeds, *, n):
        ns = arena.shape[1]                         # [slots, count, L, 128]
        idx = jax.vmap(
            lambda s: jax.random.randint(jax.random.PRNGKey(s), (n,), 0,
                                         ns, dtype=jnp.int32))(seeds)
        return ops.subsample_stats_lanes(arena, rows, idx)

    return moments_wave


def _jnp_wave_jit():
    """Module-cached jitted wave for the jnp engine's ``map_task``
    statistics (``alod``): ``jax.vmap`` over the jitted
    ``subsample.map_task`` with per-task PRNG keys derived in-graph —
    bit-identical to per-task calls for the same seeds."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import subsample as ss

    @functools.partial(jax.jit, static_argnames=("draws", "draw_size",
                                                 "grid", "statistic"))
    def jnp_wave(arena, arena_mo, rows, seeds, *, draws, draw_size, grid,
                 statistic):
        data = jnp.take(arena, rows, axis=0)
        months = jnp.take(arena_mo, rows, axis=0)
        keys = jax.vmap(jax.random.PRNGKey)(seeds)
        return jax.vmap(lambda d, m, k: ss.map_task(
            d, m, k, draws=draws, draw_size=draw_size, grid=grid,
            statistic=statistic))(data, months, keys)

    return jnp_wave


def _grid_static(workload, width: int) -> Dict[str, int]:
    """The grid wave's static arguments: the workload's draws and grid,
    and the blocks' row length, which bounds the drawn columns."""
    return dict(draws=workload.draws, draw_size=workload.draw_size,
                grid=workload.grid, width=width)


def _grid_draws(seeds, ns: int, draws: int, draw_size: int, width: int):
    """``map_task``'s draws of each task, ``[B, ns, draws·draw_size]``:
    row ``n``'s drawn columns, draw by draw."""
    import jax
    import jax.numpy as jnp

    idx = jax.vmap(lambda s: jax.random.randint(
        jax.random.PRNGKey(s), (draws, ns, draw_size), 0, width))(seeds)
    return jnp.swapaxes(idx, 1, 2).reshape(len(seeds), ns, -1)


def _grid_partials(out) -> Dict[str, Any]:
    """Grid kernel output ``[B, 2, grid]`` → the ``map_task`` partials."""
    return {"sum": out[:, 0], "count": out[:, 1]}


def _grid_wave_jit():
    """Module-cached jitted ``monthly_mean`` wave: per-task draws derived
    in-graph (the same threefry indices as ``map_task``) → the Pallas
    grid kernel, which reads each task's rows straight out of the
    resident lane-layout arena at the task's slot."""
    import functools

    import jax

    from repro.kernels import ops

    @functools.partial(jax.jit, static_argnames=("draws", "draw_size",
                                                 "grid", "width"))
    def grid_wave(arena, arena_mo, rows, seeds, *, draws, draw_size, grid,
                  width):
        idx = _grid_draws(seeds, arena.shape[1], draws, draw_size, width)
        return _grid_partials(ops.grid_stat_lanes(arena, arena_mo, rows,
                                                  idx, grid=grid))

    return grid_wave


def _moments_wave_sharded_jit(mesh):
    """Sharded moments wave: the per-device body is the SAME pipeline as
    :func:`_moments_wave_jit` (vmapped PRNG index derivation → stats-only
    Pallas kernel reading the local slots), wrapped in ``shard_map`` so
    one dispatch drives every device.  ``check_vma=False`` because Pallas
    has no varying-manual-axes rule; outputs are per-device anyway."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops

    @functools.partial(jax.jit, static_argnames=("n",))
    def moments_wave_sharded(arena, rows, seeds, *, n):
        def per_device(a, r, s):
            # a: [per_dev, count, L, 128]; r, s: [1, width]
            ns = a.shape[1]
            idx = jax.vmap(
                lambda k: jax.random.randint(jax.random.PRNGKey(k), (n,),
                                             0, ns, dtype=jnp.int32))(s[0])
            return ops.subsample_stats_lanes(a, r[0], idx)[None]
        return jax.shard_map(per_device, mesh=mesh,
                             in_specs=(P("wave"), P("wave"), P("wave")),
                             out_specs=P("wave"), check_vma=False)(
            arena, rows, seeds)

    return moments_wave_sharded


def _jnp_wave_sharded_jit(mesh):
    """Sharded jnp wave: per-device ``jax.vmap`` over the jitted
    ``subsample.map_task`` with in-graph PRNG keys — the same math as
    :func:`_jnp_wave_jit`, per device, under one ``shard_map``."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import subsample as ss

    @functools.partial(jax.jit, static_argnames=("draws", "draw_size",
                                                 "grid", "statistic"))
    def jnp_wave_sharded(arena, arena_mo, rows, seeds, *, draws, draw_size,
                         grid, statistic):
        def per_device(a, m, r, s):
            data = jnp.take(a, r[0], axis=0)
            months = jnp.take(m, r[0], axis=0)
            keys = jax.vmap(jax.random.PRNGKey)(s[0])
            out = jax.vmap(lambda d, mo, k: ss.map_task(
                d, mo, k, draws=draws, draw_size=draw_size, grid=grid,
                statistic=statistic))(data, months, keys)
            return jax.tree.map(lambda x: x[None], out)
        return jax.shard_map(per_device, mesh=mesh,
                             in_specs=(P("wave"),) * 4,
                             out_specs=P("wave"), check_vma=False)(
            arena, arena_mo, rows, seeds)

    return jnp_wave_sharded


def _grid_wave_sharded_jit(mesh):
    """Sharded ``monthly_mean`` wave: the per-device body is the SAME
    pipeline as :func:`_grid_wave_jit` on the local slots, under one
    ``shard_map`` (``check_vma=False``: Pallas has no varying-manual-axes
    rule)."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops

    @functools.partial(jax.jit, static_argnames=("draws", "draw_size",
                                                 "grid", "width"))
    def grid_wave_sharded(arena, arena_mo, rows, seeds, *, draws,
                          draw_size, grid, width):
        def per_device(a, m, r, s):
            idx = _grid_draws(s[0], a.shape[1], draws, draw_size, width)
            out = ops.grid_stat_lanes(a, m, r[0], idx, grid=grid)
            return jax.tree.map(lambda x: x[None], _grid_partials(out))
        return jax.shard_map(per_device, mesh=mesh,
                             in_specs=(P("wave"),) * 4,
                             out_specs=P("wave"), check_vma=False)(
            arena, arena_mo, rows, seeds)

    return grid_wave_sharded


_WAVE_JITS = {"moments": _moments_wave_jit, "grid": _grid_wave_jit,
              "jnp": _jnp_wave_jit}
_SHARDED_WAVE_JITS = {"moments": _moments_wave_sharded_jit,
                      "grid": _grid_wave_sharded_jit,
                      "jnp": _jnp_wave_sharded_jit}
_WAVE_FNS: Dict[str, Any] = {}
_SHARDED_WAVE_FNS: Dict[Tuple[str, Any], Any] = {}


def _wave_fn(kind: str):
    """Build the jitted wave entry point once per process so its jit
    cache persists across calls (a per-call jit would retrace every
    wave — exactly the per-task overhead waves exist to remove)."""
    if kind not in _WAVE_FNS:
        _WAVE_FNS[kind] = _WAVE_JITS[kind]()
    return _WAVE_FNS[kind]


def _sharded_wave_fn(kind: str, mesh):
    """Like :func:`_wave_fn` but keyed per (kind, mesh): jax ``Mesh`` is
    hashable and equal meshes compare equal, so rebuilding the same
    1-D wave mesh reuses the cached shard_map-wrapped jit."""
    key = (kind, mesh)
    if key not in _SHARDED_WAVE_FNS:
        _SHARDED_WAVE_FNS[key] = _SHARDED_WAVE_JITS[kind](mesh)
    return _SHARDED_WAVE_FNS[key]


def _jnp_wave_kind(workload, width: int) -> Tuple[str, Dict[str, Any]]:
    """The ``jnp`` engine's wave program for ``workload`` and its static
    arguments: the grid kernel for ``monthly_mean``, else ``map_task``."""
    if grid_kernel(workload.statistic, "jnp"):
        return "grid", _grid_static(workload, width)
    return "jnp", dict(draws=workload.draws, draw_size=workload.draw_size,
                       grid=workload.grid, statistic=workload.statistic)


def run_map_wave(arena: BlockArena, tasks: Sequence, seeds: np.ndarray,
                 workload, engine: str, pad_to: Optional[int] = None,
                 clock: Optional[tel.SpanClock] = None,
                 ) -> List[Dict[str, np.ndarray]]:
    """Execute a wave of same-shape tasks in one device dispatch and split
    the batched result back into per-task reduce-tree partials.

    The wave is padded (repeating the first slot; padded outputs
    discarded) to ``pad_to`` when given — the driver pins one wave width
    per shape bucket so exactly ONE kernel shape compiles per bucket and
    a small tail wave can never trigger a mid-job recompile — else to the
    next power of two.

    A :class:`ShardedBlockArena` routes to the multi-device dispatch —
    same signature, bit-identical results — so the service's wave
    closure need not know whether the arena is sharded.  Both time their stages in host spans of ``clock``:
    upload (the slot and seed vectors, built and sent), dispatch, device
    wait, and result fetch (the copy and the split).
    """
    import jax
    import jax.numpy as jnp

    if engine not in ("pallas", "jnp"):
        raise ValueError(
            f"engine {engine!r} does not support wave execution")
    if isinstance(arena, ShardedBlockArena):
        return run_map_wave_sharded(arena, tasks, seeds, workload, engine,
                                    pad_to=pad_to, clock=clock)

    clock = clock or tel.SpanClock()
    with clock.span("platform.upload"):
        key, rows = arena.slots(tasks)
        b = len(rows)
        b_pad = max(pad_to, b) if pad_to is not None else pow2_ceil(b)
        clock.lanes = b_pad
        seeds = np.asarray(seeds, np.int32)
        if b_pad != b:
            rows = np.concatenate([rows, np.repeat(rows[:1], b_pad - b)])
            seeds = np.concatenate([seeds,
                                    np.repeat(seeds[:1], b_pad - b)])
        rows, seeds = jnp.asarray(rows), jnp.asarray(seeds)
    data, months = arena.bucket(key)

    if engine == "pallas":
        n_idx = workload.draws * workload.draw_size
        with clock.span("platform.dispatch"):
            stats = _wave_fn("moments")(data, rows, seeds, n=n_idx)
        stats = _wait(clock, (rows, seeds), stats)
        with clock.span("platform.fetch_result"):
            return _split_moments(np.asarray(stats)[:b], n_idx,
                                  arena.width(key))
    assert months is not None, "jnp waves need their months plane"
    kind, static = _jnp_wave_kind(workload, arena.width(key))
    with clock.span("platform.dispatch"):
        out = _wave_fn(kind)(data, months, rows, seeds, **static)
    out = _wait(clock, (rows, seeds), out)
    if kind == "grid":
        clock.grid_kernel_tasks += b
    with clock.span("platform.fetch_result"):
        out = jax.tree.map(np.asarray, out)
        return [jax.tree.map(lambda a: a[i], out) for i in range(b)]


def run_map_wave_sharded(arena: ShardedBlockArena, tasks: Sequence,
                         seeds: np.ndarray, workload, engine: str,
                         pad_to: Optional[int] = None,
                         clock: Optional[tel.SpanClock] = None,
                         ) -> List[Dict[str, np.ndarray]]:
    """Execute a wave across the arena's device mesh in one dispatch.

    The wave's members are routed to their owning device's lane matrix
    (``[n_dev, width]`` local rows + seeds, sharded over the mesh), every
    device runs the identical per-task pipeline under ``shard_map``, and
    the per-device partials are gathered HOST-side in mesh-axis order
    (``parallel.collectives.gather_shards`` — a device-side all_gather
    serializes through a rendezvous on the emulated CPU mesh) before
    re-entering task order.  Padding lanes repeat local row 0 / the first
    seed and their outputs are discarded, so results depend only on each
    task's (block, seed) — bit-identical to the single-device wave.

    ``width`` is the warmup-pinned :func:`shard_wave_width` of the claim
    cap; a cross-job fused wave that lands the same slot twice can
    overfill one device, in which case the width grows to the next power
    of two (one extra bounded compile, never a per-wave retrace).
    """
    import jax

    from repro.parallel import collectives as col
    from repro.parallel.sharding import wave_sharding

    if engine not in ("pallas", "jnp"):
        raise ValueError(
            f"engine {engine!r} does not support wave execution")
    clock = clock or tel.SpanClock()
    with clock.span("platform.upload"):
        key, devs, local_rows = arena.dev_slots(tasks)
        n_dev = arena.n_dev
        b = len(tasks)
        seeds = np.asarray(seeds, np.int32)
        cap = pad_to if pad_to is not None else b
        width = shard_wave_width(cap, n_dev)
        occupancy = np.bincount(devs, minlength=n_dev)
        if occupancy.max() > width:
            width = pow2_ceil(int(occupancy.max()))
        clock.lanes = n_dev * width

        rows = np.zeros((n_dev, width), np.int32)
        lane_seeds = np.full((n_dev, width), seeds[0], np.int32)
        fill = np.zeros(n_dev, np.int32)
        place: List[Tuple[int, int]] = []
        for i in range(b):
            d = int(devs[i])
            lane = int(fill[d])
            fill[d] += 1
            rows[d, lane] = local_rows[i]
            lane_seeds[d, lane] = seeds[i]
            place.append((d, lane))

        sharding = wave_sharding(arena.mesh)
        rows_dev = jax.device_put(rows, sharding)
        seeds_dev = jax.device_put(lane_seeds, sharding)
    data, months = arena.bucket(key)

    if engine == "pallas":
        n_idx = workload.draws * workload.draw_size
        with clock.span("platform.dispatch"):
            out = _sharded_wave_fn("moments", arena.mesh)(
                data, rows_dev, seeds_dev, n=n_idx)
        out = _wait(clock, (rows_dev, seeds_dev), out)
        with clock.span("platform.fetch_result"):
            stats = col.gather_shards(out)
            picked = np.stack([stats[d, lane] for d, lane in place])
            return _split_moments(picked, n_idx, arena.width(key))
    assert months is not None, "jnp waves need their months plane"
    kind, static = _jnp_wave_kind(workload, arena.width(key))
    with clock.span("platform.dispatch"):
        out = _sharded_wave_fn(kind, arena.mesh)(
            data, months, rows_dev, seeds_dev, **static)
    out = _wait(clock, (rows_dev, seeds_dev), out)
    if kind == "grid":
        clock.grid_kernel_tasks += b
    with clock.span("platform.fetch_result"):
        host = jax.tree.map(col.gather_shards, out)  # leaves [n_dev, w, ...]
        return [jax.tree.map(lambda a, d=d, lane=lane: np.asarray(a[d, lane]),
                             host)
                for d, lane in place]


def _map_task_numpy(block: np.ndarray, months: np.ndarray, seed: int,
                    workload) -> Dict[str, np.ndarray]:
    """Pure-NumPy reference path (mirrors ``subsample.map_task`` /
    ``kernels.ref.subsample_stats_ref``)."""
    rng = np.random.default_rng(seed)
    ns, sl = block.shape
    stat = workload.statistic

    if stat == "moments":
        idx = rng.integers(0, ns, workload.draws * workload.draw_size)
        rows = block[idx].astype(np.float32)
        return {"sum": rows.sum(axis=0), "sumsq": (rows * rows).sum(axis=0),
                "count": np.asarray(float(len(idx)), np.float32)}

    draws, ds, grid = workload.draws, workload.draw_size, workload.grid
    idx = rng.integers(0, sl, (draws, ns, ds))
    gathered = np.take_along_axis(block[None, :, :], idx, axis=2)
    gathered = np.swapaxes(gathered, 0, 1)          # [ns, draws, ds]
    idx = np.swapaxes(idx, 0, 1)

    if stat == "alod":
        pos = idx.astype(np.float32) / sl
        cell = np.clip((pos * grid).astype(np.int32), 0, grid - 1)
        mean = gathered.mean(axis=2, keepdims=True)
        sd = gathered.std(axis=2, keepdims=True) + 1e-6
        z = np.abs((gathered - mean) / sd)
        curve = np.zeros(grid, np.float32)
        hits = np.zeros(grid, np.float32)
        np.add.at(curve, cell.reshape(-1), z.reshape(-1))
        np.add.at(hits, cell.reshape(-1), 1.0)
        return {"sum_curve": curve, "hits": hits,
                "count": np.asarray(float(ns * draws), np.float32)}

    if stat == "monthly_mean":
        m = np.take_along_axis(months[:, None, :], idx, axis=2)
        m = np.clip(m, 0, grid - 1)
        sums = np.zeros(grid, np.float32)
        cnts = np.zeros(grid, np.float32)
        np.add.at(sums, m.reshape(-1), gathered.reshape(-1))
        np.add.at(cnts, m.reshape(-1), 1.0)
        return {"sum": sums, "count": cnts}

    raise ValueError(f"unknown statistic {stat!r}")
