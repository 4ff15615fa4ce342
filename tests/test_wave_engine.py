"""Wave execution engine (ISSUE 2 tentpole): wave-vs-per-task bit
identity across engines and backends, the stats-only multi-row Pallas
kernel against the jnp oracle, block-arena shape bucketing, the
power-of-two index padding of ``ops.subsample_gather``, and the
scheduler's same-shape wave draining."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scheduler as sch
from repro.core import subsample as ss
from repro.kernels import ops, ref
from repro.platform import (
    MomentsSpec,
    Platform,
    PlatformSpec,
    compute as pc,
)
from tests._hypothesis_compat import given, settings, st

WL = MomentsSpec(draws=4, draw_size=16)        # 64 indices/task: fast


def _dataset(n, length=96, seed=0, ragged=False):
    rng = np.random.default_rng(seed)
    samples, months = {}, {}
    for i in range(n):
        m = int(rng.integers(length // 2, length)) if ragged else length
        samples[i] = rng.standard_normal(m).astype(np.float32)
        months[i] = rng.integers(0, 12, m).astype(np.int32)
    return samples, months


# -- wave vs per-task bit identity -------------------------------------------


@pytest.mark.parametrize("engine,workload", [
    ("pallas", WL), ("jnp", ss.NETFLIX_LOW)], ids=["pallas", "jnp"])
def test_wave_bit_identical_to_per_task(engine, workload):
    samples, months = _dataset(24)
    base = dict(platform="BTT", n_workers=2, backend="threaded",
                engine=engine, seed=11, max_wave=8)
    per = Platform(PlatformSpec(wave="off", **base)).run(
        samples, months, workload)
    wav = Platform(PlatformSpec(wave="on", **base)).run(
        samples, months, workload)
    assert per.result is not None and wav.result is not None
    for key in per.result:
        np.testing.assert_array_equal(
            np.asarray(per.result[key]), np.asarray(wav.result[key]),
            err_msg=f"wave diverged from per-task on {key!r}")


def test_wave_bit_identical_to_simulated_backend():
    """Extends PR 1's backend bit-identity guarantee to the wave engine:
    threaded waves vs the simulator's per-task calibration pass."""
    samples, months = _dataset(20, ragged=True)
    knee = 4 * 128 * 4
    wav = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="threaded", engine="pallas",
        seed=5, knee_bytes=knee, wave="on", max_wave=8)).run(
            samples, months, WL)
    sim = Platform(PlatformSpec(
        platform="BTS", n_workers=6, backend="simulated", engine="pallas",
        seed=5, knee_bytes=knee)).run(samples, months, WL)
    for key in wav.result:
        np.testing.assert_array_equal(
            np.asarray(wav.result[key]), np.asarray(sim.result[key]),
            err_msg=f"backends diverged on {key!r}")


def test_wave_invariant_to_wave_size():
    samples, months = _dataset(16)
    base = dict(platform="BTT", n_workers=1, backend="threaded",
                engine="pallas", seed=2)
    results = [
        Platform(PlatformSpec(wave="on", max_wave=w, **base)).run(
            samples, months, WL).result
        for w in (2, 5, 16)]
    for other in results[1:]:
        for key in results[0]:
            np.testing.assert_array_equal(np.asarray(results[0][key]),
                                          np.asarray(other[key]))


# -- observability counters ---------------------------------------------------


def test_wave_counters_and_dispatch_reduction():
    samples, months = _dataset(32)
    base = dict(platform="BTT", n_workers=2, backend="threaded",
                engine="pallas", seed=0, max_wave=16)
    per = Platform(PlatformSpec(wave="off", **base)).run(
        samples, months, WL)
    wav = Platform(PlatformSpec(wave="on", **base)).run(
        samples, months, WL)
    assert per.device_dispatches == per.n_tasks
    assert per.wave_sizes == []
    assert per.bytes_uploaded > 0
    assert sum(wav.wave_sizes) == wav.n_tasks
    assert wav.device_dispatches == len(wav.wave_sizes)
    assert wav.bytes_uploaded > 0
    assert per.device_dispatches >= 5 * wav.device_dispatches


def test_wave_on_rejects_unsupported_combination():
    samples, months = _dataset(4)
    with pytest.raises(ValueError, match="wave"):
        Platform(PlatformSpec(platform="BTT", backend="threaded",
                              engine="numpy", wave="on")).run(
            samples, months, WL)
    with pytest.raises(ValueError, match="wave"):
        Platform(PlatformSpec(platform="BTT", backend="simulated",
                              engine="pallas", wave="on")).run(
            samples, months, WL)


# -- stats-only kernel --------------------------------------------------------


@pytest.mark.parametrize("n,d,t,b", [(32, 16, 21, 3), (64, 128, 64, 1),
                                     (16, 8, 5, 4)])
def test_subsample_stats_matches_ref(n, d, t, b):
    """Tail masking (t not a multiple of ROWS_PER_STEP) and batching must
    both agree with the oracle."""
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    data = jax.random.normal(keys[0], (b, n, d), jnp.float32)
    idx = jax.random.randint(keys[1], (b, t), 0, n, jnp.int32)
    stats = ops.subsample_stats(data, idx)
    assert stats.shape == (b, 2, d)
    for i in range(b):
        _, want = ref.subsample_stats_ref(data[i], idx[i])
        np.testing.assert_allclose(np.asarray(stats[i]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@given(st.integers(min_value=1, max_value=40),
       st.integers(min_value=1, max_value=4),
       st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_subsample_stats_property(t, b, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    data = jax.random.normal(keys[0], (b, 16, 8), jnp.float32)
    idx = jax.random.randint(keys[1], (b, t), 0, 16, jnp.int32)
    stats = ops.subsample_stats(data, idx)
    for i in range(b):
        _, want = ref.subsample_stats_ref(data[i], idx[i])
        np.testing.assert_allclose(np.asarray(stats[i]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_wave_kernel_partition_invariant():
    """Any wave partition of the same tasks gives bitwise-equal stats."""
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    data = jax.random.normal(keys[0], (6, 32, 16), jnp.float32)
    idx = jax.random.randint(keys[1], (6, 24), 0, 32, jnp.int32)
    whole = np.asarray(ops.subsample_stats(data, idx))
    singles = np.stack([np.asarray(ops.subsample_stats(
        data[i:i + 1], idx[i:i + 1]))[0] for i in range(6)])
    np.testing.assert_array_equal(whole, singles)


def test_wave_kernel_column_split_bit_identical(monkeypatch):
    """A row wider than the VMEM budget is split over column blocks; the
    split changes no column's accumulation order, so the stats equal
    the unsplit kernel's bit for bit (and the oracle)."""
    from repro.kernels import subsample_gather as sg

    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    data = jax.random.normal(keys[0], (2, 16, 4096), jnp.float32)
    idx = jax.random.randint(keys[1], (2, 13), 0, 16, jnp.int32)
    lanes = sg.to_lane_layout(data)
    slots = jnp.arange(2, dtype=jnp.int32)
    whole = np.asarray(sg.subsample_stats_wave(lanes, slots, idx,
                                               interpret=True))
    monkeypatch.setattr(sg, "ROW_BUFFER_BYTES",
                        sg.ROWS_PER_STEP * sg.SUBLANES * sg.LANES * 4)
    assert sg.column_block(4096 // sg.LANES) == sg.SUBLANES
    split = np.asarray(sg.subsample_stats_wave(lanes, slots, idx,
                                               interpret=True))
    np.testing.assert_array_equal(whole, split)
    for i in range(2):
        _, want = ref.subsample_stats_ref(data[i], idx[i])
        np.testing.assert_allclose(split[i].reshape(2, -1), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_wave_kernel_reads_tasks_at_their_slots():
    """The kernel reads each lane's rows from block ``data[slots[b]]`` of
    the whole arena: repeated and permuted slots give, bit for bit, the
    stats of the same blocks handed over one by one."""
    from repro.kernels import subsample_gather as sg

    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    data = jax.random.normal(keys[0], (5, 16, 256), jnp.float32)
    slots = jnp.asarray([3, 0, 3, 4], jnp.int32)
    idx = jax.random.randint(keys[1], (4, 11), 0, 16, jnp.int32)
    lanes = sg.to_lane_layout(data)
    got = np.asarray(ops.subsample_stats_lanes(lanes, slots, idx))
    for b in range(4):
        one = np.asarray(ops.subsample_stats(data[slots[b]][None],
                                             idx[b][None]))[0]
        np.testing.assert_array_equal(got[b].reshape(2, -1), one)


def test_vmapped_seed_derivation_bit_identical():
    """The wave engine folds per-task seeds with jax.vmap; the derived
    index streams must match the per-task derivation bitwise."""
    n_idx, ns = 64, 32
    seeds = jnp.arange(5, 12, dtype=jnp.int32)
    batched = jax.vmap(lambda s: jax.random.randint(
        jax.random.PRNGKey(s), (n_idx,), 0, ns, dtype=jnp.int32))(seeds)
    for i, s in enumerate(range(5, 12)):
        single = jax.random.randint(jax.random.PRNGKey(s), (n_idx,), 0, ns,
                                    dtype=jnp.int32)
        np.testing.assert_array_equal(np.asarray(batched[i]),
                                      np.asarray(single))


# -- pow2 index padding (retrace fix) ----------------------------------------


def test_subsample_gather_pow2_padding_correct():
    data = jax.random.normal(jax.random.PRNGKey(0), (32, 8), jnp.float32)
    for t in (1, 5, 7, 8, 13):
        idx = jax.random.randint(jax.random.PRNGKey(t), (t,), 0, 32,
                                 jnp.int32)
        gathered, stats = ops.subsample_gather(data, idx)
        g_ref, s_ref = ref.subsample_stats_ref(data, idx)
        assert gathered.shape == (t, 8)
        np.testing.assert_array_equal(np.asarray(gathered),
                                      np.asarray(g_ref))
        np.testing.assert_allclose(np.asarray(stats), np.asarray(s_ref),
                                   rtol=1e-4, atol=1e-4)


def test_subsample_gather_shares_one_trace_across_draw_counts():
    """Index counts 5..8 all round up to 8, so they must share ONE
    compiled kernel instead of retracing per length."""
    data = jax.random.normal(jax.random.PRNGKey(1), (16, 8), jnp.float32)
    ops._subsample_gather_padded._clear_cache()
    for t in (5, 6, 7, 8):
        idx = jax.random.randint(jax.random.PRNGKey(t), (t,), 0, 16,
                                 jnp.int32)
        ops.subsample_gather(data, idx)
    assert ops._subsample_gather_padded._cache_size() == 1


# -- block arena & padding policy --------------------------------------------


def _make_tasks(sample_ids_groups):
    return [sch.Task(task_id=i, sample_ids=tuple(g), size_bytes=1.0)
            for i, g in enumerate(sample_ids_groups)]


def test_block_arena_roundtrips_build_block():
    samples, months = _dataset(12, ragged=True, seed=7)
    ids = sorted(samples)
    pad_len = pc.partial_pad_len("moments", samples)
    tasks = _make_tasks([(i, i + 1) for i in range(0, 12, 2)])

    def build(task):
        return pc.build_block(samples, months, ids, task.sample_ids, 2,
                              pad_len)

    def shape_key(task):
        longest = max(samples[ids[i]].shape[0] for i in task.sample_ids)
        return (2, pc.padded_len(longest, pad_len))

    arena = pc.BlockArena.pack(tasks, shape_key, build)
    assert arena.nbytes > 0
    for task in tasks:
        key, rows = arena.slots([task])
        data, mo = arena.bucket(key)
        want_block, want_mo = build(task)
        np.testing.assert_array_equal(np.asarray(data[rows[0]]), want_block)
        np.testing.assert_array_equal(np.asarray(mo[rows[0]]), want_mo)


def test_block_arena_rejects_cross_shape_wave():
    samples = {0: np.zeros(8, np.float32), 1: np.zeros(100, np.float32)}
    months = {0: np.zeros(8, np.int32), 1: np.zeros(100, np.int32)}
    ids = [0, 1]
    tasks = _make_tasks([(0,), (1,)])

    def build(task):
        return pc.build_block(samples, months, ids, task.sample_ids, 1, 0)

    def shape_key(task):
        return (1, pc.padded_len(samples[task.sample_ids[0]].shape[0]))

    arena = pc.BlockArena.pack(tasks, shape_key, build)
    assert len(arena.keys()) == 2
    with pytest.raises(AssertionError):
        arena.slots(tasks)           # mixed shapes must never form a wave


@given(st.integers(min_value=1, max_value=4096),
       st.integers(min_value=0, max_value=512))
@settings(max_examples=25, deadline=None)
def test_padded_len_policy(longest, min_len):
    n = pc.padded_len(longest, min_len)
    assert n >= longest and n >= max(min_len, 1)
    assert n & (n - 1) == 0                       # power of two
    assert n < 2 * max(longest, min_len, 1)       # tight


@given(st.lists(st.integers(min_value=1, max_value=64), min_size=1,
                max_size=6),
       st.integers(min_value=0, max_value=32))
@settings(max_examples=25, deadline=None)
def test_pad_to_common_roundtrip(lengths, min_len):
    arrays = [np.arange(m, dtype=np.float32) for m in lengths]
    padded = pc.pad_to_common(arrays, min_len)
    want = pc.padded_len(max(lengths), min_len)
    for orig, pad in zip(arrays, padded):
        assert pad.shape[0] == want
        np.testing.assert_array_equal(pad[:orig.shape[0]], orig)  # rtrip
        if pad.shape[0] > orig.shape[0]:          # wrap padding policy
            np.testing.assert_array_equal(
                pad[orig.shape[0]:],
                np.resize(orig, want)[orig.shape[0]:])


# -- scheduler wave draining --------------------------------------------------


def test_claim_batch_drains_same_key_fifo():
    tasks = _make_tasks([(0,), (1,), (2,), (3, 4), (5,), (6,)])
    key_fn = lambda t: len(t.sample_ids)          # noqa: E731
    sched = sch.TwoPhaseScheduler(1, tasks, sch.SchedulerConfig())
    first = sched.on_worker_idle(0)
    batch = sched.claim_batch(0, first, max_n=8, key_fn=key_fn)
    # drains task 1, 2 then stops at the 2-sample task 3
    assert [t.task_id for t in batch] == [0, 1, 2]
    assert [t.task_id for t in sched.backlog] == [3, 4, 5]


def test_claim_batch_respects_max():
    tasks = _make_tasks([(i,) for i in range(10)])
    sched = sch.TwoPhaseScheduler(1, tasks, sch.SchedulerConfig())
    first = sched.on_worker_idle(0)
    batch = sched.claim_batch(0, first, max_n=4,
                              key_fn=lambda t: len(t.sample_ids))
    assert len(batch) == 4
    assert len(sched.backlog) == 6


def test_warmup_blocks_not_rebuilt_in_execute_phase(monkeypatch):
    """Satellite: phase-3 warmup blocks are cached and reused by phase 4,
    so per-task mode builds exactly n_tasks blocks (not n_tasks +
    n_shapes)."""
    samples, months = _dataset(8)
    calls = {"n": 0}
    real = pc.build_block

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(pc, "build_block", counting)
    rep = Platform(PlatformSpec(
        platform="BTT", n_workers=1, backend="threaded", engine="pallas",
        wave="off", seed=0)).run(samples, months, WL)
    assert rep.n_tasks == 8
    assert calls["n"] == 8
