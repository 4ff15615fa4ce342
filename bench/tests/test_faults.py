"""A run with the timed path broken underneath must come out with
``correct`` false: once for each fault a subsampling cell can have.

* half the batch left out: the second half of every wave (every second
  per-task dispatch) returns a partial of the first half, so the job's
  mean is taken over the rest;
* an answer altered where it is produced: the first partial of every
  dispatch gains 1 in its first element.

(A step that returns its state unchanged belongs to training, and the
exchange between chips to cells on several chips; these cells have
neither.)"""

import numpy as np
import pytest

from repro.platform import compute as pc

from conftest import CELLS, tiny_run


def half_batch(monkeypatch):
    wave, task = pc.run_map_wave, pc.run_map_task
    calls = []

    def run_map_wave(*args, **kwargs):
        out = wave(*args, **kwargs)
        keep = (len(out) + 1) // 2
        return [out[i % keep] for i in range(len(out))]

    def run_map_task(*args, **kwargs):
        calls.append(task(*args, **kwargs))
        return calls[-2] if len(calls) % 2 == 0 else calls[-1]

    monkeypatch.setattr(pc, "run_map_wave", run_map_wave)
    monkeypatch.setattr(pc, "run_map_task", run_map_task)


def altered(monkeypatch):
    wave, task = pc.run_map_wave, pc.run_map_task

    def bump(partial):
        key = next(k for k in partial if np.ndim(partial[k]))
        partial = dict(partial)
        partial[key] = np.array(partial[key], copy=True)
        partial[key].flat[0] += 1.0
        return partial

    def run_map_wave(*args, **kwargs):
        out = wave(*args, **kwargs)
        return [bump(out[0])] + out[1:]

    monkeypatch.setattr(pc, "run_map_wave", run_map_wave)
    monkeypatch.setattr(pc, "run_map_task",
                        lambda *a, **k: bump(task(*a, **k)))


@pytest.mark.parametrize("fault", [half_batch, altered])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = tiny_run(cell, seed=77)
    assert not result["correct"], result["check"]
