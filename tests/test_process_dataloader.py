"""Multiprocess DataLoader workers (VERDICT r4 next-8; ref:
python/paddle/io/reader.py:216 — process workers because transforms
hold the GIL). Spawn + SharedMemory transport; thread tier stays the
fallback for unpicklable datasets.

Note: this sandbox exposes ONE cpu core, so these tests verify the
mechanism (spawn, ordering, shm round-trip, error/worker-info
plumbing), not a parallel speedup."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.io import DataLoader, Dataset, get_worker_info


class ArrayDs(Dataset):
    """Module-level (spawn-picklable) dataset with a visible transform."""

    def __init__(self, n=16, big=False):
        self.n = n
        self.big = big

    def __getitem__(self, i):
        rng = np.random.RandomState(i)
        size = 64 * 1024 if self.big else 8   # big -> SharedMemory path
        x = rng.standard_normal(size).astype(np.float32) * 2.0
        return x, np.int64(i)

    def __len__(self):
        return self.n


class BoomDs(ArrayDs):
    def __getitem__(self, i):
        if i == 5:
            raise ValueError("boom at 5")
        return super().__getitem__(i)


class InfoDs(ArrayDs):
    def __getitem__(self, i):
        info = get_worker_info()
        assert info is not None and info.num_workers == 2
        return np.full((4,), float(info.id), np.float32), np.int64(i)


def _collect(loader):
    out = []
    for x, y in loader:
        out.append((np.asarray(x.numpy()), np.asarray(y.numpy())))
    return out


@pytest.mark.parametrize("big", [False, True])
def test_process_workers_match_serial(big):
    ds = ArrayDs(n=13, big=big)
    serial = _collect(DataLoader(ds, batch_size=4, num_workers=0))
    procs = _collect(DataLoader(ds, batch_size=4, num_workers=2))
    assert len(serial) == len(procs) == 4
    for (sx, sy), (px, py) in zip(serial, procs):
        np.testing.assert_array_equal(sx, px)
        np.testing.assert_array_equal(sy, py)


def test_process_worker_error_propagates():
    ds = BoomDs(n=16)
    loader = DataLoader(ds, batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="boom at 5"):
        _collect(loader)


def test_worker_info_inside_process():
    ds = InfoDs(n=8)
    out = _collect(DataLoader(ds, batch_size=2, num_workers=2))
    ids = {float(x[0, 0]) for x, _ in out}
    assert ids <= {0.0, 1.0} and len(ids) == 2
    # main process sees no worker context
    assert get_worker_info() is None


def test_unpicklable_falls_back_to_threads():
    class LocalDs(ArrayDs):      # class defined in function: unpicklable
        pass

    ds = LocalDs(n=8)
    loader = DataLoader(ds, batch_size=4, num_workers=2)
    with pytest.warns(UserWarning, match="not picklable"):
        out = _collect(loader)
    assert len(out) == 2


def test_early_break_cleans_up():
    ds = ArrayDs(n=64, big=True)
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        prefetch_factor=2)
    it = iter(loader)
    next(it)
    next(it)
    it.close()      # generator finally: stop, drain, unlink segments
    # a fresh epoch over the same loader still works
    assert len(_collect(loader)) == 16
