"""Native segment-tree + host PER tests (strategy mirrors reference csrc
coverage through PrioritizedSampler behavior + direct tree semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.csrc import MinSegmentTree, SumSegmentTree
from rl_tpu.data import (
    ArrayDict,
    DeviceStorage,
    HostPrioritizedSampler,
    MemmapStorage,
    ReplayBuffer,
)

KEY = jax.random.key(0)


class TestSumTree:
    def test_library_is_built_from_source_on_this_machine(self, monkeypatch):
        """git carries segment_tree.cpp only: with no library on disk the
        first construction compiles one here (g++), and the tree that
        comes back is bound to it — there is nothing else to fall to."""
        import os

        import rl_tpu.csrc as csrc

        here = os.path.dirname(csrc.__file__)
        lib = os.path.join(here, "_build", "libsegment_tree.so")
        if os.path.exists(lib):
            os.remove(lib)
        monkeypatch.setattr(csrc, "_LIB", None)
        t = SumSegmentTree(8)
        assert os.path.getmtime(lib) >= os.path.getmtime(
            os.path.join(here, "segment_tree.cpp")
        )
        assert t._lib._name == lib
        t[3] = 2.0
        assert t.reduce() == 2.0

    def test_failed_build_raises(self, monkeypatch, tmp_path):
        import rl_tpu.csrc as csrc

        bad = tmp_path / "csrc"
        bad.mkdir()
        (bad / "segment_tree.cpp").write_text("this is not C++\n")
        monkeypatch.setattr(csrc, "_LIB", None)
        monkeypatch.setattr(csrc, "__file__", str(bad / "__init__.py"))
        with pytest.raises(RuntimeError, match="g\\+\\+ exit"):
            SumSegmentTree(8)

    def test_set_get_reduce(self):
        t = SumSegmentTree(10)
        t[np.arange(10)] = np.arange(10, dtype=np.float64)
        assert t.reduce() == 45.0
        assert t.reduce(2, 5) == 2 + 3 + 4
        np.testing.assert_allclose(t[np.array([3, 7])], [3.0, 7.0])

    def test_scan_prefix_search(self):
        t = SumSegmentTree(4)
        t[np.arange(4)] = np.array([1.0, 2.0, 3.0, 4.0])  # prefix: 1,3,6,10
        np.testing.assert_array_equal(t.scan([0.5, 1.5, 5.9, 6.1, 9.99]), [0, 1, 2, 3, 3])

    def test_scan_matches_numpy_reference(self):
        rng = np.random.default_rng(0)
        vals = rng.random(1000)
        t = SumSegmentTree(1000)
        t[np.arange(1000)] = vals
        us = rng.random(256) * vals.sum()
        expected = np.searchsorted(np.cumsum(vals), us, side="right")
        np.testing.assert_array_equal(t.scan(us), np.clip(expected, 0, 999))

    def test_overwrite_updates_internal_nodes(self):
        t = SumSegmentTree(8)
        t[0] = 5.0
        t[0] = 1.0
        assert t.reduce() == 1.0


class TestMinTree:
    def test_min_semantics(self):
        t = MinSegmentTree(6)
        t[np.arange(6)] = np.array([5.0, 3.0, 8.0, 1.0, 9.0, 2.0])
        assert t.reduce() == 1.0
        assert t.reduce(0, 3) == 3.0
        t[3] = 10.0
        assert t.reduce() == 2.0


class TestHostPER:
    def test_matches_device_per_statistics(self):
        """Host (C++ tree) and device (prefix-sum) PER draw from the same
        distribution for the same priorities."""
        from rl_tpu.data import PrioritizedSampler

        cap, n = 64, 16
        prio = np.linspace(0.1, 2.0, n)

        host = HostPrioritizedSampler(alpha=1.0, beta=1.0)
        hs = host.init(cap)
        hs = host.on_write(hs, np.arange(n), None)
        hs = host.update_priority(hs, np.arange(n), prio)
        hidx, hinfo, _ = host.sample(hs, KEY, 4096, jnp.asarray(n), cap)

        dev = PrioritizedSampler(alpha=1.0, beta=1.0)
        ds = dev.init(cap)
        ds = dev.on_write(ds, jnp.arange(n), None)
        ds = dev.update_priority(ds, jnp.arange(n), jnp.asarray(prio))
        didx, dinfo, _ = dev.sample(ds, KEY, 4096, jnp.asarray(n), cap)

        hfreq = np.bincount(np.asarray(hidx), minlength=n) / 4096
        dfreq = np.bincount(np.asarray(didx), minlength=n) / 4096
        np.testing.assert_allclose(hfreq, dfreq, atol=0.03)
        # weights agree in shape and scale
        np.testing.assert_allclose(
            np.asarray(hinfo["_weight"]).mean(),
            np.asarray(dinfo["_weight"]).mean(),
            rtol=0.1,
        )

    def test_with_memmap_buffer(self, tmp_path):
        rb = ReplayBuffer(
            MemmapStorage(32, scratch_dir=str(tmp_path)),
            HostPrioritizedSampler(),
            batch_size=256,
        )
        state = rb.init(ArrayDict(x=jnp.zeros(2)))
        data = ArrayDict(x=jnp.arange(20.0)[:, None] * jnp.ones((1, 2)))
        state = rb.extend(state, data)
        state = rb.update_priority(state, np.arange(10), np.full(10, 100.0))
        batch, state = rb.sample(state, KEY)
        # overwhelming priority on indices < 10
        assert (np.asarray(batch["index"]) < 10).mean() > 0.8


class TestPerf:
    def test_native_scan_faster_than_numpy_fallback(self):
        import time

        from rl_tpu.csrc import _NumpySumTree

        cap = 1 << 17
        vals = np.random.default_rng(1).random(cap)
        native = SumSegmentTree(cap)
        native[np.arange(cap)] = vals
        fallback = _NumpySumTree(cap)
        fallback[np.arange(cap)] = vals
        us = np.random.default_rng(2).random(64) * vals.sum() * 0.999

        # point updates dominate PER maintenance: native O(log N) vs O(N)
        # scan; min-of-runs to shrug off scheduler noise on a busy machine
        def time_min(fn, runs=3, iters=200):
            best = float("inf")
            for _ in range(runs):
                t0 = time.perf_counter()
                for _ in range(iters):
                    fn()
                best = min(best, time.perf_counter() - t0)
            return best

        idx = np.arange(64)

        def native_iter():
            native[idx] = vals[:64]
            native.scan(us)

        def fallback_iter():
            fallback[idx] = vals[:64]
            fallback.scan(us)

        t_native = time_min(native_iter)
        t_fallback = time_min(fallback_iter)
        assert t_native < t_fallback, (t_native, t_fallback)
