"""Base-layer unit tests: datapack, name_resolve, topology, timeutil.

Models the reference's unit-test coverage for realhf/base (e.g.
tests/distributed/test_nfs_name_resolve.py, datapack usage in
tests/data/test_sequence_gather_split.py).
"""

import time

import numpy as np
import pytest

from areal_tpu.base import datapack, name_resolve, timeutil
from areal_tpu.base.topology import (
    AXIS_ORDER,
    ParallelConfig,
    coords_of_rank,
    make_mesh,
    rank_of_coords,
    ranks_on_axis,
)


class TestDatapack:
    def test_ffd_respects_capacity(self, rng):
        sizes = rng.integers(1, 100, size=50).tolist()
        groups = datapack.ffd_allocate(sizes, capacity=128)
        seen = sorted(i for g in groups for i in g)
        assert seen == list(range(50))
        for g in groups:
            assert sum(sizes[i] for i in g) <= 128 or len(g) == 1

    def test_ffd_oversize_item_own_group(self):
        groups = datapack.ffd_allocate([300, 10, 10], capacity=128)
        own = [g for g in groups if 0 in g]
        assert own == [[0]]

    def test_ffd_min_groups(self):
        groups = datapack.ffd_allocate([1, 1, 1, 1], capacity=1000, min_groups=2)
        assert len(groups) >= 2
        assert sorted(i for g in groups for i in g) == [0, 1, 2, 3]

    def test_partition_balanced(self, rng):
        sizes = rng.integers(1, 50, size=23).tolist()
        groups = datapack.partition_balanced(sizes, 4)
        assert len(groups) == 4
        assert sorted(i for g in groups for i in g) == list(range(23))
        loads = [sum(sizes[i] for i in g) for g in groups]
        assert max(loads) - min(loads) <= max(sizes)

    def test_min_abs_diff_partition_contiguous(self):
        sizes = [5, 5, 5, 5, 20]
        parts = datapack.min_abs_diff_partition(sizes, 3)
        assert len(parts) == 3
        assert datapack.flat2d(parts) == list(range(5))


class TestNameResolve:
    def test_add_get_delete(self):
        name_resolve.add("a/b/c", "v1")
        assert name_resolve.get("a/b/c") == "v1"
        with pytest.raises(name_resolve.NameEntryExistsError):
            name_resolve.add("a/b/c", "v2")
        name_resolve.add("a/b/c", "v2", replace=True)
        assert name_resolve.get("a/b/c") == "v2"
        name_resolve.delete("a/b/c")
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            name_resolve.get("a/b/c")

    def test_subtree(self):
        for i in range(3):
            name_resolve.add(f"root/sub/{i}", str(i))
        assert name_resolve.get_subtree("root/sub") == ["0", "1", "2"]
        assert name_resolve.find_subtree("root/sub") == [
            "root/sub/0",
            "root/sub/1",
            "root/sub/2",
        ]
        name_resolve.clear_subtree("root")
        assert name_resolve.get_subtree("root/sub") == []

    def test_wait(self):
        import threading

        def _adder():
            time.sleep(0.1)
            name_resolve.add("late/key", "done")

        t = threading.Thread(target=_adder)
        t.start()
        assert name_resolve.wait("late/key", timeout=2) == "done"
        t.join()

    def test_backends_agree_on_subtree_root_exclusion(self, tmp_path):
        # The prefix key itself is not part of its own subtree, in BOTH backends.
        for repo in (
            name_resolve.MemoryNameResolveRepository(),
            name_resolve.FileNameResolveRepository(root=str(tmp_path)),
        ):
            repo.add("workers", "meta")
            repo.add("workers/w0", "v0")
            assert repo.get_subtree("workers") == ["v0"], type(repo).__name__

    def test_file_backend_ttl_expiry(self, tmp_path):
        import os

        repo = name_resolve.FileNameResolveRepository(root=str(tmp_path))
        repo.add("peers/w0", "alive", keepalive_ttl=10.0)
        assert repo.get("peers/w0") == "alive"
        # Simulate a dead worker: age the entry file past its TTL.
        entry = repo._path("peers/w0")
        old = time.time() - 100
        os.utime(entry, (old, old))
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            repo.get("peers/w0")
        assert repo.get_subtree("peers") == []

    def test_reset_keeps_persistent_entries(self):
        name_resolve.add("perm/key", "stay", delete_on_exit=False)
        name_resolve.add("temp/key", "go", delete_on_exit=True)
        name_resolve.reset()
        assert name_resolve.get("perm/key") == "stay"
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            name_resolve.get("temp/key")

    def test_file_backend(self, tmp_path):
        repo = name_resolve.FileNameResolveRepository(root=str(tmp_path))
        repo.add("x/y", "1")
        repo.add("x/z", "2")
        assert repo.get("x/y") == "1"
        assert repo.get_subtree("x") == ["1", "2"]
        repo.delete("x/y")
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            repo.get("x/y")
        repo.clear_subtree("x")
        assert repo.find_subtree("x") == []


class TestTopology:
    def test_parse_roundtrip(self):
        pc = ParallelConfig.from_str("d4f2m2")
        assert pc == ParallelConfig(data=4, fsdp=2, model=2)
        assert pc.world_size == 16
        assert ParallelConfig.from_str(pc.to_str()) == pc

    def test_parse_reference_style(self):
        # Reference allocation strings like "d64p1m1".
        pc = ParallelConfig.from_str("d64p1m1")
        assert (pc.data, pc.pipe, pc.model) == (64, 1, 1)

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            ParallelConfig.from_str("x3")
        with pytest.raises(ValueError):
            ParallelConfig.from_str("d2d4")

    def test_coords_rank_roundtrip(self):
        pc = ParallelConfig(data=2, fsdp=2, model=2, pipe=1, seq=1)
        for r in range(pc.world_size):
            c = coords_of_rank(pc, r)
            assert rank_of_coords(pc, **c) == r

    def test_ranks_on_axis(self):
        pc = ParallelConfig(data=2, model=2)
        assert ranks_on_axis(pc, "model", data=1) == [2, 3]
        assert ranks_on_axis(pc, "data") == [0, 2]

    def test_make_mesh_cpu(self):
        import jax

        pc = ParallelConfig(data=2, fsdp=2, model=2)
        mesh = make_mesh(pc, jax.devices())
        assert mesh.shape["data"] == 2
        assert mesh.shape["fsdp"] == 2
        assert mesh.shape["model"] == 2
        assert tuple(mesh.axis_names) == AXIS_ORDER

    def test_make_mesh_wrong_count(self):
        import jax

        with pytest.raises(ValueError):
            make_mesh(ParallelConfig(data=3), jax.devices())


class TestFrequencyControl:
    def test_steps(self):
        fc = timeutil.FrequencyControl(frequency_steps=3)
        assert [fc.check() for _ in range(7)] == [
            False,
            False,
            True,
            False,
            False,
            True,
            False,
        ]

    def test_initial_value(self):
        fc = timeutil.FrequencyControl(frequency_steps=100, initial_value=True)
        assert fc.check()
        assert not fc.check()

    def test_inert_when_unset(self):
        fc = timeutil.FrequencyControl()
        assert not any(fc.check() for _ in range(10))

    def test_state_roundtrip(self):
        fc = timeutil.FrequencyControl(frequency_steps=3)
        fc.check()
        state = fc.state_dict()
        fc2 = timeutil.FrequencyControl(frequency_steps=3)
        fc2.load_state_dict(state)
        assert not fc2.check()
        assert fc2.check()


class TestBackendDetection:
    def test_follows_the_platform_only(self, monkeypatch):
        from areal_tpu.base import distributed

        import jax

        assert distributed.is_tpu_backend() is False  # CPU test cluster
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert distributed.is_tpu_backend() is True


class TestCompilationCachePlacement:
    """The cache directory is part of the cache key: it is placed from
    outside by JAX_COMPILATION_CACHE_DIR, else it is one fixed directory
    inside the checkout — never /tmp, a pid or a time."""

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        import jax

        from areal_tpu.base import compilation_cache

        placed = tmp_path / "placed"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
        in_code = jax.config.jax_compilation_cache_dir
        assert compilation_cache.enable() == str(placed)
        assert placed.is_dir()
        # JAX reads the variable itself at import; nothing is set in code.
        assert jax.config.jax_compilation_cache_dir == in_code

    def test_default_is_one_fixed_dir_in_the_checkout(self, monkeypatch):
        import os

        import jax

        from areal_tpu.base import compilation_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compilation_cache.enable() == want
            assert compilation_cache.enable() == want  # no pid, no clock
            assert jax.config.jax_compilation_cache_dir == want
        finally:  # the suite may run with the cache placed from outside
            jax.config.update("jax_compilation_cache_dir", before)
        assert not want.startswith("/tmp")

    def test_uncreatable_dir_is_an_error(self, monkeypatch, tmp_path):
        from areal_tpu.base import compilation_cache

        blocker = tmp_path / "file"
        blocker.write_text("")
        monkeypatch.setenv(
            "JAX_COMPILATION_CACHE_DIR", str(blocker / "cache")
        )
        with pytest.raises(OSError):
            compilation_cache.enable()
