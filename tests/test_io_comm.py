"""I/O, printing, and communication-facade tests (reference
``heat/core/tests/test_io.py``, ``test_communication.py``)."""

import os

import numpy as np
import pytest

import heat_tpu as ht


class TestIO:
    def test_hdf5_roundtrip(self, tmp_path):
        data = np.random.default_rng(0).random((26, 5)).astype(np.float32)
        path = str(tmp_path / "t.h5")
        x = ht.array(data, split=0)
        ht.save_hdf5(x, path, "data")
        for split in (None, 0, 1):
            y = ht.load_hdf5(path, "data", split=split)
            np.testing.assert_allclose(y.numpy(), data, rtol=1e-6)
            assert y.split == split

    def test_load_dispatch(self, tmp_path):
        data = np.arange(12, dtype=np.float32).reshape(4, 3)
        p_h5 = str(tmp_path / "d.h5")
        ht.save(ht.array(data), p_h5, "data")
        y = ht.load(p_h5, dataset="data", split=0)
        np.testing.assert_allclose(y.numpy(), data)
        with pytest.raises(ValueError):
            ht.load("nope.xyz")
        with pytest.raises(TypeError):
            ht.load(123)

    def test_hdf5_stream_roundtrip_and_accounting(self, tmp_path):
        """stream=True: chunk-by-chunk values equal the full load, the
        stream re-iterates (the fit_stream epoch re-read), and the chunk
        accounting proves the peak resident chunk stayed below full
        materialization."""
        data = np.random.default_rng(2).random((53, 5)).astype(np.float32)
        path = str(tmp_path / "s.h5")
        ht.save_hdf5(ht.array(data, split=0), path, "data")
        st = ht.load_hdf5(path, "data", stream=True)
        assert st.shape == (53, 5)
        got = []
        for chunk in st.iter_chunks(16):
            assert chunk.split == 0
            got.append(np.asarray(chunk.numpy()))
        assert [g.shape[0] for g in got] == [16, 16, 16, 5]
        np.testing.assert_array_equal(np.concatenate(got), data)
        # re-iteration streams the same data again
        again = np.concatenate(
            [np.asarray(c.numpy()) for c in st.iter_chunks(20)])
        np.testing.assert_array_equal(again, data)
        full_bytes = data.size * 4
        assert st.chunks_read == 4 + 3
        assert 0 < st.peak_chunk_bytes < full_bytes
        assert st.bytes_read >= full_bytes  # two passes, padded chunks

    def test_hdf5_stream_rejects_bad_args(self, tmp_path):
        data = np.ones((8, 2), np.float32)
        path = str(tmp_path / "b.h5")
        ht.save_hdf5(ht.array(data), path, "data")
        with pytest.raises(ValueError):
            ht.load_hdf5(path, "data", split=1, stream=True)
        st = ht.load_hdf5(path, "data", stream=True)
        with pytest.raises(ValueError):
            next(iter(st.iter_chunks(0)))

    def test_netcdf_stream_roundtrip(self, tmp_path):
        if not ht.io.supports_netcdf():
            pytest.skip("no NetCDF backend available")
        data = np.random.default_rng(3).random((21, 3)).astype(np.float32)
        path = str(tmp_path / "s.nc")
        ht.save_netcdf(ht.array(data, split=0), path, "v")
        st = ht.load_netcdf(path, "v", stream=True)
        got = np.concatenate(
            [np.asarray(c.numpy()) for c in st.iter_chunks(8)])
        np.testing.assert_allclose(got, data, rtol=1e-6)

    def test_csv_roundtrip(self, tmp_path):
        data = np.random.default_rng(1).random((9, 4)).astype(np.float32)
        path = str(tmp_path / "t.csv")
        ht.save_csv(ht.array(data, split=0), path)
        y = ht.load_csv(path, split=0)
        np.testing.assert_allclose(y.numpy(), data, rtol=1e-4, atol=1e-5)

    def test_csv_header(self, tmp_path):
        path = str(tmp_path / "h.csv")
        with open(path, "w") as f:
            f.write("a,b\n1.0,2.0\n3.0,4.0\n")
        y = ht.load_csv(path, header_lines=1)
        np.testing.assert_allclose(y.numpy(), [[1.0, 2.0], [3.0, 4.0]])

    def test_netcdf_gated(self):
        if not ht.io.supports_netcdf():
            with pytest.raises(RuntimeError):
                ht.io.load_netcdf("x.nc", "v")

    def test_npy_dir(self, tmp_path):
        a = np.arange(6, dtype=np.float32).reshape(2, 3)
        b = np.arange(6, 12, dtype=np.float32).reshape(2, 3)
        np.save(tmp_path / "a.npy", a)
        np.save(tmp_path / "b.npy", b)
        y = ht.io.load_npy_from_path(str(tmp_path), split=0)
        np.testing.assert_allclose(y.numpy(), np.concatenate([a, b]))


class TestParallelSave:
    """Saves stream per-shard slices — never the gathered global array
    (reference rank-ordered/mpio writes, ``heat/core/io.py:147-233,487``;
    round-1/round-2 finding)."""

    def _no_gather(self, monkeypatch):
        """Make any full-gather during save an error (no-op at 1 device,
        where shard 0 IS the global array)."""
        if ht.get_comm().size == 1:
            return

        def boom(self):  # pragma: no cover - the assertion
            raise AssertionError("save path gathered the global array")

        monkeypatch.setattr(ht.DNDarray, "numpy", boom)
        monkeypatch.setattr(ht.DNDarray, "_logical", boom)

    @pytest.mark.parametrize("split", [0, 1])
    def test_hdf5_save_no_gather(self, tmp_path, split, monkeypatch):
        data = np.random.default_rng(2).random((23, 7)).astype(np.float32)
        x = ht.array(data, split=split)
        path = str(tmp_path / "p.h5")
        self._no_gather(monkeypatch)
        ht.save_hdf5(x, path, "data")
        monkeypatch.undo()
        y = ht.load_hdf5(path, "data")
        np.testing.assert_allclose(y.numpy(), data, rtol=1e-6)

    def test_csv_save_no_gather_row_split(self, tmp_path, monkeypatch):
        data = np.random.default_rng(3).random((19, 3)).astype(np.float32)
        x = ht.array(data, split=0)
        path = str(tmp_path / "p.csv")
        self._no_gather(monkeypatch)
        ht.save_csv(x, path)
        monkeypatch.undo()
        y = ht.load_csv(path)
        np.testing.assert_allclose(y.numpy(), data, rtol=1e-4, atol=1e-5)

    def test_csv_save_column_split_resplits(self, tmp_path):
        data = np.random.default_rng(4).random((6, 11)).astype(np.float32)
        path = str(tmp_path / "c.csv")
        ht.save_csv(ht.array(data, split=1), path)
        y = ht.load_csv(path)
        np.testing.assert_allclose(y.numpy(), data, rtol=1e-4, atol=1e-5)

    def test_hdf5_save_1d_uneven(self, tmp_path):
        data = np.arange(13, dtype=np.float32)  # prime: padded shards
        path = str(tmp_path / "u.h5")
        ht.save_hdf5(ht.array(data, split=0), path, "d")
        np.testing.assert_allclose(ht.load_hdf5(path, "d").numpy(), data)

    def test_hdf5_save_bf16_widens(self, tmp_path):
        data = np.linspace(0, 1, 16, dtype=np.float32)
        x = ht.array(data, split=0, dtype=ht.bfloat16)
        path = str(tmp_path / "b.h5")
        ht.save_hdf5(x, path, "d")
        y = ht.load_hdf5(path, "d")
        np.testing.assert_allclose(y.numpy(), data, atol=1e-2)

    def test_netcdf_save_no_gather(self, tmp_path, monkeypatch):
        if not ht.io.supports_netcdf():
            pytest.skip("netCDF4 not available")
        data = np.random.default_rng(5).random((17, 4)).astype(np.float32)
        x = ht.array(data, split=0)
        path = str(tmp_path / "p.nc")
        self._no_gather(monkeypatch)
        ht.save_netcdf(x, path, "v")
        monkeypatch.undo()
        y = ht.load_netcdf(path, "v")
        np.testing.assert_allclose(y.numpy(), data, rtol=1e-6)

    def test_netcdf_append_and_bundled_iris(self, tmp_path):
        if not ht.io.supports_netcdf():
            pytest.skip("no NetCDF backend (netCDF4 or scipy) available")
        # append mode creates a second variable in the same file
        data = np.arange(12, dtype=np.float32).reshape(6, 2)
        path = str(tmp_path / "a.nc")
        ht.save_netcdf(ht.array(data, split=0), path, "x")
        ht.save_netcdf(ht.array(data[:, 0].copy(), split=0), path, "y",
                       mode="a")
        np.testing.assert_allclose(ht.load_netcdf(path, "x").numpy(), data)
        np.testing.assert_allclose(ht.load_netcdf(path, "y").numpy(),
                                   data[:, 0])
        # the bundled NetCDF dataset loads split (reference ships iris.nc)
        from heat_tpu import datasets

        iris = ht.load_netcdf(datasets.path("iris.nc"), "data", split=0)
        assert iris.shape == (150, 4)

    def test_save_replicated(self, tmp_path):
        data = np.arange(20, dtype=np.float32).reshape(4, 5)
        path = str(tmp_path / "r.h5")
        ht.save_hdf5(ht.array(data), path, "d")
        np.testing.assert_allclose(ht.load_hdf5(path, "d").numpy(), data)


class TestCommFacade:
    def test_chunk(self):
        comm = ht.get_comm()
        n = 10
        per = -(-n // comm.size)
        off, lshape, slices = comm.chunk((n, 4), 0, rank=0)
        assert off == 0 and lshape == (min(per, n), 4)
        off, lshape, _ = comm.chunk((n, 4), 0, rank=comm.size - 1)
        assert lshape[0] == max(0, n - per * (comm.size - 1))  # ceil-chunk tail
        counts, displs = comm.counts_displs(n)
        assert sum(counts) == n
        assert len(displs) == comm.size

    def test_collectives_in_shard_map(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.core._compat import shard_map

        comm = ht.get_comm()
        x = ht.arange(16, dtype=ht.float32, split=0)
        spec = comm.spec(1, 0)

        def body(blk):
            s = comm.psum(jnp.sum(blk))
            return jnp.broadcast_to(s, blk.shape)

        fn = shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=spec, check_vma=False)
        out = jax.jit(fn)(x.larray)
        np.testing.assert_allclose(np.asarray(out), 120.0)

    def test_ring_shift(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.core._compat import shard_map

        comm = ht.get_comm()
        n = comm.size
        x = ht.arange(n, dtype=ht.float32, split=0)
        spec = comm.spec(1, 0)

        fn = shard_map(
            lambda b: comm.ring_shift(b), mesh=comm.mesh, in_specs=spec, out_specs=spec,
            check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(x.larray))
        np.testing.assert_array_equal(out, np.roll(np.arange(n), 1))

    def test_exscan(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.core._compat import shard_map

        comm = ht.get_comm()
        n = comm.size
        x = ht.ones(n, split=0)
        spec = comm.spec(1, 0)
        fn = shard_map(
            lambda b: comm.exscan(jnp.sum(b)).reshape(1),
            mesh=comm.mesh, in_specs=spec, out_specs=spec, check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(x.larray))
        np.testing.assert_array_equal(out, np.arange(n))

    def test_split_subcomm(self):
        comm = ht.get_comm()
        if comm.size < 2:
            pytest.skip("needs >=2 devices")
        half = comm.size // 2
        sub = comm.Split(list(range(half)))
        assert sub.size == half
        x = ht.arange(8, split=0, comm=sub)
        assert int(x.sum().item()) == 28

    def test_use_comm(self):
        default = ht.get_comm()
        if default.size < 2:
            pytest.skip("needs >=2 devices")
        sub = default.Split([0, 1])
        ht.use_comm(sub)
        try:
            assert ht.get_comm().size == 2
        finally:
            ht.use_comm(default)
        with pytest.raises(TypeError):
            ht.use_comm("nope")


class TestPrinting:
    def test_printoptions(self):
        ht.set_printoptions(precision=2)
        assert ht.get_printoptions()["precision"] == 2
        ht.set_printoptions(profile="default")
        assert ht.get_printoptions()["precision"] == 4

    def test_print0(self, capsys):
        ht.print0("hello")
        assert "hello" in capsys.readouterr().out


class TestReferenceNamedAliases:
    """The MPI-named migration surface (reference ``communication.py:458-1872``):
    blocking names map onto the collectives, I-variants return a complete
    Request (XLA owns overlap)."""

    def test_blocking_aliases(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.core._compat import shard_map

        comm = ht.get_comm()
        n = comm.size
        x = ht.arange(4 * n, dtype=ht.float32, split=0)
        spec = comm.spec(1, 0)

        def body(blk):
            total = comm.Allreduce(jnp.sum(blk))        # 0+..+(4n-1)
            first = comm.Bcast(blk[:1], root=0)          # rank 0's first elem
            ex = comm.Exscan(jnp.sum(blk))
            inc = comm.Scan(jnp.sum(blk))
            return jnp.stack([total, first[0], ex, inc])  # (4,) per device

        fn = shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
        out = np.asarray(jax.jit(fn)(x.larray)).reshape(n, 4)
        shard_sums = np.arange(4 * n, dtype=np.float64).reshape(n, 4).sum(1)
        np.testing.assert_allclose(out[:, 0], 4 * n * (4 * n - 1) / 2)  # Allreduce
        np.testing.assert_allclose(out[:, 1], 0.0)                      # Bcast root 0
        np.testing.assert_allclose(                                     # Exscan
            out[:, 2], np.concatenate([[0.0], np.cumsum(shard_sums)[:-1]]))
        np.testing.assert_allclose(out[:, 3], np.cumsum(shard_sums))    # Scan

    def test_nonblocking_aliases_complete_requests(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.core._compat import shard_map

        comm = ht.get_comm()
        x = ht.arange(2 * comm.size, dtype=ht.float32, split=0)
        spec = comm.spec(1, 0)

        def body(blk):
            req = comm.Iallreduce(jnp.sum(blk))
            assert req.Test()
            return jnp.broadcast_to(req.Wait(), blk.shape)

        fn = shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
        out = np.asarray(jax.jit(fn)(x.larray))
        n = 2 * comm.size
        np.testing.assert_allclose(out, n * (n - 1) / 2)

    def test_alltoall_alias(self):
        import jax
        import jax.numpy as jnp
        from heat_tpu.core._compat import shard_map

        comm = ht.get_comm()
        n = comm.size
        x = ht.arange(n * n, dtype=ht.float32, split=0)  # n rows per device? n total
        spec = comm.spec(1, 0)

        def body(blk):
            return comm.Alltoall(blk, split_axis=0, concat_axis=0)

        fn = shard_map(body, mesh=comm.mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
        out = np.asarray(jax.jit(fn)(x.larray))
        want = np.arange(n * n, dtype=np.float32).reshape(n, n).T.reshape(-1)
        np.testing.assert_allclose(out, want)


class TestDistributedInit:
    def test_import_does_not_touch_backend_and_init_rebuilds_world(self):
        """`import heat_tpu` must leave the XLA backend uninitialized so
        `distributed_init` (multi-host bring-up) can still run; afterwards
        the world communicator spans the global device set."""
        import subprocess
        import sys

        import socket

        with socket.socket() as sock:  # a free port: concurrent runs must
            sock.bind(("localhost", 0))  # not collide on a fixed coordinator
            port = sock.getsockname()[1]
        code = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
            "import heat_tpu as ht\n"
            "import jax._src.xla_bridge as xb\n"
            "assert not xb.backends_are_initialized()\n"
            f"comm = ht.distributed_init(coordinator_address='localhost:{port}',\n"
            "                           num_processes=1, process_id=0)\n"
            "assert comm.size == 4 and ht.get_comm() is comm\n"
            "assert ht.MESH_WORLD is comm\n"
            "assert int(ht.arange(17, split=0).sum().item()) == 136\n"
        )
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           timeout=240)
        assert r.returncode == 0, r.stderr.decode()[-800:]
