"""Checkpoint/resume and profiling subsystem tests (these subsystems exceed
the reference, which has neither — SURVEY.md §5)."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

import heat_tpu as ht


class TestCheckpoint:
    def test_dndarray_roundtrip(self, tmp_path):
        x = ht.arange(26, dtype=ht.float32, split=0)
        ht.utils.save_checkpoint(str(tmp_path / "ck"), {"x": x, "note": "hello"}, step=3)
        state = ht.utils.load_checkpoint(str(tmp_path / "ck"))
        assert state["__step__"] == 3
        assert state["note"] == "hello"
        restored = state["x"]
        assert restored.split == 0
        assert restored.dtype is ht.float32
        np.testing.assert_array_equal(restored.numpy(), np.arange(26, dtype=np.float32))

    def test_pytree_roundtrip(self, tmp_path):
        import jax.numpy as jnp

        params = {"layer1": {"w": jnp.ones((3, 4)), "b": jnp.zeros(4)},
                  "layer2": {"w": jnp.full((4, 2), 2.0)}}
        ht.utils.save_checkpoint(str(tmp_path / "ck"), {"params": params})
        state = ht.utils.load_checkpoint(str(tmp_path / "ck"))
        np.testing.assert_array_equal(np.asarray(state["params"]["layer1"]["w"]), np.ones((3, 4)))
        np.testing.assert_array_equal(np.asarray(state["params"]["layer2"]["w"]), np.full((4, 2), 2.0))

    def test_train_resume(self, tmp_path):
        """Checkpoint mid-training, restore, continue — losses must match."""
        import flax.linen as fnn

        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        y = (X.sum(1) > 0).astype(np.int32)
        xd, yd = ht.array(X, split=0), ht.array(y, split=0)

        class Net(fnn.Module):
            @fnn.compact
            def __call__(self, x):
                return fnn.Dense(2)(x)

        def make_net():
            opt = ht.optim.DataParallelOptimizer(ht.optim.SGD(lr=0.1))
            return ht.nn.DataParallel(Net(), optimizer=opt)

        net = make_net()
        net.init(xd)
        for _ in range(3):
            net.step(xd, yd)
        ht.utils.save_checkpoint(str(tmp_path / "ck"), {"params": net.params})
        ref_losses = [net.step(xd, yd) for _ in range(3)]

        net2 = make_net()
        net2.init(xd)
        state = ht.utils.load_checkpoint(str(tmp_path / "ck"))
        net2.params = state["params"]
        net2.optimizer.reset_state(net2.params)
        new_losses = [net2.step(xd, yd) for _ in range(3)]
        np.testing.assert_allclose(ref_losses, new_losses, rtol=1e-5)

    def test_estimator_checkpoint(self, tmp_path):
        data = np.random.default_rng(1).random((40, 3)).astype(np.float32)
        km = ht.cluster.KMeans(n_clusters=2, max_iter=10, random_state=0)
        km.fit(ht.array(data, split=0))
        ht.utils.checkpoint_estimator(str(tmp_path / "km"), km)
        km2 = ht.cluster.KMeans(n_clusters=2)
        ht.utils.restore_estimator(str(tmp_path / "km"), km2)
        np.testing.assert_allclose(
            km2.cluster_centers_.numpy(), km.cluster_centers_.numpy(), rtol=1e-6
        )
        with pytest.raises(TypeError):
            ht.utils.restore_estimator(str(tmp_path / "km"), ht.cluster.KMedians())


class TestProfiling:
    def test_timer(self):
        x = ht.random.rand(1000, split=0)
        with ht.utils.profiling.Timer("sum") as t:
            s = x.sum()
            t.sync(s.larray)
        assert t.seconds is not None and t.seconds > 0

    def test_annotate(self):
        # `annotate` became `span` (PR 29): off it is the shared null object,
        # on it is a record in the ring around the work it encloses
        prof = ht.utils.profiling
        assert prof.span("scope") is prof.span("other")
        prof.clear()
        prof.enable()
        try:
            with prof.span("scope", n=4):
                _ = ht.arange(4).sum()
        finally:
            prof.disable()
        rec = [r for r in prof.spans() if r.name == "scope"]
        assert len(rec) == 1 and rec[0].attrs == {"n": 4}
        assert rec[0].t1 > rec[0].t0
        prof.clear()


class TestPytreeStructureRoundTrip:
    def test_optax_state_namedtuples(self, tmp_path):
        import jax
        import jax.numpy as jnp
        import optax

        tx = optax.adam(1e-3)
        params = {"w": jnp.ones((3, 2)), "b": jnp.zeros(2)}
        state = tx.init(params)
        ht.utils.save_checkpoint(str(tmp_path / "ck"), {"opt": state, "params": params})
        st = ht.utils.load_checkpoint(str(tmp_path / "ck"))
        assert jax.tree_util.tree_structure(st["opt"]) == jax.tree_util.tree_structure(state)
        # a further update step must accept the restored state
        tx.update(jax.tree_util.tree_map(jnp.zeros_like, params), st["opt"], st["params"])

    def test_list_tuple_and_nested_dndarray(self, tmp_path):
        import jax.numpy as jnp

        state = {"misc": {"l": [jnp.ones(2)], "t": (jnp.ones(2),), "d": ht.arange(8, split=0)}}
        ht.utils.save_checkpoint(str(tmp_path / "ck"), state)
        st = ht.utils.load_checkpoint(str(tmp_path / "ck"))
        assert isinstance(st["misc"]["l"], list)
        assert isinstance(st["misc"]["t"], tuple)
        assert isinstance(st["misc"]["d"], ht.DNDarray) and st["misc"]["d"].split == 0


class TestCheckpointManager:
    def test_rotation_and_restore(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "run"), every_steps=2, keep=2)
        for step in range(1, 8):
            wrote = mgr.save(step, {"w": jnp.full((3,), float(step)), "step": step})
            assert wrote == (step % 2 == 0)
        assert mgr.all_steps() == [4, 6]  # keep=2 rotation
        step, state = mgr.restore()
        assert step == 6 and state["step"] == 6
        np.testing.assert_allclose(np.asarray(state["w"]), 6.0)

    def test_restore_skips_corrupt_newest(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager, _MANIFEST

        mgr = CheckpointManager(str(tmp_path / "run"), keep=3)
        mgr.save(1, {"v": 1}, force=True)
        mgr.save(2, {"v": 2}, force=True)
        # corrupt the newest manifest (as a crash mid-write would)
        manifest = os.path.join(mgr._path(2), _MANIFEST)
        with open(manifest, "w") as f:
            f.write("{ not json")
        step, state = mgr.restore()
        assert step == 1 and state["v"] == 1

    def test_run_with_recovery(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager, run_with_recovery

        mgr = CheckpointManager(str(tmp_path / "run"), every_steps=1, keep=2)
        crashes = {"left": 2}

        def train(state, start, save):
            assert "__step__" not in state  # restore() returns the saved dict
            w = state["w"]
            for step in range(start, 10):
                w = w + 1.0
                save(step + 1, {"w": w})
                # crash on the first save of each attempt while budget lasts
                # (a fixed step would never recur after resuming past it)
                if step == start and crashes["left"] > 0:
                    crashes["left"] -= 1
                    raise RuntimeError("simulated preemption")
            return {"w": w}

        out = run_with_recovery(train, mgr, {"w": jnp.zeros(())})
        # every step contributes exactly once despite two crashes
        assert crashes["left"] == 0
        assert float(out["w"]) == 10.0

    def test_run_with_recovery_gives_up(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager, run_with_recovery

        mgr = CheckpointManager(str(tmp_path / "run2"), every_steps=1, keep=1)

        def always_fails(state, start, save):
            raise RuntimeError("hard failure")

        with pytest.raises(RuntimeError, match="hard failure"):
            run_with_recovery(always_fails, mgr, {"w": 0}, max_failures=2)

    def test_run_with_recovery_max_restarts_bounded_and_counted(self, tmp_path):
        """max_restarts bounds the retry loop (default 3) and each restart
        ticks checkpoint.recovery_restarts in the process-wide counters."""
        from heat_tpu.utils import metrics as _pm
        from heat_tpu.utils.checkpointing import CheckpointManager, run_with_recovery

        mgr = CheckpointManager(str(tmp_path / "runb"), every_steps=1, keep=1)
        attempts = {"n": 0}

        def always_fails(state, start, save):
            attempts["n"] += 1
            raise RuntimeError("hard failure")

        before = int(_pm.counters().get("checkpoint.recovery_restarts", 0))
        with pytest.raises(RuntimeError, match="hard failure"):
            run_with_recovery(always_fails, mgr, {"w": 0}, max_restarts=2,
                              backoff_s=0.001)
        # 1 initial attempt + 2 bounded restarts, each restart counted
        assert attempts["n"] == 3
        assert int(_pm.counters().get(
            "checkpoint.recovery_restarts", 0)) == before + 2

    def test_restore_quarantines_corruption_kinds(self, tmp_path):
        """Regression (ISSUE 8 satellite): garbage in step N — bad
        manifest JSON, missing leaf file, truncated npz — must restore
        step N-1, quarantine N under a .corrupt rename (NOT delete it),
        and count checkpoint.corrupt_skipped."""
        import warnings

        from heat_tpu.utils import metrics as _pm
        from heat_tpu.utils.checkpointing import CheckpointManager, _MANIFEST

        def corrupt_manifest(path):
            with open(os.path.join(path, _MANIFEST), "w") as f:
                f.write("{ not json")

        def missing_leaf(path):
            os.unlink(os.path.join(path, "arrays.npz"))

        def truncated_leaf(path):
            npz = os.path.join(path, "arrays.npz")
            with open(npz, "rb") as f:
                blob = f.read()
            with open(npz, "wb") as f:
                f.write(blob[: max(4, len(blob) // 3)])

        for i, corrupt in enumerate(
                [corrupt_manifest, missing_leaf, truncated_leaf]):
            mgr = CheckpointManager(str(tmp_path / f"q{i}"), keep=3)
            mgr.save(1, {"v": 1, "w": jnp.arange(4.0)}, force=True)
            mgr.save(2, {"v": 2, "w": jnp.arange(4.0) * 2}, force=True)
            corrupt(mgr._path(2))
            before = int(_pm.counters().get("checkpoint.corrupt_skipped", 0))
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                step, state = mgr.restore()
            assert step == 1 and state["v"] == 1, corrupt.__name__
            assert os.path.isdir(mgr._path(2) + ".corrupt"), corrupt.__name__
            assert not os.path.exists(mgr._path(2)), corrupt.__name__
            assert int(_pm.counters().get(
                "checkpoint.corrupt_skipped", 0)) == before + 1
            # the quarantined dir survives the next save's orphan sweep
            # (it is evidence, not a dead partial write)
            mgr.save(3, {"v": 3}, force=True)
            assert os.path.isdir(mgr._path(2) + ".corrupt"), corrupt.__name__

    def test_transient_write_fault_retried_atomically(self, tmp_path):
        """An injected IO error on the leaf/manifest write is retried once
        and never leaves a temp or partial file visible."""
        from heat_tpu.utils import faults
        from heat_tpu.utils import metrics as _pm
        from heat_tpu.utils.checkpointing import (load_checkpoint,
                                                  save_checkpoint)

        for site in ("checkpoint.leaf.write", "checkpoint.manifest.write"):
            path = str(tmp_path / site.replace(".", "_"))
            before = int(_pm.counters().get("checkpoint.write_retries", 0))
            with faults.inject(f"{site}=nth:1"):
                save_checkpoint(path, {"w": jnp.arange(3.0), "n": 7})
            assert int(_pm.counters().get(
                "checkpoint.write_retries", 0)) == before + 1
            state = load_checkpoint(path)
            np.testing.assert_array_equal(np.asarray(state["w"]),
                                          np.arange(3.0))
            assert state["n"] == 7
            leftovers = [f for f in os.listdir(path)
                         if f not in ("arrays.npz", "manifest.json")]
            assert leftovers == [], leftovers

    def test_persistent_write_fault_raises_without_partial(self, tmp_path):
        """Two IO failures surface the error; the checkpoint dir holds no
        half-written payload under the real names."""
        from heat_tpu.utils import faults
        from heat_tpu.utils.checkpointing import save_checkpoint

        path = str(tmp_path / "persist")
        with faults.inject("checkpoint.leaf.write=every:1"):
            with pytest.raises(OSError):
                save_checkpoint(path, {"w": jnp.arange(3.0)})
        assert "arrays.npz" not in os.listdir(path)
        assert "manifest.json" not in os.listdir(path)

    def test_non_io_write_error_leaves_no_temp_file(self, tmp_path):
        """A non-OSError mid-write (unserializable manifest value) must
        raise immediately AND still unlink the temp file — the atomic
        contract is 'temp never survives', not 'temp cleaned on IO
        errors only'."""
        from heat_tpu.utils.checkpointing import save_checkpoint

        path = str(tmp_path / "nonio")
        with pytest.raises(TypeError):
            # a tuple dict key is not JSON-serializable: json.dump raises
            # TypeError inside the manifest write, past the leaf write
            save_checkpoint(path, {"bad": {(1, 2): 3.0}})
        leftovers = [f for f in os.listdir(path) if ".tmp" in f]
        assert leftovers == [], leftovers
        # and no manifest became visible for the failed save
        assert "manifest.json" not in os.listdir(path)

    def test_orphan_partial_checkpoints_swept(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "run3"), every_steps=1, keep=2)
        mgr.save(1, {"v": 1})
        # simulate a crash mid-save: dir exists, no manifest
        orphan = os.path.join(mgr.directory, "ckpt_000000000099")
        os.makedirs(orphan)
        with open(os.path.join(orphan, "arrays.npz"), "wb") as f:
            f.write(b"partial")
        mgr.save(2, {"v": 2})
        assert not os.path.exists(orphan)
        assert mgr.all_steps() == [1, 2]

    def test_retry_gets_pristine_init_state(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager, run_with_recovery

        mgr = CheckpointManager(str(tmp_path / "run4"), every_steps=100, keep=1)
        attempts = {"n": 0}

        def train(state, start, save):
            attempts["n"] += 1
            state["epoch"] += 1  # in-place mutation before any save lands
            if attempts["n"] == 1:
                raise RuntimeError("crash before first checkpoint")
            return state

        out = run_with_recovery(train, mgr, {"epoch": 0})
        assert out["epoch"] == 1  # not 2: retry saw a fresh copy

    def test_retry_copy_handles_dndarrays(self, tmp_path):
        """The per-attempt fresh copy must not deepcopy device handles:
        DNDarray-bearing init states work and arrays are shared, not
        round-tripped through the host."""
        from heat_tpu.utils.checkpointing import CheckpointManager, run_with_recovery

        mgr = CheckpointManager(str(tmp_path / "run5"), every_steps=100, keep=1)
        init = {"x": ht.arange(16, split=0), "n": np.zeros(2), "lst": []}
        attempts = {"n": 0}

        def train(state, start, save):
            attempts["n"] += 1
            assert isinstance(state["x"], ht.DNDarray) and state["x"].split == 0
            state["lst"].append(attempts["n"])  # container mutation
            state["n"][0] = attempts["n"]       # numpy mutation
            if attempts["n"] == 1:
                raise RuntimeError("crash")
            return state

        out = run_with_recovery(train, mgr, init)
        assert out["lst"] == [2] and out["n"][0] == 2  # no leak from attempt 1
        assert init["lst"] == [] and init["n"][0] == 0  # init untouched

    def test_retry_copy_deep_copies_odd_mutables(self, tmp_path):
        from heat_tpu.utils.checkpointing import CheckpointManager, run_with_recovery

        mgr = CheckpointManager(str(tmp_path / "run6"), every_steps=100, keep=1)
        init = {"seen": set(), "buf": bytearray(b"ab")}
        attempts = {"n": 0}

        def train(state, start, save):
            attempts["n"] += 1
            state["seen"].add(attempts["n"])
            state["buf"][0] = attempts["n"]
            if attempts["n"] == 1:
                raise RuntimeError("crash")
            return state

        out = run_with_recovery(train, mgr, init)
        assert out["seen"] == {2}          # attempt 1's mutation didn't leak
        assert init["seen"] == set() and init["buf"] == bytearray(b"ab")


class TestMetrics:
    def test_counters_gauges_observations(self, tmp_path):
        from heat_tpu.utils.metrics import Metrics

        m = Metrics()
        m.inc("steps"); m.inc("steps"); m.inc("tokens", 512)
        m.gauge("lr", 3e-4)
        for v in (0.5, 0.4, 0.3):
            m.observe("loss", v)
        with m.timer("step_time"):
            pass
        snap = m.to_dict()
        assert snap["counters"]["steps"] == 2
        assert snap["counters"]["tokens"] == 512
        assert snap["gauges"]["lr"] == 3e-4
        loss = snap["series"]["loss"]
        assert loss["count"] == 3 and loss["last"] == 0.3
        assert loss["min"] == 0.3 and loss["max"] == 0.5
        assert snap["series"]["step_time"]["count"] == 1

        p = tmp_path / "m.jsonl"
        m.dump(str(p), step=7)
        m.observe("loss", 0.2)
        m.dump(str(p), step=8)
        import json as _json

        lines = [_json.loads(l) for l in open(p)]
        assert len(lines) == 2 and lines[0]["step"] == 7
        # dump windows the series: line 2 only sees the post-dump value,
        # counters persist
        assert lines[1]["series"]["loss"]["count"] == 1
        assert lines[1]["counters"]["steps"] == 2

    def test_name_collisions_are_sectioned(self):
        from heat_tpu.utils.metrics import Metrics

        m = Metrics()
        m.inc("loss")             # a counter AND a series named "loss"
        m.observe("loss", 0.4)
        snap = m.to_dict()
        assert snap["counters"]["loss"] == 1
        assert snap["series"]["loss"]["last"] == 0.4

    def test_nonfinite_values_stay_valid_json(self, tmp_path):
        from heat_tpu.utils.metrics import Metrics

        m = Metrics()
        m.observe("loss", float("nan"))
        m.gauge("g", float("inf"))
        p = tmp_path / "m.jsonl"
        m.dump(str(p))
        import json as _json

        rec = _json.loads(open(p).read())  # must parse strictly
        assert rec["series"]["loss"]["last"] is None
        assert rec["gauges"]["g"] is None

    def test_device_scalars_fetched_at_dump(self):
        import jax.numpy as jnp

        from heat_tpu.utils.metrics import Metrics

        m = Metrics()
        m.observe("loss", jnp.asarray(1.5))
        m.gauge("g", jnp.asarray(2.0))
        snap = m.to_dict()
        assert snap["series"]["loss"]["last"] == 1.5
        assert snap["gauges"]["g"] == 2.0

    def test_timer_sync_handle(self):
        import jax.numpy as jnp

        from heat_tpu.utils.metrics import Metrics

        m = Metrics()
        with m.timer("t") as t:
            s = jnp.arange(1000).sum()
            t.sync(s)
        assert m.to_dict()["series"]["t"]["last"] > 0

    def test_module_level_registry(self):
        from heat_tpu.utils import metrics

        metrics.reset()
        metrics.inc("x")
        assert metrics.to_dict()["counters"]["x"] == 1
        metrics.reset()
        assert metrics.to_dict()["counters"] == {}

    def test_nonscalar_and_nonfinite_counters_dump_strictly(self, tmp_path):
        import jax.numpy as jnp

        from heat_tpu.utils.metrics import Metrics

        m = Metrics()
        m.gauge("per_class", jnp.arange(4.0))       # non-scalar device array
        m.inc("bad_sum", float("nan"))               # non-finite counter
        p = tmp_path / "m.jsonl"
        m.dump(str(p))
        import json as _json

        rec = _json.loads(open(p).read(), parse_constant=lambda c: 1 / 0)
        assert rec["gauges"]["per_class"] == [0.0, 1.0, 2.0, 3.0]
        assert rec["counters"]["bad_sum"] is None
