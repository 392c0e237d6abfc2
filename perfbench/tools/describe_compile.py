"""Compile the cells' programs at their REAL sizes for a DESCRIBED v5e:2x2,
without the chip (the third rehearsal of the `on-chip-measurement` guide):

    JAX_PLATFORMS=cpu python3 -m perfbench.tools.describe_compile kmeans|train|reference

What the chip's compiler refuses here (memory, tiling, partitioning) it
refuses on the chip too; nothing runs, so this says nothing about results or
times. PR 28's verdicts are written into the cells' files (`memory` in the
KMeans configurations, `memory_analysis` in `traffic/train-s2048.json`). The
decode engine is not covered: it places its cache arena when it is
constructed, which a described device cannot hold; its programs were proved
on the chip itself.
"""

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from perfbench import run as harness  # noqa: E402


def show(name, compiled):
    m = compiled.memory_analysis()
    print(name, {"argument": m.argument_size_in_bytes,
                 "temp": m.temp_size_in_bytes, "output": m.output_size_in_bytes,
                 "alias": m.alias_size_in_bytes}, flush=True)


def attempt(name, thunk):
    try:
        show(name, thunk())
    except Exception as exc:  # noqa: BLE001 — the refusal IS the finding
        print(name, "REFUSED:", str(exc)[:400].replace("\n", " "), flush=True)


def config(name):
    return harness.load_json(os.path.join(harness.HERE, "configs", name + ".json"))


def kmeans(topo):
    from heat_tpu.cluster import kmeans as km
    from heat_tpu.core import fusion
    from heat_tpu.core.communication import TPUCommunication
    from perfbench.drivers import kmeans_fit
    from perfbench.references import lloyd

    for cfg in (config("heat-kmeans-w25m"), config("heat-kmeans-w25m-x4")):
        chips, rows, f, k = (cfg["chips"], cfg["n_rows"], cfg["n_features"],
                             cfg["n_clusters"])
        comm = TPUCommunication(devices=topo.devices[:chips])
        xs = jax.ShapeDtypeStruct((rows, f), jnp.float32,
                                  sharding=comm.sharding(2, 0))
        cs = jax.ShapeDtypeStruct((k, f), jnp.float32,
                                  sharding=comm.sharding(2, None))
        jdt = jnp.dtype(jnp.float32)
        step = km._lloyd_fused_fn((rows, f), jdt, k, rows, comm,
                                  fusion.quant_key(), fusion.chunk_key(),
                                  fusion.hier_key())
        attempt(f"lloyd step x{chips}", lambda: step.lower(xs, cs).compile())
        assign = km._assign_fn((rows, f), jdt, k, rows, comm)
        attempt(f"assignment x{chips}", lambda: assign.lower(xs, cs).compile())
        attempt(f"reference lloyd x{chips}", lambda: lloyd.lloyd(
            xs, cs, cfg["max_iter"]).lower(xs, cs).compile())
        axis = comm.axis_name if chips > 1 else None
        local = functools.partial(
            kmeans_fit._blobs_local, rows=rows // chips, features=f, k=k,
            sigma=cfg["blob_sigma"], axis=axis,
            drift=cfg.get("shard_drift", 0.0))
        blobs = jax.jit(jax.shard_map(local, mesh=comm.mesh, in_specs=P(),
                                      out_specs=comm.spec(2, 0),
                                      check_vma=False))
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                   sharding=comm.sharding(0, None))
        attempt(f"blobs x{chips}", lambda: blobs.lower(key).compile())


def _lm(topo):
    from heat_tpu.core import pallas_kernels as pk
    from perfbench.drivers import _lm as lm

    pk._interpret = lambda: False       # take the branch the chip takes
    pk.set_pallas(True)
    cfg = config("pythia-1.4b-d8")
    cfg = {k: v for k, v in cfg.items() if k != "rehearse"}
    model = lm.build_model(cfg, topo.devices[:1], {})
    shapes = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s, jnp.float32, sharding=h),
        lm.param_shapes(cfg), lm.shardings_of(model),
        is_leaf=lambda s: isinstance(s, tuple))
    return cfg, model, shapes


def train(topo):
    import optax

    cfg, model, psds = _lm(topo)
    tx = optax.adam(1e-3)
    rep = NamedSharding(model.grid.mesh, P())
    osds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(tx.init, psds))
    step = model.make_train_step(tx)
    for batch in (4, 2):
        toks = jax.ShapeDtypeStruct(
            (batch, cfg["max_position_embeddings"]), jnp.int32,
            sharding=NamedSharding(model.grid.mesh, model._data_spec()))
        attempt(f"train step B={batch}",
                lambda: step.lower(psds, osds, toks).compile())


def reference(topo):
    from perfbench.drivers import lm_decode
    from perfbench.references import lm as ref

    cfg, _model, psds = _lm(topo)
    one = SingleDeviceSharding(topo.devices[0])
    p1 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=one), psds)
    S = cfg["max_position_embeddings"]
    batch = jax.ShapeDtypeStruct((2, S), jnp.int32, sharding=one)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    row = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one)
    for fp8 in (False, True):
        attempt(f"reference adam step fp8={fp8}", lambda: ref.adam_step.lower(
            p1, p1, p1, batch, t, theta=10000.0, lr=1e-3, fp8=fp8).compile())
        attempt(f"reference gap row control={fp8}",
                lambda: lm_decode._widest_gap.lower(
                    p1, row, 5, 9, theta=10000.0, control=fp8).compile())


def main():
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    {"kmeans": kmeans, "train": train, "reference": reference}[sys.argv[1]](topo)


if __name__ == "__main__":
    main()
