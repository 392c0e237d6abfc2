"""Driver `kmeans_fit`: back-to-back `ht.cluster.KMeans(...).fit(x)` jobs on a
resident split array. The entry point is the public estimator: DNDarray ->
`fusion.fit_step_call` -> the Lloyd step -> (across chips) the packed psum,
one dispatch and one `float(shift)` sync per iteration, then the assignment
pass. Nothing private of `heat_tpu.cluster` is called.

Data: k well-separated Gaussian blobs made ON the device from `--seed`, each
chip generating its own rows in blocks (never through the host, never more
than a block of temporaries). The same X then feeds the plain reference
(`references/lloyd.py`) once the window has closed.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from perfbench.drivers import _heat
from perfbench.references import lloyd as ref


def _blobs_local(key, rows, features, k, sigma, axis, drift=0.0):
    """This chip's rows: centers[label] + sigma * normal, in blocks. Across
    chips the rows are NOT shuffled: chip r of n adds `drift * (r - (n-1)/2)
    / n` to every feature (the shifts sum to nought), so one chip's cluster
    means are not the whole array's and a lost exchange shows."""
    centers = jax.random.normal(jax.random.fold_in(key, 0), (k, features),
                                jnp.float32)
    dkey = jax.random.fold_in(key, 1)
    if axis:
        dkey = jax.random.fold_in(dkey, lax.axis_index(axis))
    nb = ref.n_blocks(rows)
    br = rows // nb
    shift = 0.0
    if axis and drift:
        n = lax.axis_size(axis)
        shift = drift * (lax.axis_index(axis) - (n - 1) / 2.0) / n

    # built transposed, (features, rows): that is how the TPU keeps an
    # (n, 64) float32 array, so the last `.T` moves nothing
    def block(i, xt):
        kl, kn = jax.random.split(jax.random.fold_in(dkey, i))
        lab = jax.random.randint(kl, (br,), 0, k)
        xb = sigma * jax.random.normal(kn, (features, br), jnp.float32)
        xb = xb + jnp.take(centers.T, lab, axis=1) + shift
        return lax.dynamic_update_slice_in_dim(xt, xb, i * br, 1)

    xt = lax.fori_loop(0, nb, block, jnp.zeros((features, rows), jnp.float32))
    return xt.T


def make_blobs(seed, rows, features, k, sigma, comm, drift=0.0):
    """(X split by rows over `comm`, init centroids (k, f) on the host)."""
    key = jax.random.key(seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    axis = comm.axis_name if comm.size > 1 else None
    local = functools.partial(_blobs_local, rows=rows // comm.size,
                              features=features, k=k, sigma=sigma, axis=axis,
                              drift=drift)
    fn = jax.jit(jax.shard_map(local, mesh=comm.mesh, in_specs=P(),
                               out_specs=comm.spec(2, 0), check_vma=False))
    x = fn(key)
    centers = jax.random.normal(jax.random.fold_in(key, 0), (k, features),
                                jnp.float32)
    init = centers + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), (k, features), jnp.float32)
    return x, np.asarray(init)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        c = ctx.config
        self.rows, self.features = int(c["n_rows"]), int(c["n_features"])
        self.k, self.iters = int(c["n_clusters"]), int(c["max_iter"])
        self.limits = ctx.limits
        self.jobs = []            # (centroids, inertia, n_iter) of every fit
        self._ref = None

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import heat_tpu as ht
        from heat_tpu.core import fusion
        from heat_tpu.core.communication import TPUCommunication

        self.ht, self.fusion = ht, fusion
        self.comm = TPUCommunication(devices=self.ctx.devices)
        if self.rows % self.comm.size:
            raise ValueError("rows must divide over the chips")
        xj, init = make_blobs(self.ctx.seed, self.rows, self.features, self.k,
                              float(self.ctx.config["blob_sigma"]), self.comm,
                              float(self.ctx.config.get("shard_drift", 0.0)))
        self.xj, self.init = xj, init
        self.x = ht.array(xj, split=0, copy=False, comm=self.comm)
        self.init_ht = ht.array(init, comm=self.comm)
        self.fit()                # the warm job: compiles step and assignment
        self.jobs.clear()

    def fit(self, x=None):
        km = self.ht.cluster.KMeans(
            n_clusters=self.k, init=self.init_ht, max_iter=self.iters,
            tol=float(self.ctx.config["tol"]))
        km.fit(self.x if x is None else x)
        out = (np.asarray(km.cluster_centers_.numpy(), np.float64),
               float(km.inertia_), int(km.n_iter_))
        self.jobs.append(out)
        return out

    def counters(self):
        st = self.fusion.program_cache().stats()
        return {"program_cache_misses": st["misses"],
                "program_cache_hits": st["hits"],
                "iterations": sum(j[2] for j in self.jobs),
                "fallbacks": _heat.fallbacks_total()}

    def sync(self):
        pass                      # a fit ends in float(inertia): nothing queued

    # -- the window -----------------------------------------------------
    def window(self, probe):
        n = 0
        while True:
            with probe.span("fit"):
                self.fit()
            n += 1
            probe.unit()
            if probe.done():
                break
        elapsed = probe.elapsed()
        return {"metrics": {"job_ms": 1e3 * elapsed / n},
                "attempted": n, "failed": 0}

    def release(self):
        self.x = None

    # -- correct ----------------------------------------------------------
    def numbers(self, jobs):
        """The numbers compared, worst over `jobs`, against the reference.
        `centroid_err` is the worst centroid coordinate's error over the
        largest coordinate, as it stands. Two more are reported beside it and
        carry no limit: `centroid_shrink`, the common factor by which the
        program's centroids lie off the reference's (signed, least squares),
        and `centroid_resid`, the worst error once that factor is taken out.
        They tell a common scale error from scatter (PERF.md section 7.1)."""
        if self._ref is None:
            self._ref = jax.device_get(
                ref.lloyd(self.xj, None, self.iters)(
                    self.xj, jnp.asarray(self.init)))
        c_ref, i_ref, _counts = self._ref
        c_ref = np.asarray(c_ref, np.float64)
        scale = float(np.abs(c_ref).max())
        resid, shrink = [], []
        for c, _i, _n in jobs:
            e = c - c_ref
            a = float((e * c_ref).sum() / (c_ref * c_ref).sum())
            shrink.append(a)
            resid.append(float(np.abs(e - a * c_ref).max()) / scale)
        return {
            "centroid_err": max(float(np.abs(c - c_ref).max()) / scale
                                for c, _i, _n in jobs),
            "inertia_rel": max(abs(i - float(i_ref)) / float(i_ref)
                               for _c, i, _n in jobs),
            "iters_missing": float(max(self.iters - n for _c, _i, n in jobs)),
            "centroid_shrink": max(shrink, key=abs),
            "centroid_resid": max(resid),
        }

    def dump(self):
        """The last job's centroids and the reference's (`readings --dump`)."""
        return {"program": self.jobs[-1][0].tolist(),
                "reference": np.asarray(self._ref[0], np.float64).tolist()}

    def check(self, got=None):
        """The numbers beside their limits; `got` puts other numbers (the
        control's, a fault's) in the program's place."""
        got = self.numbers(self.jobs) if got is None else got
        return [(n, got[n], float(self.limits[n])) for n in self.limits]

    def readings(self):
        """One job's numbers (for setting limits: `tools/readings.py`)."""
        self.jobs.clear()
        return self.numbers([self.fit()])

    def control(self):
        """The program's own lower-precision path: the same fit on X stored
        in bfloat16 (centroids carried in bfloat16, float32 accumulation)."""
        xb = self.ht.array(self.xj.astype(jnp.bfloat16), split=0, copy=False,
                           comm=self.comm)
        self.jobs.clear()
        job = self.fit(xb)
        del xb
        return self.numbers([job])

    def close(self):
        self.xj = None
