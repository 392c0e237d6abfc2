"""Driver `cdist_jobs`: back-to-back `ht.spatial.cdist(X, X,
quadratic_expansion=True)` jobs on a resident array split by rows. The entry
point is the public function: DNDarray -> `spatial.distance._dist` -> the
Mosaic `cdist_tile` kernel (on the TPU) -> a DNDarray split by rows. A job is
one call, awaited with `block_until_ready`; its result (n x n float32, 6.4 GB
at 40,000 rows) is dropped before the next call is sent, as an analyst's loop
over such matrices has to on a 16 GB chip.

Data: standard-normal rows made ON the device from `--seed` in one jitted
call. The same X then feeds the plain reference (`references/cdist.py`) once
the window has closed: `dist_err` is the largest error of the window's LAST
result over every entry, relative to the largest distance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.drivers import _heat
from perfbench.references import cdist as ref


@functools.partial(jax.jit, static_argnames=("rows", "features"))
def make_rows(key, rows, features):
    return jax.random.normal(key, (rows, features), jnp.float32)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        c = ctx.config
        self.rows, self.features = int(c["n_rows"]), int(c["n_features"])
        self.expand = bool(c["quadratic_expansion"])
        self.block = int(ctx.traffic["check_block_rows"])
        self.limits = ctx.limits
        self.jobs = 0
        self.shapes = set()       # of every result since set-up
        self.last = None          # the newest result's device array

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import heat_tpu as ht
        from heat_tpu.core import fusion
        from heat_tpu.core.communication import TPUCommunication

        self.ht, self.fusion = ht, fusion
        self.comm = TPUCommunication(devices=self.ctx.devices)
        key = jax.random.key(self.ctx.seed % (2 ** 31))
        key = jax.random.fold_in(key, self.ctx.seed // (2 ** 31))
        self.xj = jax.device_put(make_rows(key, self.rows, self.features),
                                 self.comm.sharding(2, 0))
        self.x = ht.array(self.xj, split=0, copy=False, comm=self.comm)
        for _ in range(int(self.ctx.traffic["warm_jobs"])):
            self.job()
        self.jobs = 0
        self.shapes.clear()

    def job(self, x=None):
        self.last = None          # the old result goes before the new is sent
        x = self.x if x is None else x
        d = self.ht.spatial.cdist(x, x, quadratic_expansion=self.expand)
        self.last = d.larray
        self.last.block_until_ready()
        self.jobs += 1
        self.shapes.add((tuple(d.shape), str(self.last.dtype)))

    def counters(self):
        st = self.fusion.program_cache().stats()
        return {"program_cache_misses": st["misses"], "jobs": self.jobs,
                "fallbacks": _heat.fallbacks_total()}

    def sync(self):
        pass                      # a job ends in block_until_ready

    # -- the window -----------------------------------------------------
    def window(self, probe):
        n = 0
        while True:
            with probe.span("job"):
                self.job()
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
    def numbers(self):
        """`dist_err`: the last result against the direct form, every entry,
        over the largest distance. A result of another shape or type than
        (n, n) float32 is not an answer: it reads infinity."""
        got = self.last
        if got is None or self.shapes != {((self.rows, self.rows), "float32")}:
            return {"dist_err": float("inf")}
        x = jax.device_put(self.xj, self.ctx.devices[0])
        got = jax.device_put(got, self.ctx.devices[0])
        err, largest = jax.device_get(ref.worst_error(x, got, self.block))
        return {"dist_err": float(err) / float(largest),
                "largest_distance": float(largest)}

    def check(self, got=None):
        """The numbers beside their limits; `got` puts other numbers (the
        control's, a fault's) in the program's place."""
        got = self.numbers() if got is None else got
        return [(n, got[n], float(self.limits[n])) for n in self.limits]

    def readings(self):
        """One job's numbers (for setting limits: `tools/readings.py`)."""
        self.job()
        return self.numbers()

    def control(self):
        """The reference's expansion in the answer's place, product at `high`
        (the step below float32 at `highest`) and with bfloat16 operands."""
        self.last = None
        x = jax.device_put(self.xj, self.ctx.devices[0])
        out = {}
        for kind in ("high", "bf16"):
            err, largest = jax.device_get(
                ref.control_error(x, self.block, kind))
            out["dist_err." + kind] = float(err) / float(largest)
        out["dist_err"] = min(out.values())
        return out

    def close(self):
        self.xj = self.last = None
