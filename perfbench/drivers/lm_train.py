"""Driver `lm_train`: `TransformerLM.make_train_step(optax.adam(lr))` on seeded
token batches, one chip. The entry point is the public one: forward, flash
attention backward, the packed step through `fusion`, the optimizer update,
parameters and optimizer state donated every step.

ONE object (the compiled step with its state) is built in set-up, driven from
the seed through its first three steps by the window's own call and feed, and
handed on to the window. Those three steps are what `correct` compares with
the plain reference (`references/lm.py`): each step's loss, the norm of the
first gradient per leaf (from Adam's first moment after one step), and the
norm of each leaf's change after the three.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from perfbench import traffic as gen
from perfbench.drivers import _heat, _lm
from perfbench.references import lm as ref

CHECK_STEPS = 3
B1 = 0.9                      # optax.adam's default, as references/lm.py


def _names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree.leaves_with_path(tree)]


def _host(tree):
    return np.asarray([float(x) for x in jax.tree.leaves(jax.device_get(tree))])


_leaf_norms = jax.jit(ref._leaf_norms)
_change_norms = jax.jit(ref.change_norms)


def compare(got, want):
    """The numbers compared. `got`/`want`: dicts with `losses` (3,),
    `grad` and `change` (per-leaf norms, same leaf order). Gaps of norms by
    the worst leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Leaves whose reference gradient is
    under a thousandth of the median leaf's move under Adam by round-off
    alone and are left out of the change."""
    g_ref, c_ref = want["grad"], want["change"]
    g_floor, c_floor = np.median(g_ref), np.median(c_ref)
    grad_gap = np.abs(got["grad"] - g_ref) / np.maximum(g_ref, g_floor)
    moved = g_ref >= 1e-3 * g_floor
    change_gap = (np.abs(got["change"] - c_ref)
                  / np.maximum(c_ref, c_floor))[moved]
    return {"loss_err": float(np.max(np.abs(got["losses"] - want["losses"]))),
            "grad_norm_gap": float(grad_gap.max()),
            "change_gap": float(change_gap.max())}


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.B, self.S, self.Q = int(t["batch"]), int(t["seq"]), int(t["queue"])
        self.lr = float(t["lr"])
        self.limits = ctx.limits
        self.steps_done = 0
        self._ref = None

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import optax
        from heat_tpu.core import fusion

        self.fusion = fusion
        cfg = self.ctx.config
        self.model = _lm.build_model(cfg, self.ctx.devices, self.ctx.memo)
        self.key = _lm.seed_key(self.ctx.seed)
        self.shardings = _lm.shardings_of(self.model)
        self.params = self._fresh_params()
        tx = optax.adam(self.lr)
        self.opt = tx.init(self.params)
        if "step" not in self.ctx.memo:
            self.ctx.memo["step"] = self.model.make_train_step(tx)
        self.step = self.ctx.memo["step"]
        # the feed: Q batches of B rows that all differ, on the device
        self.host_batches = gen.token_batches(
            self.ctx.traffic, self.ctx.seed, cfg["vocab_size"])
        self.queue = [self.model.shard_batch(b) for b in self.host_batches]
        # the first three steps, through the window's own call and feed
        losses, grad = [], None
        for i in range(CHECK_STEPS):
            loss = self._step()
            losses.append(float(jax.device_get(loss)))
            if i == 0:
                grad = _host(_leaf_norms(self.opt[0].mu)) / (1.0 - B1)
        p0 = self._fresh_params()
        change = _host(_change_norms(self.params, p0))
        del p0
        self.first = {"losses": np.asarray(losses), "grad": grad,
                      "change": change}
        self.window_losses = []

    def _fresh_params(self):
        return _lm.make_params(jax.random.fold_in(self.key, 0),
                               self.ctx.config, self.shardings)

    def _step(self):
        batch = self.queue[self.steps_done % self.Q]
        self.params, self.opt, loss = self.step(self.params, self.opt, batch)
        self.steps_done += 1
        return loss

    def counters(self):
        return {"steps": self.steps_done,
                "program_cache_misses":
                    self.fusion.program_cache().stats()["misses"],
                "fallbacks": _heat.fallbacks_total()}

    def sync(self):
        jax.block_until_ready(self.params)

    # -- the window -----------------------------------------------------
    def window(self, probe):
        n, prev = 0, None
        while True:
            with probe.span("step"):
                loss = self._step()
            if prev is not None:
                with probe.span("wait"):
                    prev.block_until_ready()   # one step in flight, no more
            prev = loss
            self.window_losses.append(loss)
            n += 1
            probe.unit()
            if probe.done():
                break
        loss.block_until_ready()
        elapsed = probe.elapsed()
        got = np.asarray(jax.device_get(self.window_losses), np.float64)
        return {"metrics": {"train_tokens_per_s": n * self.B * self.S / elapsed},
                "attempted": n, "failed": int((~np.isfinite(got)).sum()),
                "tokens_per_step": self.B * self.S}

    def release(self):
        self.params = self.opt = self.queue = self.window_losses = None

    # -- correct ----------------------------------------------------------
    def _reference(self, fp8=False, rows=None):
        """Three steps of the plain reference from the same seed: what the
        program's first three steps are compared with. `fp8`: the control.
        `rows`: only the first `rows` rows of every batch (a planted fault)."""
        theta = float(self.ctx.config["rotary_emb_base"])
        batches = jnp.asarray(self.host_batches[:CHECK_STEPS, :rows])
        p0 = jax.device_put(self._fresh_params(), jax.devices()[0])
        p3, losses, grad = ref.train_steps(p0, batches, theta, self.lr, fp8)
        p0 = jax.device_put(self._fresh_params(), jax.devices()[0])
        out = {"losses": np.asarray(jax.device_get(losses), np.float64),
               "grad": _host(grad), "change": _host(_change_norms(p3, p0))}
        del p0, p3
        return out

    def reference(self):
        if self._ref is None:
            self._ref = self._reference()
        return self._ref

    def check(self, got=None):
        """The numbers beside their limits; `got` puts other numbers (the
        control's, a fault's) in the program's place."""
        got = compare(self.first, self.reference()) if got is None else got
        return [(n, got[n], float(self.limits[n])) for n in self.limits]

    def readings(self):
        self.release()
        out = compare(self.first, self.reference())
        out["first_loss"] = float(self.first["losses"][0])
        out["leaves"] = dict(zip(_names(self.shardings), (
            np.abs(self.first["grad"] - self._ref["grad"])
            / np.maximum(self._ref["grad"], np.median(self._ref["grad"]))
        ).round(5).tolist()))
        return out

    def control(self):
        return compare(self._reference(fp8=True), self.reference())

    def faults(self):
        return {"half_batch": compare(self._reference(rows=self.B // 2),
                                      self.reference())}

    def close(self):
        self.step = self.model = None
