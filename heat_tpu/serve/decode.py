"""Continuous-batching decode engine: slot-based device-resident cache
with in-flight request join/leave (ROADMAP item 2(d) — the LLM-serving
traffic shape).

``TransformerLM.generate()`` is a monolithic batch program: every sequence
in a batch runs until the longest finishes, and new requests wait for the
whole batch to drain (the convoy effect). The :class:`DecodeEngine`
replaces that for serving traffic with SLOTS independent lanes over a
persistent, device-resident KV cache:

* **State.** What each layer keeps between tokens is the MODEL's to say
  (``TransformerLM.cache_layout``); the engine holds that tree, hands it to
  its two programs, donates it, and never looks inside. It is a leaf a
  layer: a dense layer's K and V lanes, each ``(SLOTS, S_cap, H, Dh)``
  (slots over dp, heads over tp; all layers' together are the "arena" of
  ``stats()``); in a per-layer pattern a ring of ``window`` rows for a
  window-attention layer, ONE lane of ``S_cap`` rows for the full-attention
  layer (the cross layers read it), a float32 recurrent state and a
  convolution tail for a state-space layer, nothing for a gated memory
  unit, a lane of its own for a grouped-query layer, ONE latent lane of
  ``kv_lora_rank + qk_rope_head_dim`` a position for a latent-attention
  layer, which all its heads read. A step writes one
  row a slot into each leaf and reads each leaf once, where it lies; a
  prefill writes one slot's rows: on one device no lane is copied, sliced
  out or written back. Plus per-slot position and
  last-token vectors — all device-resident
  for the engine's lifetime. ``S_cap`` is a rung of the power-of-two
  sequence ladder (``TransformerLM.prompt_bucket``), and every prompt pads
  onto the same ladder, so the compiled-program set is finite by
  construction. A granted slot's recurrent state is RESET by its prefill:
  the prompt's scan starts from zero and overwrites the state and the tail
  whole (a freed slot's state is live garbage: it is not masked by a
  position the way stale K/V rows are).
* **Experts.** A pattern's "moe" layers route every token over ALL experts
  and compute the ones the device holds (``TransformerLM``'s
  ``experts_held``), with no capacity. Both programs hand the count of
  routed pairs by held expert back in the SAME vector as the sampled tokens
  (one fetch a dispatch, as before); ``stats()`` sums them as
  ``moe_pairs_total`` / ``moe_pairs_held`` / ``moe_pairs_by_expert``. A
  model without such a layer has none of the three and its programs are
  what they were.
* **Log-probabilities.** ``DecodeConfig(logprobs=True)``: both programs
  also take each sampled token's log-probability (float32, the softmax of
  the step's own logits) and hand it back in that same one vector; a
  request's are on its future as ``future.logprobs`` (one a generated
  token) from the moment it is done. What a caller ranks answers by, and
  what lets a judge hold the engine's OWN arithmetic, slot by slot, against
  a reference where the tokens alone say little. Off (the default), the
  programs are what they were.
* **Exactly TWO executables per (bucket, codec) signature.** A bucketed
  PREFILL program (runs the padded prompt forward, writes what the cache
  keeps of it into a free slot, samples the first token) and ONE
  donated-carry DECODE-STEP
  program (cache, positions, live-mask, tokens in; cache donated back)
  dispatched repeatedly. Steady-state decoding compiles nothing, and the
  only per-step device→host transfer is the sampled-token vector
  (SLOTS·int32) — cache, positions and logits never leave the device
  (audited via ``jax.transfer_guard`` in ``tests/test_serve_decode.py``).
* **Join/leave is masked, not specialized.** A finished slot (EOS or
  max_new_tokens) resolves its future and goes dead in the live-mask; a
  queued request prefills into the free slot between steps. The ONE step
  executable serves every occupancy — it never re-specializes.
* **Program keys carry the wire-codec configuration.** Like every other
  builder cache, prefill/step programs key on ``fusion.quant_key() /
  chunk_key() / hier_key()`` — the per-token tp psums ride
  :func:`heat_tpu.core.fusion.packed_psum`, so codec toggles compile
  SIBLING programs, toggle-back re-hits, and steady-state misses stay 0.
* **Tenancy.** ``register_tenant`` arms the same
  :class:`~heat_tpu.serve.admission.AdmissionController` registry the
  batch executor uses: slot grants are priority-ordered (FIFO within a
  priority), tenant ``slo_ms`` is the default deadline, and per-tenant
  admitted/completed/shed counters fold into ``runtime_stats()``.
* **Fault containment.** A failed decode-step dispatch that left the
  donated buffers intact degrades that step to the step program's OWN
  body run uncompiled (``jax.disable_jit()`` around the same call: op by
  op on one device, under its ``shard_map`` on dp x tp), with every
  future intact — ``serve.decode_fallbacks`` ticks and the chaos matrix
  pins fault-free-equal tokens (``serve.decode.step`` in
  ``doc/robustness.md``). The engine holds no layer mathematics: what a
  layer does with a token is ``TransformerLM``'s to say.

``serve_transformer(model, params, seq_len, decode=True)`` is the adapter
entry point; ``examples/nn/gpt_parallel.py --serve`` drives it.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core._compat import shard_map
from ..utils.profiling import begin, scope, span
from .errors import ServeClosed, ServeDeadlineExceeded, ServeOverloaded
from .program_cache import ProgramCache

__all__ = ["DecodeConfig", "DecodeEngine", "live_decode_engines",
           "DECODE_STATS_KEYS"]

# the pinned runtime_stats()["serve"]["decode"] shape (tests/test_stats_contract.py)
DECODE_STATS_KEYS = ("slots", "occupancy", "prefills", "decode_steps",
                     "tokens_out", "decode_fallbacks")

_ENGINES: "weakref.WeakSet[DecodeEngine]" = weakref.WeakSet()


def live_decode_engines():
    return list(_ENGINES)


@dataclass
class DecodeConfig:
    """Engine policy knobs (host-side; none affect greedy results)."""

    slots: Optional[int] = None     # default 2 * dp_world, rounded up
    max_seq_len: int = 256          # S_cap = prompt_bucket(max_seq_len)
    queue_limit: int = 128          # admission bound -> ServeOverloaded
    default_deadline_ms: Optional[float] = None
    temperature: float = 0.0        # 0 = greedy (the parity-checked mode)
    seed: int = 0                   # sampling stream (temperature > 0)
    logprobs: bool = False          # each token's log-probability rides the
    #                                 fetch with it -> ``future.logprobs``

    def __post_init__(self):
        if self.queue_limit < 1:
            raise ValueError(
                f"queue_limit must be >= 1, got {self.queue_limit}")
        if self.max_seq_len < 2:
            raise ValueError(
                f"max_seq_len must be >= 2, got {self.max_seq_len}")


_SEQ = itertools.count()  # FIFO tiebreaker within a priority


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "tenant", "priority", "seq",
                 "enq_t", "deadline_t", "future", "generated", "logprobs",
                 "slot", "span", "stage")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 eos_id: Optional[int], deadline_t: Optional[float],
                 tenant: Optional[str]):
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.tenant = tenant
        self.priority = 0
        self.seq = next(_SEQ)
        self.enq_t = time.monotonic()
        self.deadline_t = deadline_t
        self.future = Future()
        self.generated: List[int] = []
        self.logprobs: List[float] = []     # ``DecodeConfig.logprobs``
        self.slot = -1
        # profiling: the request's life (`decode.request`, token times as
        # its events) and the stage it is in (`decode.queue`, then
        # `decode.prefill`); the shared null span while recording is off
        self.span = begin("decode.request", rid=self.seq,
                          prompt=int(prompt.size))
        self.stage = begin("decode.queue", parent=self.span, rid=self.seq)

    def end_spans(self) -> None:
        self.stage.end()
        self.span.set(n_out=len(self.generated))
        self.span.end()


class DecodeEngine:
    """Continuous-batching decode front end for one ``TransformerLM``.

    Parameters
    ----------
    model : TransformerLM
        A pp=1, sp=1 model without Switch-MoE (``check_decode_grid``): the
        dense model on any dp×tp grid, optionally with the leading dcn tier
        axis, or a per-layer pattern (state-space, window, full, cross,
        grouped-query and gated-memory mixers; a gated MLP or routed experts)
        on a dp-only grid.
    params : pytree
        The model's sharded parameters (``model.init`` / ``shard_params``).
        The engine holds ``model.serving_params(params)``: the tree as its
        step reads it (dtype and, for the dense QKV weights, layout), made
        once here, never in a program; ``params`` itself only where it is
        that already. The masters stay the caller's.
    config : DecodeConfig, optional
    program_cache : ProgramCache, optional
        Counters aggregate under ``serve.program_*`` like every serving
        cache; pass a shared one to pool programs across engines.

    Always ``close()`` an engine you are done with (or use it as a
    context manager) — the worker thread holds a reference.
    """

    def __init__(self, model, params, config: Optional[DecodeConfig] = None,
                 *, name: str = "decode",
                 program_cache: Optional[ProgramCache] = None):
        model.check_decode_grid()
        self.model = model
        self.params = model.serving_params(params)
        self._param_bytes = sum(
            int(a.nbytes) for a in jax.tree.leaves(self.params))
        self.config = config if config is not None else DecodeConfig()
        self.name = name
        self.program_cache = (program_cache if program_cache is not None
                              else ProgramCache(name=name))
        dpw = model.dp_world
        slots = self.config.slots
        if slots is None:
            slots = 2 * dpw
        # slots shard over the data-parallel world: round up to divide
        self.slots = -(-int(slots) // dpw) * dpw
        self.S_cap = model.prompt_bucket(self.config.max_seq_len)
        c = model.cfg
        if c.vocab < 2:
            raise ValueError("decode needs vocab >= 2")
        self._dp_axes = (("dcn", "dp") if model._has_dcn else "dp")
        self._vec_spec = P(self._dp_axes)
        # on a mesh of one device the programs are plain `jit`s (`_program`)
        self._one_device = model.mesh_size == 1
        # the cache is the model's: a tuple of trees, each an argument of
        # the two programs
        self._cache_shapes, self._cache_specs, self._cache_bytes = \
            model.cache_layout(self.slots, self.S_cap, self._dp_axes)
        self._fresh_lanes()
        self._base_key = jax.random.key(self.config.seed)
        # host mirrors: which request owns each slot (None = free) and the
        # live mask uploaded to the step program every dispatch
        self._slot_req: List[Optional[_DecodeRequest]] = [None] * self.slots
        self._live = np.zeros(self.slots, bool)
        # device-resident live mask, re-uploaded ONLY on join/leave (a
        # steady full-occupancy decode stream uploads nothing per step)
        self._live_dev = None
        self._greedy_key = None  # cached key: greedy ignores it, so one
        #                          constant array serves every dispatch
        self._q: List[_DecodeRequest] = []
        self._cv_lock = threading.Condition()
        self._admission = None
        self._closed = False
        self._draining = False
        self._paused = False
        self._step_seq = 0
        self._prefill_seq = 0
        # per-engine figures (process-wide serve.decode_* counters mirror).
        # Prefills, steps and the tokens they gave change TOGETHER under
        # `_count_lock`, and `stats()` reads them under it: with every slot
        # live, a snapshot that held a step without its tokens (or the other
        # way round) read more live slots than there are
        self._count_lock = threading.Lock()
        self._prefills = 0
        self._prefill_tokens = 0
        self._state_resets = 0
        self._steps = 0
        self._tokens_out = 0
        # the positions the steps' live slots attended over (a slot at
        # position p attends p + 1 rows), summed on the host from each
        # slot's length (prompt + tokens generated), kept beside `_live`
        self._attended = 0
        self._slot_len = np.zeros(self.slots, np.int64)
        # a model with "mla" layers whose step reads its latent lanes in the
        # kernel: the rows it fetched, a live slot's blocks up to its
        # position in every such layer, counted beside `_attended` (0 where
        # the core reads whole lanes in XLA)
        self._latent_layers = model.kinds.count("mla")
        self._latent_block = model.latent_block(self.S_cap)
        self._latent_rows = 0
        self._fallbacks = 0
        # a model with "moe" layers: pairs routed (all experts counted), and
        # those that fell on the experts held here, by expert
        self._moe_layers = model.ffn.count("moe")
        self._moe_pairs = 0
        self._moe_held = np.zeros(
            c.experts_held[1] if self._moe_layers else 0, np.int64)
        self._occupancy = deque(maxlen=512)
        self._worker = threading.Thread(
            target=self._run, name=f"heat-decode-{name}", daemon=True)
        self._worker.start()
        _ENGINES.add(self)

    # ------------------------------------------------------------------ #
    # submission / tenancy                                               #
    # ------------------------------------------------------------------ #
    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue one decode request; returns a Future resolving to the
        full int32 token sequence (prompt + generated — the
        ``generate()`` contract per request). Generation stops at
        ``max_new_tokens`` or on sampling ``eos_id`` (included in the
        result). Under ``DecodeConfig(logprobs=True)`` the done future also
        carries ``.logprobs``, float32, one a generated token. Raises the
        typed serve errors on shed/close."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if (prompt < 0).any() or (prompt >= self.model.cfg.vocab).any():
            raise ValueError("prompt tokens outside the model vocab")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        need = self.model.serving_bucket(prompt.size) + max_new
        if need > self.S_cap:
            # rings and recurrent states never fill up: what runs out of
            # room is the kind that keeps a row a position
            kind = next((k for k in ("lane", "latent")
                         if k in self._cache_bytes), "arena")
            raise ValueError(
                f"request needs {need} cache rows (prompt bucket "
                f"{self.model.serving_bucket(prompt.size)} + {max_new} new) "
                f"but a slot's {kind} (the engine's sequence bucket) holds "
                f"{self.S_cap}; raise DecodeConfig.max_seq_len")
        adm = self._admission
        if adm is not None:
            tname = adm.resolve(tenant)
        elif tenant is not None:
            raise ValueError(
                f"submit(tenant={tenant!r}) needs register_tenant() first")
        else:
            tname = None
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
            if deadline_ms is None and adm is not None:
                deadline_ms = adm.slo_ms(tname)
        deadline_t = (None if deadline_ms is None
                      else time.monotonic() + deadline_ms / 1e3)
        req = _DecodeRequest(prompt, max_new, eos_id, deadline_t, tname)
        req.span.set(bucket=need - max_new)
        with self._cv_lock:
            if self._closed:
                raise ServeClosed(f"decode engine {self.name!r} is closed")
            if len(self._q) >= self.config.queue_limit:
                if adm is not None:
                    adm.count(tname, "shed")
                from ..utils import metrics as _pm

                _pm.inc("serve.decode_shed")
                raise ServeOverloaded(
                    f"decode engine {self.name!r} queue is full "
                    f"({self.config.queue_limit} pending)")
            if adm is not None:
                req.priority = int(adm.get(tname).priority)
                adm.count(tname, "admitted")
            self._insert(req)
            self._cv_lock.notify_all()
        return req.future

    def generate(self, prompt, max_new_tokens: int,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None) -> np.ndarray:
        """Synchronous convenience: ``submit(...).result(timeout)``."""
        return self.submit(prompt, max_new_tokens,
                           eos_id=eos_id).result(timeout)

    def register_tenant(self, name: str, *, priority: int = 0,
                        slo_ms: Optional[float] = None, **policy):
        """Register a tenant — the same
        :class:`~heat_tpu.serve.admission.AdmissionController` registry
        the batch executor arms. Slot grants become priority-ordered
        (higher priority prefills first when a slot frees; FIFO within a
        priority) and ``slo_ms`` is the tenant's default deadline. The
        rate/breaker knobs are accepted for registry parity but decode
        admission enforces only priority/SLO/queue bound (documented in
        ``doc/serving.md``)."""
        from .admission import AdmissionController

        with self._cv_lock:
            if self._admission is None:
                self._admission = AdmissionController()
            adm = self._admission
        return adm.register(name, priority=priority, slo_ms=slo_ms, **policy)

    @property
    def admission(self):
        return self._admission

    def _insert(self, req: _DecodeRequest) -> None:
        """Priority-ordered insert (lock held): descending priority, FIFO
        within one — identical discipline to the batch executor."""
        q = self._q
        key = (-req.priority, req.seq)
        i = len(q)
        while i > 0 and (-q[i - 1].priority, q[i - 1].seq) > key:
            i -= 1
        q.insert(i, req)

    # ------------------------------------------------------------------ #
    # lifecycle / introspection                                          #
    # ------------------------------------------------------------------ #
    @property
    def live_slots(self) -> int:
        return int(self._live.sum())

    @property
    def queue_depth(self) -> int:
        with self._cv_lock:
            return len(self._q) + self.live_slots

    @property
    def worker_alive(self) -> bool:
        return self._worker.is_alive()

    def pause(self) -> None:
        """Hold the worker before its next admit/step (test/ops hook)."""
        with self._cv_lock:
            self._paused = True
            self._cv_lock.notify_all()

    def resume(self) -> None:
        with self._cv_lock:
            self._paused = False
            self._cv_lock.notify_all()

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until everything queued/live at call time is answered."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv_lock:
            while self._q or self._live.any():
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    return False
                self._cv_lock.wait(rem if rem is not None else 0.1)
        return True

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Stop admission; drain (finish queued + live sequences) or
        abort (fail them with :class:`ServeClosed`). Idempotent."""
        queued: list = []
        inflight: list = []
        with self._cv_lock:
            self._closed = True
            self._draining = drain
            if not drain:
                queued = list(self._q)
                self._q.clear()
                for s, req in enumerate(self._slot_req):
                    if req is not None:
                        inflight.append(req)
                        self._slot_req[s] = None
                self._live[:] = False
                self._live_dev = None
            self._paused = False
            self._cv_lock.notify_all()
        # fail futures OUTSIDE the lock (done-callback discipline). Queued
        # futures are PENDING: claim them so a client cancel cannot race
        # set_exception. Slot-granted futures are already RUNNING (claimed
        # at grant) — set_running_or_notify_cancel would RAISE on them, so
        # they take the done()-guarded path like _reset_state, tolerating
        # a race with the worker resolving its last step.
        err = ServeClosed(
            f"decode engine {self.name!r} closed without drain")
        for req in queued:
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(err)
        for req in inflight:
            try:
                if not req.future.done():
                    req.future.set_exception(err)
            except InvalidStateError:
                pass  # the worker's final step resolved it first
        if threading.current_thread() is not self._worker:
            self._worker.join(timeout)
            if not self._worker.is_alive():
                # the programs' closures hold the engine, so nothing frees
                # its gigabytes until the cycle collector runs: let go here
                self.params = self._cache = None
                self._pos = self._toks = self._live_dev = None

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "DecodeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(drain=exc_type is None)

    def stats(self) -> dict:
        """Engine snapshot: the pinned decode figures plus queue/cache/
        tenant detail."""
        occ = list(self._occupancy)
        adm = self._admission
        with self._count_lock:
            prefills, steps, tokens_out, attended, latent_rows = (
                self._prefills, self._steps, self._tokens_out,
                self._attended, self._latent_rows)
        moe = {} if not self._moe_layers else {
            "moe_pairs_total": self._moe_pairs,
            "moe_pairs_held": int(self._moe_held.sum()),
            "moe_pairs_by_expert": self._moe_held.tolist()}
        return {
            **moe,
            "slots": self.slots,
            "live": self.live_slots,
            "queue_depth": len(self._q),
            "seq_bucket": self.S_cap,
            "occupancy": (sum(occ) / len(occ)) if occ else 0.0,
            "prefills": prefills,
            "prefill_tokens": self._prefill_tokens,
            "state_resets": self._state_resets,
            "param_bytes": self._param_bytes,
            "cache_bytes": dict(self._cache_bytes),
            "decode_steps": steps,
            "tokens_out": tokens_out,
            "attended_positions": attended,
            "latent_rows_fetched": latent_rows,
            "decode_fallbacks": self._fallbacks,
            "program_cache": self.program_cache.stats(),
            "tenants": adm.tenant_stats() if adm is not None else {},
        }

    def warmup(self, prompt_lens=None) -> dict:
        """Pre-compile the prefill ladder + the decode step so traffic
        never pays a compile: one throwaway prefill per distinct prompt
        bucket (into slot 0, never marked live — the next real prefill
        overwrites it) and one all-dead decode step. Returns the program
        cache stats; steady-state traffic over the same ladder must add
        zero misses from here on. Must run before traffic: the
        throwaway prefill writes slot 0's cache rows."""
        with self._cv_lock:
            if self._q or self._live.any():
                raise RuntimeError(
                    "warmup() must run before traffic (its throwaway "
                    "prefill writes slot 0)")
        if prompt_lens is None:
            rungs, r = [], self.model.PROMPT_BUCKET_MIN
            while r < self.S_cap:
                rungs.append(r)
                r <<= 1
            prompt_lens = rungs
        seen = set()
        for s0 in prompt_lens:
            sp = self.model.serving_bucket(int(s0))
            if sp in seen or sp >= self.S_cap:
                continue
            seen.add(sp)
            self._dispatch_prefill(np.zeros(int(s0), np.int32), 0,
                                   record=False)
        self._dispatch_step(np.zeros(self.slots, bool), record=False)
        return self.program_cache.stats()

    # ------------------------------------------------------------------ #
    # compiled programs                                                  #
    # ------------------------------------------------------------------ #
    def _wire(self):
        """The (quant, chunk, hier) key triple captured at BUILD time and
        pinned into the traced body — jax traces at first dispatch, and a
        codec toggle in between must not change the wire format out from
        under the program key (the PR 9 r4 lesson)."""
        from ..core import fusion

        return (fusion.quant_key(), fusion.chunk_key(), fusion.hier_key())

    def _program(self, body, in_specs, out_specs, donate):
        """``body`` compiled over the model's mesh. On ONE device that is a
        plain ``jit`` (the bodies name no mesh axis there): a ``shard_map``
        of one shard computes the same, and its boundary copied every
        donated lane of a pattern's cache (PR 33). On dp x tp the bodies
        name the mesh's axes and the ``shard_map`` stays."""
        if self._one_device:
            return jax.jit(body, donate_argnums=donate)
        return jax.jit(shard_map(
            body, mesh=self.model.grid.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False), donate_argnums=donate)

    def _packed(self, toks, logp, pairs, sharded):
        """What the host fetches as ONE int32 vector: ``toks``, then their
        log-probabilities (``logp``: a list of none or one, float32, bit for
        bit), then the experts' ``pairs`` (alike). On a dp grid a step's are
        a shard's own (``sharded``: gathered, summed); a prefill is computed
        alike on all."""
        per_slot = [toks.reshape(-1)] + [lax.bitcast_convert_type(
            lp.reshape(-1), jnp.int32) for lp in logp]
        if sharded and not self._one_device:
            axes = self._dp_axes
            per_slot = [lax.all_gather(v, axes, tiled=True) for v in per_slot]
            pairs = [lax.psum(p, axes) for p in pairs]
        return jnp.concatenate(per_slot + list(pairs))

    @staticmethod
    def _logprob_of(logits, chosen):
        """log softmax(``logits``)[``chosen``] a row, float32."""
        with scope("sample"):
            at = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
            return at - jax.nn.logsumexp(logits, axis=-1)

    def _dp_index(self):
        m = self.model
        if self._one_device:
            return jnp.int32(0)
        idx = lax.axis_index("dp")
        if m._has_dcn:
            idx = lax.axis_index("dcn") * m.dp + idx
        return idx

    # ------------------------------------------------------------------ #
    # the cache: the model's tuple of trees                              #
    # ------------------------------------------------------------------ #
    def _fresh_lanes(self) -> None:
        """Zeroed device state: the model's cache, positions, last tokens."""
        mesh = self.model.grid.mesh
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self._cache_specs,
                                 is_leaf=lambda s: isinstance(s, P))
        self._cache = jax.tree.map(
            lambda sd, sh: jax.device_put(jnp.zeros(sd.shape, sd.dtype), sh),
            self._cache_shapes, shardings)
        vec_sh = NamedSharding(mesh, self._vec_spec)
        self._pos = jax.device_put(jnp.zeros(self.slots, jnp.int32), vec_sh)
        self._toks = jax.device_put(jnp.zeros(self.slots, jnp.int32), vec_sh)

    def _step_prog(self):
        """THE decode-step executable: (params, *cache, pos, live, toks,
        key) -> (*cache, pos', toks'), carries donated. One per (S_cap,
        slots, temperature, codec-keys) signature. With ``logprobs`` or a
        model with "moe" layers it returns one more, the vector the host
        fetches (``_packed``): every slot's token, their log-probabilities,
        the live slots' routed pairs by held expert."""
        wire = self._wire()
        temp = float(self.config.temperature)
        want_lp = bool(self.config.logprobs)
        key = ("decode_step", self.S_cap, self.slots, temp, want_lp) + wire

        def build():
            m = self.model

            def decode_step(params, *rest):
                *cache, pos, live, toks, skey = rest
                Bl = toks.shape[0]
                logits, cache, *pairs = m.decode_step_logits(
                    params, tuple(cache), toks, pos, wire=wire, live=live)
                with scope("sample"):
                    if temp == 0.0:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    else:
                        gsl = self._dp_index() * Bl + jnp.arange(Bl)
                        keys = jax.vmap(
                            lambda i: jax.random.fold_in(skey, i))(gsl)
                        nxt = jax.vmap(lambda k, lg: jax.random.categorical(
                            k, lg / temp))(keys, logits).astype(jnp.int32)
                # join/leave is a MASK, not a program change: dead slots
                # keep their token and position (their cache write lands
                # on the same already-masked row every step)
                toks2 = jnp.where(live, nxt, toks)
                pos2 = pos + live.astype(jnp.int32)
                logp = [self._logprob_of(logits, nxt)] if want_lp else []
                if not (pairs or logp):
                    return (*cache, pos2, toks2)
                return (*cache, pos2, toks2,
                        self._packed(toks2, logp, pairs, sharded=True))

            cs, vs = self._cache_specs, self._vec_spec
            n = len(cs)
            return self._program(
                decode_step,
                (self.model.serving_param_specs(), *cs, vs, vs, vs, P()),
                (*cs, vs, vs) + (P(),) * (self.model.has_experts or want_lp),
                (*range(1, n + 2), n + 3))

        return self.program_cache.get_custom(key, build)

    def _prefill_prog(self, Sp: int):
        """The bucketed prefill executable for prompt bucket ``Sp``:
        (params, *cache, pos, toks, prompt, n_valid, slot, key) ->
        (*cache, pos', toks', first_token); carries donated. With
        ``logprobs`` or a model with "moe" layers the first token's
        log-probability and the prompt's routed pairs by held expert stand
        behind it, in one vector (``_packed``).

        The prompt rides replicated (every dp shard runs the forward,
        only the owning shard keeps the K/V write) and joins dispatch
        one request at a time — dp-way redundant prefill compute and k
        serialized dispatches on a k-request join. Acceptable while
        prefill is a small fraction of decode wall (the benched shape);
        the batched form (one prompt row per dp shard, one dispatch per
        wave of grants) is the known follow-up when prefill-bound.

        The slot's lanes are written WHOLE from a forward that starts
        from nothing (a state-space scan from the zero state, stopped at
        ``n_valid``): that is the reset a granted slot's recurrent state
        needs."""
        wire = self._wire()
        temp = float(self.config.temperature)
        want_lp = bool(self.config.logprobs)
        key = ("decode_prefill", Sp, self.S_cap, self.slots, temp,
               want_lp) + wire

        def build():
            m = self.model

            def decode_prefill(params, *rest):
                *cache, pos, toks, prompt, n_valid, slot, skey = rest
                kept, logits, *pairs = m.prefill(
                    params, prompt[None], n_valid, wire=wire)
                with scope("sample"):
                    if temp == 0.0:
                        first = jnp.argmax(logits[0]).astype(jnp.int32)
                    else:
                        first = jax.random.categorical(
                            jax.random.fold_in(skey, slot),
                            logits[0] / temp).astype(jnp.int32)
                ls = pos.shape[0]  # local slots on this dp shard
                local = slot - self._dp_index() * ls
                ok = (local >= 0) & (local < ls)
                lc = jnp.clip(local, 0, ls - 1)
                # one slot's `Sp` rows a layer, written where they lie (a
                # leaf a layer: nothing else of the cache is touched, so a
                # prefill is O(prompt), not O(cache);
                # tests/test_chip_compile.py holds that at the decode
                # cell's size). Non-owning dp shards write the slot's OWN
                # current rows back (a no-op): the select is block-sized
                cache = m.cache_store(tuple(cache), kept, lc, ok)
                hit = ok & (jnp.arange(ls) == lc)
                pos = jnp.where(hit, n_valid, pos)
                toks = jnp.where(hit, first, toks)
                logp = [self._logprob_of(logits[0], first)] if want_lp else []
                if pairs or logp:
                    first = self._packed(first, logp, pairs, sharded=False)
                return (*cache, pos, toks, first)

            cs, vs = self._cache_specs, self._vec_spec
            return self._program(
                decode_prefill,
                (self.model.serving_param_specs(), *cs, vs, vs, P(), P(), P(),
                 P()),
                (*cs, vs, vs, P()), tuple(range(1, len(cs) + 3)))

        return self.program_cache.get_custom(key, build)

    # ------------------------------------------------------------------ #
    # the device-residency choke point                                   #
    # ------------------------------------------------------------------ #
    @staticmethod
    def _fetch(arr) -> np.ndarray:
        """The ONE device→host doorway. Everything else the worker does
        stays on device, so a test wrapping the engine in
        ``jax.transfer_guard_device_to_host("disallow")`` proves the
        per-step fetch is only the sampled-token vector."""
        with span("decode.fetch"), \
                jax.transfer_guard_device_to_host("allow"):
            return np.asarray(arr)

    # ------------------------------------------------------------------ #
    # worker                                                             #
    # ------------------------------------------------------------------ #
    def _run(self) -> None:
        from ..utils import metrics as _pm

        while True:
            expired: list = []
            grants: list = []
            with self._cv_lock:
                while not self._closed and (
                        self._paused
                        or (not self._q and not self._live.any())):
                    self._cv_lock.wait(1.0)
                if self._closed and not (
                        self._draining
                        and (self._q or self._live.any())):
                    return
                if not self._paused:
                    with span("decode.grant"):
                        grants, expired = self._grant_locked()
            for req in expired:
                self._fail_deadline(req)
            try:
                # one turn of the loop once there is work: the wait above
                # is the engine with nothing to do, and carries no span
                with span("decode.loop", n_live=int(self._live.sum())):
                    for req, slot in grants:
                        self._do_prefill(req, slot)
                    if self._live.any():
                        self._do_step()
            except Exception as exc:
                # backstop: NOTHING kills the worker. The donated device
                # state may be gone — fail every in-flight future typed,
                # free the slots, and rebuild fresh lanes.
                _pm.inc("serve.worker_backstops")
                self._reset_state(exc)
            finally:
                with self._cv_lock:
                    self._cv_lock.notify_all()

    def _grant_locked(self):
        """Pop (request, slot) grants for every free slot while the queue
        has work (lock held); queued-past-deadline and client-cancelled
        requests drop out. The queue is priority-ordered at insert, so
        grants ARE the tenant-priority order."""
        grants, expired = [], []
        now = time.monotonic()
        free = [s for s in range(self.slots) if self._slot_req[s] is None]
        while free and self._q:
            req = self._q.pop(0)
            if not req.future.set_running_or_notify_cancel():
                continue  # cancelled while queued: never run it
            if req.deadline_t is not None and now > req.deadline_t:
                expired.append(req)
                continue
            slot = free.pop(0)
            req.slot = slot
            self._slot_req[slot] = req
            req.stage.end()             # queued until here
            req.stage = begin("decode.prefill", parent=req.span,
                              rid=req.seq, slot=slot)
            grants.append((req, slot))
        return grants, expired

    def _fail_deadline(self, req) -> None:
        from ..utils import metrics as _pm

        _pm.inc("serve.decode_deadline_expired")
        if self._admission is not None:
            self._admission.count(req.tenant, "deadline_expired")
        req.end_spans()
        req.future.set_exception(ServeDeadlineExceeded(
            f"decode request expired after "
            f"{(time.monotonic() - req.enq_t) * 1e3:.1f} ms in queue"))

    def _next_key(self, salt: int):
        return jax.random.fold_in(self._base_key, salt)

    def _dispatch_prefill(self, prompt: np.ndarray, slot: int,
                          record: bool = True):
        from ..utils import metrics as _pm

        m = self.model
        S0 = int(prompt.size)
        Sp = m.serving_bucket(S0)
        prog = self._prefill_prog(Sp)
        padded = np.zeros(Sp, np.int32)
        padded[:S0] = prompt
        self._prefill_seq += 1
        with span("decode.prefill.dispatch", slot=slot, bucket=Sp):
            out = prog(self.params, *self._cache, self._pos,
                       self._toks, jnp.asarray(padded), jnp.int32(S0),
                       jnp.int32(slot),
                       self._next_key(2 * self._prefill_seq + 1))
        *cache, self._pos, self._toks, first = out
        self._cache = tuple(cache)
        first = self._fetch(first).reshape(-1)
        n_lp = int(bool(self.config.logprobs))
        if record:
            self._prefill_tokens += S0
            self._state_resets += "state" in self._cache_bytes
            self._count_pairs(S0, first[1 + n_lp:])
            with self._count_lock:      # the prefill and its one token
                self._prefills += 1
                self._tokens_out += 1
            _pm.inc("serve.decode_prefills")
            _pm.inc("serve.decode_tokens_out")
        return int(first[0]), first[1:1 + n_lp].view(np.float32).tolist()

    def _count_pairs(self, tokens: int, held) -> None:
        """``tokens`` tokens went through every "moe" layer; ``held``: their
        pairs by held expert, as the program counted them."""
        if self._moe_layers:
            self._moe_pairs += (tokens * self._moe_layers
                                * self.model.cfg.experts_per_token)
            self._moe_held += held

    def _do_prefill(self, req: _DecodeRequest, slot: int) -> None:
        try:
            first, req.logprobs = self._dispatch_prefill(req.prompt, slot)
        except Exception as exc:
            # a failed prefill fails ITS request only; the slot stays
            # free and the engine (and every other lane) lives on
            if self._donated_gone():
                raise  # state lost mid-donation: the backstop rebuilds
            self._slot_req[slot] = None
            req.end_spans()
            req.future.set_exception(exc)
            return
        req.stage.end()                 # granted until the first token
        req.span.event("token")
        req.generated = [first]
        self._slot_len[slot] = req.prompt.size + 1
        if req.max_new <= 1 or (req.eos_id is not None
                                and first == req.eos_id):
            self._finish(slot, req)
        else:
            self._live[slot] = True
            self._live_dev = None  # membership changed: re-upload

    def _dispatch_step(self, live: np.ndarray, record: bool = True):
        from ..utils import faults as _faults
        from ..utils import metrics as _pm

        self._step_seq += 1
        prog = self._step_prog()
        if float(self.config.temperature) == 0.0:
            # greedy ignores the key: one cached constant avoids a
            # fold_in dispatch on every step of the hot loop
            if self._greedy_key is None:
                self._greedy_key = self._base_key
            skey = self._greedy_key
        else:
            skey = self._next_key(2 * self._step_seq)
        if self._live_dev is None:
            self._live_dev = jax.device_put(
                live, NamedSharding(self.model.grid.mesh, self._vec_spec))
        args = (self.params, *self._cache, self._pos, self._live_dev,
                self._toks, skey)
        try:
            _faults.check("serve.decode.step")
            with span("decode.step.dispatch"):
                out = prog(*args)
        except Exception:
            if self._donated_gone():
                raise  # donated buffers invalidated mid-dispatch (PR 8)
            # DEGRADED: the step program's own body, uncompiled (op by op,
            # under its `shard_map` on dp x tp): the same tokens, nothing
            # donated, every future intact
            _pm.inc("serve.decode_fallbacks")
            self._fallbacks += 1
            with jax.disable_jit():
                out = prog(*args)
        n = len(self._cache)
        cache, (self._pos, self._toks, *packed) = out[:n], out[n:]
        self._cache = tuple(cache)
        got = self._fetch(packed[0] if packed else self._toks)
        n_lp = self.slots * bool(self.config.logprobs)
        # a live slot's fed token sits at position prompt + generated - 1
        attended = int(self._slot_len[live].sum())
        bk = self._latent_block
        fetched = bk and self._latent_layers * int(np.minimum(
            -(-self._slot_len[live] // bk) * bk, self.S_cap).sum())
        self._slot_len[live] += 1
        if record:
            n_live = int(live.sum())
            self._count_pairs(n_live, got[self.slots + n_lp:])
            with self._count_lock:      # the step and its tokens
                self._steps += 1
                self._tokens_out += n_live
                self._attended += attended
                self._latent_rows += fetched
            _pm.inc("serve.decode_steps")
            _pm.inc("serve.decode_tokens_out", n_live)
        return got[:self.slots], got[self.slots:self.slots + n_lp].view(
            np.float32)

    def _do_step(self) -> None:
        live = self._live.copy()
        n_live = int(live.sum())
        with span("decode.step", n_live=n_live):
            toks_np, logp_np = self._dispatch_step(live)
            self._occupancy.append(n_live / self.slots)
            for slot in np.nonzero(live)[0]:
                req = self._slot_req[slot]
                if req is None:
                    continue
                t = int(toks_np[slot])
                req.generated.append(t)
                if logp_np.size:
                    req.logprobs.append(float(logp_np[slot]))
                req.span.event("token")
                done = (len(req.generated) >= req.max_new
                        or (req.eos_id is not None and t == req.eos_id))
                if done:
                    self._finish(slot, req)

    def _finish(self, slot: int, req: _DecodeRequest) -> None:
        from ..utils import metrics as _pm

        with span("decode.emit", slot=slot):
            self._live[slot] = False
            self._live_dev = None  # membership changed: re-upload
            self._slot_req[slot] = None
            _pm.inc("serve.decode_completed")
            if self._admission is not None:
                self._admission.count(req.tenant, "completed")
            req.end_spans()
            if self.config.logprobs:
                req.future.logprobs = np.asarray(req.logprobs, np.float32)
            req.future.set_result(np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)]))

    def _donated_gone(self) -> bool:
        try:
            return bool(jax.tree.leaves(self._cache)[0].is_deleted())
        except Exception:
            return False

    def _reset_state(self, exc: Exception) -> None:
        """Backstop recovery: fail every in-flight future typed, free all
        slots, rebuild fresh device lanes (the donated ones may be
        invalid)."""
        self._fresh_lanes()
        failed = []
        with self._cv_lock:
            for s, req in enumerate(self._slot_req):
                if req is not None:
                    failed.append(req)
                    self._slot_req[s] = None
            self._live[:] = False
            self._live_dev = None
        for req in failed:
            try:
                if not req.future.done():
                    req.future.set_exception(exc)
            except Exception:
                pass
