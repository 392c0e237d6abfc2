"""Driver `lm_decode`: `serve_transformer(model, params, max_seq_len,
decode=True, slots=...)`, greedy, under a closed loop of clients. The entry
point is `DecodeEngine.submit`, whose `Future` is all a caller sees: prefill
programs, the one decode-step program, slot reuse and the engine's host loop
are all behind it.

ONE thread drives the load: a completed `Future` puts itself on a queue (its
done-callback, in the engine's thread, does nothing else), and the driver's
loop takes it off, notes the time, and submits that client's next request.
The loop is started in set-up and runs `warm_completions` requests before the
window opens, so the window sees steady state. When the window has closed
nothing more is sent, and the driver waits for the requests that were under
way: `decode_tokens_per_s` is the tokens callers were DELIVERED inside the
window (`work.tokens_delivered`: a request's tokens spread over its life from
submit to last token), so a request that straddles an end of the window needs
its own end known. `req_ms_per_token_p90` is over the requests that completed
inside the window.

`correct`: once the window has closed and the engine is gone, a sample of the
requests it finished (the longest among them, the rest drawn from the seed)
goes through the plain float32 reference (`references/lm.py`), one forward
per request over prompt + served tokens; the number compared is the widest
gap by which a served token's reference logit lies below the reference's
best. Every finished request is also checked for its shape and for echoing
its prompt.
"""

from __future__ import annotations

import functools
import queue
import time

import numpy as np

import jax
import jax.numpy as jnp

from perfbench import traffic as gen
from perfbench.drivers import _heat, _lm
from perfbench.references import lm as ref


@functools.partial(jax.jit, static_argnames=("theta", "control"))
def _widest_gap(params, toks, n_prompt, n_total, theta, control=False):
    """Over the served positions of ONE padded sequence: the reference's best
    logit minus its logit of the token judged. The token judged is the served
    one, or (`control`) the one the float8 forward puts first there."""
    logits = ref.row_logits(params, toks, theta)
    if control:
        judged = jnp.argmax(ref.row_logits(params, toks, theta, fp8=True), -1)
    else:
        judged = jnp.roll(toks, -1)              # position i predicts toks[i+1]
    gap = jnp.max(logits, -1) - jnp.take_along_axis(
        logits, judged[:, None], -1)[:, 0]
    pos = jnp.arange(toks.shape[0])
    served = (pos >= n_prompt - 1) & (pos < n_total - 1)
    return jnp.max(jnp.where(served, gap, 0.0))


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.traffic
        self.limits = ctx.limits
        self.done_q = queue.Queue()
        self.next_req = 0
        self.records = []          # (index, t_submit, t_done, tokens or None)
        self.submit_errors = 0
        self.open = True

    # -- set-up ---------------------------------------------------------
    def setup(self):
        from heat_tpu.serve import serve_transformer

        cfg, mix = self.ctx.config, self.mix
        self.model = _lm.build_model(cfg, self.ctx.devices,
                                                  self.ctx.memo)
        self.key = _lm.seed_key(self.ctx.seed)
        params = _lm.make_params(jax.random.fold_in(self.key, 0), cfg,
                                 _lm.shardings_of(self.model))
        self.requests = gen.requests(mix, self.ctx.seed, cfg["vocab_size"])
        self.eng = serve_transformer(
            self.model, params, int(mix["max_seq_len"]), decode=True,
            slots=int(mix["slots"]), queue_limit=int(mix["queue_limit"]))
        del params
        self.eng.warmup(prompt_lens=sorted({len(p) for p, _o in self.requests}))
        for c in range(int(mix["clients"])):
            self._submit(c)
        warm = int(mix["warm_completions"])
        while len(self.records) < warm:
            self._take(timeout=600.0)
        self.n_warm = len(self.records)

    def _submit(self, client):
        i = self.next_req
        self.next_req += 1
        prompt, n_out = self.requests[i % len(self.requests)]
        t = time.perf_counter()
        try:
            fut = self.eng.submit(prompt, n_out)
        except Exception as exc:              # refused: counts as failed
            self.submit_errors += 1
            self.done_q.put((client, i, t, t, exc))
            return
        fut.add_done_callback(
            lambda f, c=client, i=i, t=t: self.done_q.put(
                (c, i, t, time.perf_counter(), f)))

    def _take(self, timeout):
        """One completion off the queue; its client sends the next request."""
        client, i, t0, t1, fut = self.done_q.get(timeout=timeout)
        toks = None
        if not isinstance(fut, Exception) and fut.exception() is None:
            toks = fut.result()
        self.records.append((i, t0, t1, toks))
        if self.open:
            self._submit(client)

    def counters(self):
        st = self.eng.stats()
        return {"prefills": st["prefills"], "decode_steps": st["decode_steps"],
                "tokens_out": st["tokens_out"],
                "decode_fallbacks": st["decode_fallbacks"],
                "program_cache_misses": st["program_cache"]["misses"],
                "completed": len(self.records),
                "fallbacks": _heat.fallbacks_total()}

    def sync(self):
        pass                  # the engine runs on: the trace cuts where it is

    # -- the window -----------------------------------------------------
    def window(self, probe, drain_s=90.0):
        first = len(self.records)
        while not probe.done():
            try:
                with probe.span("wait"):
                    self._take(timeout=0.02)
            except queue.Empty:
                probe.poll()
            else:
                probe.unit()
        probe.close()
        elapsed = probe.window_s
        t_open, t_close = probe.t0, probe.t0 + elapsed
        self.open = False
        done = self.records[first:]
        self.window_done = done
        # the requests under way at the close: each is waited for, `drain_s`
        # at the most; one that never ends has failed
        under_way = self.next_req - len(self.records)
        try:
            while drain_s and len(self.records) < self.next_req:
                self._take(timeout=max(
                    0.001, t_close + drain_s - time.perf_counter()))
        except queue.Empty:
            pass
        late = self.records[first + len(done):]
        self.window_late = late
        self.never_ended = (under_way - len(late)) if drain_s else 0
        lost = self.never_ended + sum(r[3] is None for r in late)
        ok = [r for r in done if r[3] is not None]
        per_tok = [1e3 * (r[2] - r[1]) / self._n_out(r) for r in ok]
        worst = 1e3 * elapsed             # a failed request: the worst there is
        per_tok += [worst] * (len(done) - len(ok))
        delivered = self.ctx.work.tokens_delivered(
            [(r[1], r[2], self._n_out(r)) for r in done + late
             if r[3] is not None], t_open, t_close)
        return {"metrics": {
                    "decode_tokens_per_s": delivered / elapsed,
                    "req_ms_per_token_p90": float(np.percentile(per_tok, 90))},
                "attempted": len(done) + len(late) + self.never_ended,
                "failed": len(done) - len(ok) + lost}

    def _n_out(self, record):
        return len(record[3]) - len(
            self.requests[record[0] % len(self.requests)][0])

    def release(self):
        self.open = False
        if self.eng is not None:
            self.eng.close(drain=False, timeout=60.0)
        self.eng = None
        self.model = None

    # -- correct ----------------------------------------------------------
    def _sample(self):
        ok = [r for r in self.window_done if r[3] is not None]
        if not ok:
            return []
        longest = max(range(len(ok)), key=lambda j: len(ok[j][3]))
        rng = np.random.default_rng([self.ctx.seed, 3])
        rest = [j for j in rng.permutation(len(ok)) if j != longest]
        return [ok[j] for j in [longest] + rest[:int(
            self.mix["check_requests"]) - 1]]

    def _wrong_answers(self):
        """Finished requests (in the window, or under way at its close and
        waited for) whose answer has the wrong length or does not echo its
        prompt, plus requests that failed, were refused or never ended."""
        bad = self.never_ended
        for i, _t0, _t1, toks in self.window_done + self.window_late:
            prompt, n_out = self.requests[i % len(self.requests)]
            if (toks is None or len(toks) != len(prompt) + n_out
                    or not np.array_equal(toks[:len(prompt)], prompt)
                    or toks.min() < 0
                    or toks.max() >= self.ctx.config["vocab_size"]):
                bad += 1
        return float(bad)

    def numbers(self, control=False):
        cfg = self.ctx.config
        theta = float(cfg["rotary_emb_base"])
        params = _lm.make_params(jax.random.fold_in(self.key, 0), cfg)
        S = int(self.mix["max_seq_len"])
        gaps, served = [], 0
        for i, _t0, _t1, toks in self._sample():
            n_prompt = len(self.requests[i % len(self.requests)][0])
            padded = np.zeros(S, np.int32)
            padded[:len(toks)] = toks
            gaps.append(float(_widest_gap(
                params, jnp.asarray(padded), n_prompt, len(toks),
                theta=theta, control=control)))
            served += len(toks) - n_prompt
        del params
        return {"token_gap": max(gaps) if gaps else float("nan"),
                "wrong_answers": self._wrong_answers(),
                "tokens_judged": served, "requests_judged": len(gaps)}

    def check(self, got=None):
        """The numbers beside their limits; `got` puts other numbers (the
        control's, a fault's) in the program's place."""
        got = self.numbers() if got is None else got
        return [(n, got[n], float(self.limits[n])) for n in self.limits]

    def readings(self):
        """A short window at the cell's own load, then the numbers."""
        from perfbench.run import Probe

        probe = Probe(float(self.mix.get("readings_seconds", 15.0)), None,
                      self.counters, self.sync)
        probe.start()
        self.window(probe, drain_s=0.0)   # the numbers need no rate
        self.release()
        return self.numbers()

    def control(self):
        return self.numbers(control=True)

    def close(self):
        pass
