"""Driver `lm_decode_granite4h`: the `lm_decode` cell for one chip's share of
`granite-4.0-h-small` (Mamba-2 mixers, plain grouped-query attention, routed
experts beside a shared one: `TransformerLM`'s pattern with `norm_kind`,
`ffn`, `experts_held` and the four multipliers set). The loop, the window, the
judgement of shapes and `check` are `lm_decode.Cell`'s, inherited through
`lm_decode_pattern.Cell` (whose `release` and `counters` serve here too), so
all three decode cells are timed by the same code; this file brings the
model, its weights, its reference and what `correct` compares.

Set-up differs from `lm_decode_pattern` in the model built and the module the
weights come from (`references/granite4h.py`: made from the seed a layer at a
time, matrices rounded once to the configuration's `param_dtype`; the program
is handed those values, stacked as it holds them, the reference asks for the
same values as float32). `lm_decode.Cell.setup` names its model and weights
inline, so its few lines on the engine and the clients are repeated here as
they are there (PERF.md section 7.4 asks a `benchmark` PR for the hook).

The program counts the pairs it routes (`DecodeEngine.stats()`:
`moe_pairs_total`, `moe_pairs_held`, `moe_pairs_by_expert`); `counters` hands
them to the readers, the by-expert list as one counter an expert. A program
without them (the parent's) gives none, and the readers read nothing.

`correct` holds what the TIMED run computed against the reference, and by
more than its tokens. With random weights this model COPIES: the head is tied
to an embedding that enters the stream times 12, so the last token's own logit
stands 7 standard deviations above the best of the other 100,351 (0.91 against
0.35: `PERF.md` section 6, PR 37) and every served token repeats the prompt's
last, in bfloat16 and in float8 alike. So the engine is asked for each
token's log-probability as well (`DecodeConfig(logprobs=True)`: one float32 a
slot beside the token, in the step's one fetch; a request's are on its future
when it is done), and the loop keeps them with the tokens (`_take`). Once the
window has closed and the engine is gone, the judged requests (`_sample`: the
longest finished, one of each prefill bucket the window finished, the rest
drawn from the seed) go through the plain float32 reference TOGETHER, a layer
at a time, padded to the longest sequence the mix can send, and EVERY served
token of theirs is compared: what the engine's step program said at 64 live
slots, in the slot the scheduler gave the request, after whatever tenant
held that slot before, against what the reference says of the same tokens.

* `logprob_err`: |the engine's log-probability of the served token - the
  reference's|, in units of the reference logits' spread at that position;
  the root of its mean square over a request's served tokens, the worst
  judged request of the run. A request's mean and not its widest token: a
  fault of a slot's state, of a mask, of a prefill program or of a layer
  moves every token that follows it, while the widest single token of
  thousands has a heavy tail (in tier-1's small model such a token is a
  router's near-tie decided the other way: one expert's gate, a step and not
  a rounding) and grows with the number of tokens judged; that one is
  reported as `logprob_err_widest` and limits nothing.
* `token_gap`, as the other two decode cells have it: the widest gap by
  which a served token's reference logit lies below the reference's best. It
  reads 0.0 here for the program and for the float8 control alike (both
  copy), so it separates no precision; it is what catches a token that is
  not the model's at all.
* `wrong_answers` (length, echo, range), inherited.

The control puts the float8 forward's log-probabilities of the served tokens
in the engine's place.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from perfbench import traffic as gen
from perfbench.drivers import _lm, lm_decode_pattern
from perfbench.references import granite4h as ref


def build_model(config: dict, devices, memo: dict):
    """The share's `TransformerLM` on a (1,1,1,1) grid of one chip."""
    if "model" in memo:
        return memo["model"]
    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    z = ref.sizes(config)
    cfg = TransformerLMConfig(
        vocab=z["V"], d_model=z["D"], n_heads=z["H"], n_kv_heads=z["Hkv"],
        n_layers=z["L"], rope=False, pattern=ref.kinds(config),
        ffn=("moe",) * z["L"], norm_kind="rmsnorm", norm_eps=z["eps"],
        d_inner=z["di"], d_state=z["N"], d_conv=z["K"], ssm_heads=z["Hs"],
        ssm_chunk=z["Q"], n_experts=z["E"], experts_per_token=z["k"],
        d_expert=z["Fe"], d_shared=z["Fs"],
        experts_held=(z["first"], z["count"]),
        embedding_multiplier=z["emb"], residual_multiplier=z["res"],
        attention_multiplier=z["att"], logits_scaling=z["logit"],
        init_scale=z["scale"],
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]))
    grid = ht.MeshGrid((1, 1, 1, 1), _lm.AXES, devices=list(devices)[:1])
    memo["model"] = TransformerLM(grid, cfg)
    return memo["model"]


class Cell(lm_decode_pattern.Cell):
    def setup(self):
        from heat_tpu.serve import serve_transformer

        cfg, mix = self.ctx.config, self.mix
        self.model = build_model(cfg, self.ctx.devices, self.ctx.memo)
        self.key = _lm.seed_key(self.ctx.seed)
        key = jax.random.fold_in(self.key, 0)
        # the program holds a run of repeating layers stacked by repeat
        # (`TransformerLM.stack_layers`): the same values, a layer at a time
        params = jax.device_put(
            dict(ref.top_weights(key, cfg), segments=self.model.stack_layers(
                lambda l: ref.layer_weights(key, l, cfg))),
            _lm.shardings_of(self.model))
        self.requests = gen.requests(mix, self.ctx.seed, cfg["vocab_size"])
        self.logprobs = {}      # request -> what the engine said of its tokens
        self.eng = serve_transformer(
            self.model, params, int(mix["max_seq_len"]), decode=True,
            slots=int(mix["slots"]), queue_limit=int(mix["queue_limit"]),
            logprobs=True)
        del params
        self.eng.warmup(prompt_lens=sorted({len(p) for p, _o in self.requests}))
        for c in range(int(mix["clients"])):
            self._submit(c)
        warm = int(mix["warm_completions"])
        while len(self.records) < warm:
            self._take(timeout=600.0)
        self.n_warm = len(self.records)

    def counters(self):
        st = self.eng.stats()
        out = super().counters()
        if "moe_pairs_total" in st:
            out.update(moe_pairs_total=st["moe_pairs_total"],
                       moe_pairs_held=st["moe_pairs_held"])
            out.update({f"moe_pairs_expert_{e}": n for e, n in
                        enumerate(st["moe_pairs_by_expert"])})
        return out

    def _take(self, timeout):
        """`lm_decode.Cell._take`, and the request's log-probabilities kept
        beside its tokens."""
        client, i, t0, t1, fut = self.done_q.get(timeout=timeout)
        toks = None
        if not isinstance(fut, Exception) and fut.exception() is None:
            toks = fut.result()
            self.logprobs[i] = fut.logprobs
        self.records.append((i, t0, t1, toks))
        if self.open:
            self._submit(client)

    def _sample(self):
        """The judged requests: the longest the window finished, then the
        first of each prefill bucket in the seed's order (every prefill
        program the window ran is held against the reference), then that
        order on, `check_requests` in all."""
        ok = [r for r in self.window_done if r[3] is not None]
        if not ok:
            return []
        model = build_model(self.ctx.config, self.ctx.devices, self.ctx.memo)
        longest = max(range(len(ok)), key=lambda j: len(ok[j][3]))
        rng = np.random.default_rng([self.ctx.seed, 3])
        order = [longest] + [j for j in rng.permutation(len(ok))
                             if j != longest]
        first_of = {}
        for j in order:
            prompt = self.requests[ok[j][0] % len(self.requests)][0]
            first_of.setdefault(model.serving_bucket(len(prompt)), j)
        picked = dict.fromkeys([longest, *first_of.values(), *order])
        return [ok[j] for j in picked][:int(self.mix["check_requests"])]

    def numbers(self, control=False):
        sample = self._sample()
        nothing = {"logprob_err": float("nan"), "token_gap": float("nan"),
                   "wrong_answers": self._wrong_answers(),
                   "logprob_err_widest": float("nan"), "tokens_judged": 0,
                   "requests_judged": 0}
        if not sample:
            return nothing
        # one shape for every seed: the longest sequence the mix sends
        S = int(gen.request_sizes(self.mix).sum(axis=1).max())
        toks = np.zeros((len(sample), -(-S // 256) * 256), np.int32)
        n_prompt, n_total = [], []
        for row, (i, _t0, _t1, served) in zip(toks, sample):
            row[:len(served)] = served
            n_prompt.append(len(self.requests[i % len(self.requests)][0]))
            n_total.append(len(served))
        judged = ref.judge_served(jax.random.fold_in(self.key, 0),
                                  self.ctx.config, toks, n_prompt, n_total,
                                  control=control)
        errs = [np.abs((j["logp8"] if control else self.logprobs[i])
                       - j["logp"]) / j["spread"]
                for (i, *_rest), j in zip(sample, judged)]
        return dict(
            nothing,
            logprob_err=max(float(np.sqrt(np.mean(e * e))) for e in errs),
            logprob_err_widest=max(float(e.max()) for e in errs),
            token_gap=max(float(j["gap"].max()) for j in judged),
            tokens_judged=int(sum(n_total) - sum(n_prompt)),
            requests_judged=len(sample))

    def faults(self):
        """`tools/readings.py --faults`: ONE served token, the middle one of
        the longest judged answer, replaced by the next in the vocabulary,
        and the numbers read again: what `token_gap`'s limit is under."""
        record = self._sample()[0]
        i, t0, t1, served = record
        altered = served.copy()
        n_prompt = len(self.requests[i % len(self.requests)][0])
        at = (n_prompt + len(served)) // 2
        altered[at] = (altered[at] + 1) % self.ctx.config["vocab_size"]
        where = next(j for j, r in enumerate(self.window_done) if r is record)
        self.window_done[where] = (i, t0, t1, altered)
        try:
            return {"token_altered": self.numbers()}
        finally:
            self.window_done[where] = record
