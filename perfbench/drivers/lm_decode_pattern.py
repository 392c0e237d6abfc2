"""Driver `lm_decode_pattern`: the `lm_decode` cell for a model with a
per-layer PATTERN (`phi-4-mini-flash`: state-space, window, full, cross and
gated-memory mixers). The loop, the window, the judgement of shapes and
`check` are `lm_decode.Cell`'s, inherited, so both decode cells are timed by
the same code; this file brings the model, its weights and its reference.

Set-up differs in two places only: the `TransformerLM` is built with the
pattern (`build_model`), and the weights are the reference module's
(`references/phi4flash.py`: made from the seed a layer at a time, matrices
rounded once to the configuration's `param_dtype`; the program is handed those
values, stacked as it holds them, the reference asks for the same values as
float32). `lm_decode.Cell.setup`
names its model and weights inline, so its few lines on the engine and the
clients are repeated here (PERF.md section 7.4 asks a `benchmark` PR for the
hook).

`correct`: the judged requests (the longest finished and the rest drawn from
the seed, `_sample`) go through the float32 reference TOGETHER, a layer at a
time, padded to `max_seq_len`; `token_gap` is the widest gap by which a served
token's reference logit lies below the reference's best.
"""

from __future__ import annotations

import gc

import numpy as np

import jax
import jax.numpy as jnp

from perfbench import traffic as gen
from perfbench.drivers import _lm, lm_decode
from perfbench.references import phi4flash as ref


def build_model(config: dict, devices, memo: dict):
    """The pattern `TransformerLM` on a (1,1,1,1) grid of one chip."""
    if "model" in memo:
        return memo["model"]
    import heat_tpu as ht
    from heat_tpu.nn.transformer import (TransformerLM, TransformerLMConfig,
                                         sambay_pattern)

    z = ref.sizes(config)
    cfg = TransformerLMConfig(
        vocab=z["V"], d_model=z["D"], n_heads=z["H"], n_kv_heads=z["Hkv"],
        n_layers=z["L"], d_ff=z["F"], rope=False,
        pattern=sambay_pattern(z["L"]), window=z["W"], d_inner=z["di"],
        d_state=z["N"], d_conv=z["K"], dt_rank=z["R"], norm_eps=z["eps"],
        init_scale=z["scale"],
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]))
    assert cfg.pattern == ref.kinds(z["L"])
    grid = ht.MeshGrid((1, 1, 1, 1), _lm.AXES, devices=list(devices)[:1])
    memo["model"] = TransformerLM(grid, cfg)
    return memo["model"]


class Cell(lm_decode.Cell):
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

    def release(self):
        super().release()
        gc.collect()     # the reference needs the chip the engine held

    def counters(self):
        st = self.eng.stats()
        return dict(super().counters(), prefill_tokens=st["prefill_tokens"],
                    state_resets=st["state_resets"])

    def numbers(self, control=False):
        cfg = self.ctx.config
        sample = self._sample()
        S = int(self.mix["max_seq_len"])
        toks = np.zeros((len(sample), S), np.int32)
        n_prompt, n_total = [], []
        for row, (i, _t0, _t1, served) in zip(toks, sample):
            row[:len(served)] = served
            n_prompt.append(len(self.requests[i % len(self.requests)][0]))
            n_total.append(len(served))
        gaps = ref.widest_gaps(jax.random.fold_in(self.key, 0), cfg, toks,
                               n_prompt, n_total, control=control) \
            if sample else []
        return {"token_gap": max(gaps) if gaps else float("nan"),
                "wrong_answers": self._wrong_answers(),
                "tokens_judged": int(sum(n_total) - sum(n_prompt)),
                "requests_judged": len(gaps)}
