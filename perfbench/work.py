"""Operations and bytes that the algorithms NEED, from shapes alone.

The counts are of the mathematics, not of the program: recomputation, padding
rows, a second pass over the data or an upcast that the compiler did not fuse
are the program's business and lower the share it reaches. `tokens_delivered`
is the serving cells' count of work done inside a window.
"""

from __future__ import annotations


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that sit in matrix multiplications of one forward pass of
    the causal LM: per layer the fused QKV (d x 3d), the output projection
    (d x d) and the two MLP matrices (d x f, f x d), plus the unembedding
    (d x V). The embedding table is a gather and the norm scales are
    elementwise: neither counts."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = d * 3 * d + d * d + 2 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def lm_attention_flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs of the attention CORE for one token that attends over
    ``context`` positions (itself included): per layer QK^T and PV at 2 * d
    FLOPs per attended position each. Masked positions do not count."""
    return cfg["num_hidden_layers"] * 4.0 * cfg["hidden_size"] * context


def lm_forward_flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs for ONE token that attends over ``context`` positions:
    2 per matrix parameter, plus the attention core's."""
    return (2.0 * lm_matmul_params(cfg)
            + lm_attention_flops_per_token(cfg, context))


def lm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of a causal sequence of ``seq`` tokens, per token:
    three times the forward, whose mean attended context is (seq + 1) / 2.
    Recomputed forwards (remat) do not count."""
    return 3.0 * lm_forward_flops_per_token(cfg, (seq + 1) / 2.0)


def lm_decode_flops(cfg: dict, prompt_len: int, n_out: int) -> float:
    """Forward FLOPs one request NEEDS: a causal prefill of its prompt (the
    first output token comes from it) and one cached step per further
    output token, the i-th of which attends over prompt_len + i positions."""
    prefill = prompt_len * lm_forward_flops_per_token(
        cfg, (prompt_len + 1) / 2.0)
    steps = n_out - 1
    mean_ctx = prompt_len + (steps + 1) / 2.0
    return prefill + steps * lm_forward_flops_per_token(cfg, mean_ctx)


def lloyd_bytes_per_iteration(rows: int, features: int, itemsize: int) -> int:
    """Bytes ONE Lloyd iteration has to move on one device: one read of its
    rows of X. Centroids, sums and counts are k x features and vanish."""
    return rows * features * itemsize


def cdist_bytes(n: int, m: int = None, itemsize: int = 4) -> int:
    """Bytes ONE distance matrix of n x m has to move: one write of the
    result. The inputs are (n + m) x features and vanish beside it."""
    return n * (n if m is None else m) * itemsize


def tokens_delivered(requests, lo: float, hi: float) -> float:
    """Output tokens that callers were delivered inside [lo, hi]. A caller sees
    a request from its submit `s` to its last token `d` and gets `n` tokens for
    it: n / (d - s) tokens a second over that life, the reciprocal of the
    request's ms per token. A request counts the part of its life that lies
    inside the window. `requests`: (s, d, n), every request whose life touches
    the window, those that ended after it too. Counting a request WHOLE where
    it completes reads 597 or 609 tokens/s on one load as a 360-token answer
    falls 0.07 s before the close or after it, and 625 in a window of 51 s
    (PERF.md section 6); this reads the same in all three."""
    total = 0.0
    for s, d, n in requests:
        inside = min(d, hi) - max(s, lo)
        if inside > 0.0 and d > s:
            total += n * inside / (d - s)
    return total


def mfu_percent(flops: float, seconds: float, chips: int, peak: float) -> float:
    return 100.0 * flops / (seconds * chips * peak)
