"""Operations and bytes that the `phi-4-mini-flash` configuration NEEDS, from
its shapes alone (`work.py`'s counts are the dense model's; that file is code
the benchmark already has, so this configuration's counts live beside it).

The counts are of the mathematics, not of the program: pad rows of a prompt's
bucket, the zeros the step's plain-matrix attention multiplies, lane rows
beyond a slot's position and a second pass over anything are the program's
business and lower the share it reaches.
"""

from __future__ import annotations

from perfbench.references import phi4flash as ref


def _z(cfg: dict) -> dict:
    return ref.sizes(cfg)


def matmul_params_by_kind(cfg: dict) -> dict:
    """Parameters in matrix products of ONE layer of each kind (its gated MLP
    included), and of the tied head."""
    z = _z(cfg)
    D, F, H, Hkv, d = z["D"], z["F"], z["H"], z["Hkv"], z["d"]
    di, N, R = z["di"], z["N"], z["R"]
    mlp = D * 2 * F + F * D
    self_attn = D * (H + 2 * Hkv) * d + H * d * D
    return {"mamba": mlp + D * 2 * di + di * (R + 2 * N) + R * di + di * D,
            "window": mlp + self_attn, "full": mlp + self_attn,
            "cross": mlp + D * H * d + H * d * D,
            "gmu": mlp + D * di + di * D, "head": D * z["V"]}


def layer_counts(cfg: dict) -> dict:
    kinds = ref.kinds(cfg["num_hidden_layers"])
    return {k: kinds.count(k) for k in set(kinds)}


def flops_per_token(cfg: dict, context: float, head: bool = True) -> float:
    """Forward FLOPs ONE token needs when it attends over `context`
    positions (itself included): 2 a matrix parameter; per attended position
    and attention layer the pairs' two maps, QK^T at 2 H d and the 2d-wide
    values at 4 H d (a window layer attends min(context, window)); per
    state-space layer the convolution (2 K d_inner) and the recurrence (7 a
    state element: step-size times A, the exponential, two products and a sum
    for the state, a product and a sum for y). `head`: the tied output head
    (an output token has one; a prompt token has none)."""
    z, n, mm = _z(cfg), layer_counts(cfg), matmul_params_by_kind(cfg)
    flops = 2.0 * sum(n[k] * mm[k] for k in n)
    core = 6.0 * z["H"] * z["d"]
    flops += core * (n["full"] + n["cross"]) * context
    flops += core * n["window"] * min(context, z["W"])
    flops += n["mamba"] * (2.0 * z["K"] * z["di"] + 7.0 * z["di"] * z["N"])
    flops += n["gmu"] * z["di"]
    return flops + (2.0 * mm["head"] if head else 0.0)


def param_bytes(cfg: dict) -> int:
    """Bytes of every parameter a decode step reads: each layer's matrices
    and the embedding, which is the head (2 bytes in bfloat16); the small
    float32 vectors vanish beside them and are counted too."""
    z = _z(cfg)
    item = 2 if cfg["param_dtype"] == "bfloat16" else 4
    total = z["V"] * z["D"] * item + 2 * z["D"] * 4
    for kind in ref.kinds(z["L"]):
        for _name, (shape, held) in ref.layer_shapes(kind, z).items():
            n = 1
            for s in shape:
                n *= s
            total += n * (item if held else 4)
    return total


def slot_bytes_per_step(cfg: dict, context: float) -> float:
    """Bytes ONE live slot's state costs a decode step at an attended
    `context`: the full layer's lane read by it and by every cross layer
    (context rows of K and V each, 2 bytes an element), a window layer's ring
    (min(context, window) rows), one row of K and V written a self-attention
    layer, and a state-space layer's float32 state and convolution tail read
    and written."""
    z, n = _z(cfg), layer_counts(cfg)
    row = 2 * z["Hkv"] * z["d"] * 2                       # K and V, bfloat16
    lane = (n["full"] + n["cross"]) * context * row
    ring = n["window"] * min(context, z["W"]) * row
    wrote = (n["full"] + n["window"]) * row
    state = n["mamba"] * 2 * (z["N"] * z["di"] * 4
                              + (z["K"] - 1) * z["di"] * 2)
    return lane + ring + wrote + state


def step_bytes(cfg: dict, live_slots: float, context: float) -> float:
    """Bytes ONE decode step needs: the parameters once, and each live
    slot's state."""
    return param_bytes(cfg) + live_slots * slot_bytes_per_step(cfg, context)


def mean_contexts(sizes) -> tuple:
    """(mean attended context of an OUTPUT token, of a PROMPT token) over
    the mix's (prompt, output) sizes: output token i of a request attends
    over prompt + i positions, prompt token t over t + 1."""
    p = [float(a) for a, _b in sizes]
    o = [float(b) for _a, b in sizes]
    out_ctx = sum(b * (a + (b + 1) / 2.0) for a, b in zip(p, o)) / sum(o)
    prompt_ctx = sum(a * (a + 1) / 2.0 for a in p) / sum(p)
    return out_ctx, prompt_ctx
