"""Operations and bytes that the `granite-4.0-h-small-d10e36` configuration
NEEDS, from its shapes alone (`work.py`'s counts are the dense model's; that
file is code the benchmark already has, so this configuration's counts live
beside it, as `work_phi4flash.py` does).

The counts are of the mathematics, not of the program: pad rows of a prompt's
bucket, the chunked prompt form's extra products, the zeros the step's
plain-matrix attention multiplies, lane rows beyond a slot's position, pairs
that fall on experts held elsewhere (they cost this chip nothing) and a second
pass over anything are the program's business and lower the share it reaches.
The routed experts' FLOPs follow the pairs that fell on HELD experts, which the
program counts (`moe_pairs_held`): with random weights about half of a token's
ten choices.
"""

from __future__ import annotations

from perfbench.references import granite4h as ref
from perfbench.trace_scopes import UNSCOPED, scope_seconds
from perfbench.work_phi4flash import mean_contexts  # noqa: F401 (the readers')

# the grouped products (`lax.ragged_dot`) leave the TPU's compiler as custom
# calls named `ragged-dot-none.<n>` that carry NO scope of the program (my
# chip run, PR 37): the expert layer's time is its `moe` scope AND these
GROUPED = "ragged-dot"


def moe_seconds(trace: dict, program: str = None) -> float:
    """Device 0's self time in the expert layers (one program or all): under
    the program's `moe` scope, plus the unscoped grouped products by name."""
    return (scope_seconds(trace, "moe", program=program)
            + scope_seconds(trace, UNSCOPED, program=program,
                            op_prefix=GROUPED))


def layer_counts(cfg: dict) -> dict:
    kinds = ref.kinds(cfg)
    return {k: kinds.count(k) for k in ("mamba2", "gqa")}


def matmul_params(cfg: dict) -> dict:
    """Parameters in the matrix products ONE token passes: each kind of
    mixer, the shared expert and the router of a layer, ONE routed expert,
    and the tied head."""
    z = ref.sizes(cfg)
    D, H, Hkv, d = z["D"], z["H"], z["Hkv"], z["d"]
    return {"mamba2": D * (2 * z["di"] + 2 * z["N"] + z["Hs"]) + z["di"] * D,
            "gqa": D * (H + 2 * Hkv) * d + H * d * D,
            "shared": 3 * D * z["Fs"], "router": D * z["E"],
            "expert": 3 * D * z["Fe"], "head": D * z["V"]}


def flops_per_token(cfg: dict, context: float, held_pairs: float,
                    head: bool = True) -> float:
    """Forward FLOPs ONE token needs on THIS chip when it attends over
    `context` positions (itself included) and `held_pairs` of its choices a
    layer fall on held experts: 2 a matrix parameter (mixer, shared expert
    and router of every layer, `held_pairs` routed experts a layer); per
    attended position of the attention layer QK^T and PV at 2 H d each; per
    state-space layer the filter (2 K (d_inner + 2 N)) and the recurrence
    (5 a state element: the decay's product, x (x) B's product and their
    sum for the state, a product and a sum for y). `head`: the tied output
    head (an output token has one; a prompt token has none)."""
    z, n, mm = ref.sizes(cfg), layer_counts(cfg), matmul_params(cfg)
    flops = 2.0 * (n["mamba2"] * mm["mamba2"] + n["gqa"] * mm["gqa"]
                   + z["L"] * (mm["shared"] + mm["router"]
                               + held_pairs * mm["expert"]))
    flops += n["gqa"] * 4.0 * z["H"] * z["d"] * context
    flops += n["mamba2"] * (2.0 * z["K"] * (z["di"] + 2 * z["N"])
                            + 5.0 * z["di"] * z["N"])
    return flops + (2.0 * mm["head"] if head else 0.0)


def _bytes(shapes: dict, item: int, names=None) -> int:
    total = 0
    for name, (shape, held) in shapes.items():
        if names is None or name in names:
            n = 1
            for s in shape:
                n *= s
            total += n * (item if held else 4)
    return total


def param_bytes(cfg: dict) -> int:
    """Bytes of every parameter a decode step reads: each layer's matrices,
    its HELD experts among them (at the cell's occupancy every held expert is
    hit every step: 320 held pairs a layer on 36 experts), and the embedding,
    which is the head; the small float32 vectors are counted too."""
    z = ref.sizes(cfg)
    item = 2 if cfg["param_dtype"] == "bfloat16" else 4
    return (z["V"] * z["D"] * item + z["D"] * 4
            + sum(_bytes(ref.layer_shapes(kind, z), item)
                  for kind in ref.kinds(cfg)))


def moe_bytes(cfg: dict) -> int:
    """Bytes of the expert layers' parameters a step reads: the held
    experts, the shared expert and the router of every layer."""
    z = ref.sizes(cfg)
    item = 2 if cfg["param_dtype"] == "bfloat16" else 4
    return z["L"] * _bytes(ref.layer_shapes("gqa", z), item,
                           ("router", "we1", "we2", "ws1", "ws2"))


def state_bytes_per_step(cfg: dict, context: float) -> float:
    """Bytes ONE live slot's mixers move a step beside their weights: a
    state-space layer's float32 state and its convolution tail read and
    written; `context` rows of K and V read from the attention layer's lane
    and one row of each written (2 bytes an element)."""
    z, n = ref.sizes(cfg), layer_counts(cfg)
    state = n["mamba2"] * 2 * (z["di"] * z["N"] * 4
                               + (z["K"] - 1) * (z["di"] + 2 * z["N"]) * 2)
    row = 2 * z["Hkv"] * z["d"] * 2                       # K and V, bfloat16
    return state + n["gqa"] * (context + 1) * row


def step_bytes(cfg: dict, live_slots: float, context: float) -> float:
    """Bytes ONE decode step needs: the parameters once, and each live
    slot's states and lane."""
    return param_bytes(cfg) + live_slots * state_bytes_per_step(cfg, context)
