"""Flagship combined-parallelism transformer LM over a MeshGrid.

One compiled ``shard_map`` train step composes every strategy in the
framework's parallelism inventory (PARITY.md §2.6):

* **dp** — batch sharded over the ``dp`` axis; gradient averaging is the
  AD transpose of the loss ``psum`` (the reference's ``nn.DataParallel``
  Allreduce, ``heat/nn/data_parallel.py:223-297``, fused into the step).
* **pp** — layers split into pipeline stages over the ``pp`` axis
  (:func:`heat_tpu.nn.parallel.pipeline_apply`, GPipe microbatch schedule).
* **tp** — attention heads and MLP features Megatron-sharded over the
  ``tp`` axis (one psum per block).
* **sp** — the token sequence sharded over the ``sp`` axis end to end;
  attention runs as an exact causal ring
  (:func:`heat_tpu.nn.attention._ring_body`: ppermute + online softmax).
* **ep** — optional Switch-MoE MLPs with experts sharded over the ``dp``
  axis (:func:`heat_tpu.nn.parallel.switch_moe`, all_to_all routing), the
  standard experts-over-dp placement.

Gradient correctness: the step runs under ``check_vma=True`` so shard_map
tracks which values are varying vs replicated along each mesh axis. That
makes every collective transpose exact — in particular, cotangents of
replicated parameters (embeddings, norm scales, each stage's weights
w.r.t. the dp/sp axes) are psum'd across exactly the axes the parameter
is replicated over, with no manual factor bookkeeping. Verified against a
dense single-device reference in ``tests/test_transformer.py``.

The reference has no transformer stack (SURVEY.md §2.6); this is the
"long-context and distributed are first-class" flagship built on the
reference's three sequence primitives (halo/ring/all-to-all).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core._compat import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.communication import MeshGrid
from ..utils.profiling import scope, span
from . import mixers
from .attention import (_ring_body, _ulysses_core, _zigzag_core,
                        local_attention, zigzag_layout, zigzag_unlayout)
from .parallel import (gated_ffn, pipeline_apply, routed_experts,
                       switch_moe)

__all__ = ["TransformerLM", "TransformerLMConfig", "MIXER_KINDS",
           "FFN_KINDS", "sambay_pattern"]

# what a layer's token mixer may be (``TransformerLMConfig.pattern``)
MIXER_KINDS = ("attn", "mamba", "window", "full", "cross", "gmu", "mamba2",
               "gqa")
# the mixers that are differential attention (pairs of heads)
DIFF_KINDS = ("window", "full", "cross")
# what a pattern layer's feed-forward may be (``TransformerLMConfig.ffn``)
FFN_KINDS = ("mlp", "moe")


def sambay_pattern(n_layers: int) -> Tuple[str, ...]:
    """The decoder-hybrid-decoder's placement rule (arXiv:2507.06607, as
    Phi-4-mini-flash's modeling file applies it) for ``n_layers`` layers, a
    multiple of four. Self-decoder, layers 0..n/2+1: Mamba on the even
    layers, window attention on the odd ones, and ONE full-attention layer
    last (n/2+1), whose keys and values are the only full-length ones.
    Cross-decoder, the rest: gated memory units (even; they read the last
    Mamba layer's output) and cross-attention to the full layer's keys and
    values (odd)."""
    if n_layers < 4 or n_layers % 4:
        raise ValueError(
            f"the pattern needs a multiple of four layers, got {n_layers}")
    half = n_layers // 2
    return tuple(
        ("mamba" if l % 2 == 0 else "window") if l <= half
        else "full" if l == half + 1
        else ("gmu" if l % 2 == 0 else "cross")
        for l in range(n_layers))


def _segments(kinds) -> Tuple[Tuple[int, int, int], ...]:
    """``kinds`` cut into runs ``(first layer, period, repeats)``: from each
    layer on, the period of at most four kinds that repeats over the most
    layers; a layer that opens no repeat is a run of its own, (l, 1, 1)."""
    out, l = [], 0
    while l < len(kinds):
        period, reps = 1, 1
        for p in range(1, 5):
            r = 1
            while kinds[l + r * p:l + (r + 1) * p] == kinds[l:l + p]:
                r += 1
            if r > 1 and p * r > period * reps:
                period, reps = p, r
        out.append((l, period, reps))
        l += period * reps
    return tuple(out)


@dataclass
class TransformerLMConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 8
    n_layers: int = 2
    d_ff: Optional[int] = None          # default 4 * d_model
    moe_experts: int = 0                # 0 = dense MLP; >0 = Switch-MoE
    capacity_factor: float = 1.25
    n_micro: int = 1                    # microbatches for the pp schedule
    compute_dtype: Any = jnp.float32    # bf16 on real TPUs for MXU rate
    init_scale: float = 0.02
    attn_schedule: str = "ring"         # "ring" | "zigzag" (load-balanced
                                        # causal ring) | "ulysses" (all_to_all
                                        # head-parallel; local heads % sp == 0)
    rope: bool = True                   # rotary position embeddings on q/k
    rope_theta: float = 10000.0
    remat: bool = False                 # jax.checkpoint each layer: trade
                                        # recompute FLOPs for activation HBM
    # -- the per-layer pattern (None: every layer plain causal attention, a
    # GELU MLP and RMSNorm, what training and ``generate`` support). A
    # pattern is SERVED (``serve_transformer(..., decode=True)``): one mixer
    # kind a layer out of MIXER_KINDS, pre-norm, no positional encoding, the
    # head tied to the embedding. Its pieces are fields of their own, each
    # with the first served pattern's choice as its default: LayerNorm with
    # bias, a gated SiLU MLP in every layer, no multipliers.
    pattern: Optional[Tuple[str, ...]] = None
    n_kv_heads: Optional[int] = None    # default n_heads
    window: int = 0                     # rows a "window" layer sees and keeps
    d_inner: Optional[int] = None       # state-space width, default 2 d_model
    d_state: int = 16
    d_conv: int = 4
    dt_rank: Optional[int] = None       # default ceil(d_model / 16)
    norm_eps: float = 1e-5              # every norm of a pattern
    param_dtype: Any = jnp.float32      # what the matrices are HELD in
    norm_kind: str = "layernorm"        # | "rmsnorm" (a scale, no bias)
    ssm_heads: int = 0                  # "mamba2": heads of d_inner/ssm_heads,
    ssm_chunk: int = 256                # each a scalar decay; a prompt's chunk
    # one feed-forward kind a layer out of FFN_KINDS (None: "mlp" everywhere).
    # "moe": ``experts_per_token`` of ``n_experts`` routed gated experts of
    # width ``d_expert``, gates a softmax over the chosen, beside one shared
    # gated expert of width ``d_shared`` (0: none). The device HOLDS experts
    # ``experts_held = (first, count)`` (None: all) and computes their part.
    ffn: Optional[Tuple[str, ...]] = None
    n_experts: int = 0
    experts_per_token: int = 1
    d_expert: int = 0
    d_shared: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    embedding_multiplier: float = 1.0   # h0 = m * E[tok]
    residual_multiplier: float = 1.0    # h += m * Mixer(..), h += m * FFN(..)
    attention_multiplier: Optional[float] = None    # "gqa" scores' scale,
    #                                     default 1 / sqrt(head_dim)
    logits_scaling: float = 1.0         # logits = .. / m

    def __post_init__(self):
        if self.d_ff is None:
            self.d_ff = 4 * self.d_model
        if self.d_model % self.n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        if self.n_kv_heads is None:
            self.n_kv_heads = self.n_heads
        if self.d_inner is None:
            self.d_inner = 2 * self.d_model
        if self.dt_rank is None:
            self.dt_rank = -(-self.d_model // 16)
        if self.attention_multiplier is None:
            self.attention_multiplier = 1.0 / math.sqrt(self.head_dim)
        if self.pattern is not None:
            self.pattern = tuple(self.pattern)
            self.ffn = (tuple(self.ffn) if self.ffn is not None
                        else ("mlp",) * len(self.pattern))
            if self.experts_held is None:
                self.experts_held = (0, self.n_experts)
            self.experts_held = tuple(int(n) for n in self.experts_held)
            self._check_pattern()
        elif self.ffn is not None:
            raise ValueError("ffn names a pattern's feed-forward kinds: it "
                             "needs a pattern")
        if self.attn_schedule not in ("ring", "zigzag", "ulysses"):
            raise ValueError(
                f"attn_schedule must be 'ring', 'zigzag' or 'ulysses', got "
                f"{self.attn_schedule!r}")
        if self.rope and self.head_dim % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {self.head_dim}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def _check_pattern(self):
        kinds = self.pattern
        if len(kinds) != self.n_layers:
            raise ValueError(
                f"pattern names {len(kinds)} layers, n_layers is "
                f"{self.n_layers}")
        unknown = sorted(set(kinds) - set(MIXER_KINDS[1:]))
        if unknown:
            raise ValueError(
                f"pattern kinds must be of {MIXER_KINDS[1:]}, got {unknown}")
        if self.moe_experts or self.rope:
            raise ValueError(
                "a pattern has a gated dense MLP (or, by `ffn`, routed "
                "experts) and no positional encoding: moe_experts=0, "
                "rope=False")
        if self.norm_kind not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"norm_kind must be 'layernorm' or 'rmsnorm', got "
                f"{self.norm_kind!r}")
        if "gqa" in kinds and self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"grouped-query attention shares a key/value head among "
                f"n_heads / n_kv_heads query heads: {self.n_heads} is no "
                f"multiple of {self.n_kv_heads}")
        if "mamba2" in kinds and (self.ssm_heads < 1
                                  or self.d_inner % self.ssm_heads):
            raise ValueError(
                f"a 'mamba2' layer needs ssm_heads >= 1 that divides d_inner "
                f"({self.d_inner}), got {self.ssm_heads}")
        if len(self.ffn) != self.n_layers:
            raise ValueError(
                f"ffn names {len(self.ffn)} layers, n_layers is "
                f"{self.n_layers}")
        unknown = sorted(set(self.ffn) - set(FFN_KINDS))
        if unknown:
            raise ValueError(
                f"ffn kinds must be of {FFN_KINDS}, got {unknown}")
        if "moe" in self.ffn:
            E, (first, count) = self.n_experts, self.experts_held
            if not 1 <= self.experts_per_token <= E:
                raise ValueError(
                    f"experts_per_token ({self.experts_per_token}) must lie "
                    f"in 1..n_experts ({E})")
            if first < 0 or count < 1 or first + count > E:
                raise ValueError(
                    f"experts_held {self.experts_held} (first, count) "
                    f"reaches outside the {E} experts there are")
            if self.d_expert < 1 or self.d_shared < 0:
                raise ValueError(
                    f"a 'moe' layer needs d_expert >= 1 and d_shared >= 0, "
                    f"got {self.d_expert} and {self.d_shared}")
        if set(kinds) & set(DIFF_KINDS) and (
                self.n_heads % 4 or self.n_heads != 2 * self.n_kv_heads):
            raise ValueError(
                "differential attention pairs adjacent heads and each pair "
                "of pairs shares two key/value heads: n_heads must be a "
                f"multiple of 4 and twice n_kv_heads, got {self.n_heads} and "
                f"{self.n_kv_heads}")
        if "window" in kinds and self.window < 1:
            raise ValueError("a 'window' layer needs window >= 1")
        for l, kind in enumerate(kinds):
            if kind == "cross" and "full" not in kinds[:l]:
                raise ValueError(
                    f"layer {l} is 'cross' with no 'full' layer before it "
                    "whose keys and values it could read")
            if kind == "gmu" and "mamba" not in kinds[:l]:
                raise ValueError(
                    f"layer {l} is 'gmu' with no 'mamba' layer before it "
                    "whose output it could gate")


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


def rope_apply(x, pos, theta: float = 10000.0):
    """Rotary position embedding (half-split convention) on ``(mb, S, H,
    Dh)`` with GLOBAL token positions ``pos`` of shape ``(S,)`` — or
    ``(mb, S)`` when every batch row sits at its own position (the
    serving decode engine: one slot per row, each mid-stream). Positions
    are supplied explicitly because under sequence parallelism the local
    block's positions depend on the layout: contiguous split gives
    ``r*S_local + arange``, the zigzag layout two chunk-offset ranges."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq  # (S, half) | (B, S, half)
    if ang.ndim == 2:
        ang = ang[None]                              # shared across the batch
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class TransformerLM:
    """Causal LM with dp x pp x tp x sp (x ep) over a 4-axis MeshGrid.

    ``grid`` must have axes named ``("dp", "pp", "tp", "sp")`` (any sizes,
    1 allowed). Parameters are held as global ``jax.Array``s with
    ``NamedSharding``s; stage weights carry a leading ``pp`` axis, head /
    feature axes shard over ``tp``, expert axes over ``dp``.
    """

    AXES = ("dp", "pp", "tp", "sp")

    def __init__(self, grid: MeshGrid, config: TransformerLMConfig):
        names = tuple(grid.axis_names)
        # an optional LEADING "dcn" axis declares the slow inter-host
        # tier of a 2-level dp grid (dcn x dp both shard the batch):
        # parameters stay replicated over it (the specs never name it),
        # and the packed train step's gradient all-reduce decomposes
        # hierarchically — reduce-scatter inside the fast tier,
        # all-reduce of the 1/p_ici shard across dcn, all-gather back
        # (heat_tpu.core.fusion.packed_psum, HEAT_TPU_HIER)
        if names == self.AXES:
            self._has_dcn = False
        elif names == ("dcn",) + self.AXES:
            self._has_dcn = True
        else:
            raise ValueError(
                f"grid axes must be {self.AXES} (optionally with a "
                f"leading 'dcn' tier axis), got {grid.axis_names}")
        self.grid = grid
        self.cfg = config
        c = config
        self.dcn = grid.mesh.shape["dcn"] if self._has_dcn else 1
        self.pp = grid.mesh.shape["pp"]
        self.tp = grid.mesh.shape["tp"]
        self.dp = grid.mesh.shape["dp"]
        self.sp = grid.mesh.shape["sp"]
        if c.n_layers % self.pp:
            raise ValueError(f"n_layers ({c.n_layers}) must divide over pp ({self.pp})")
        if c.n_heads % self.tp:
            raise ValueError(f"n_heads ({c.n_heads}) must divide over tp ({self.tp})")
        if c.d_ff % self.tp:
            raise ValueError(f"d_ff ({c.d_ff}) must divide over tp ({self.tp})")
        if c.moe_experts and c.moe_experts % self.dp:
            raise ValueError(
                f"moe_experts ({c.moe_experts}) must divide over dp ({self.dp}) "
                "(experts are sharded over the dp axis)")
        if (c.attn_schedule == "ulysses" and self.sp > 1
                and (c.n_heads // self.tp) % self.sp):
            raise ValueError(
                f"ulysses schedule needs local heads ({c.n_heads}//{self.tp}"
                f"={c.n_heads // self.tp}) divisible by sp ({self.sp})")
        self.layers_per_stage = c.n_layers // self.pp
        self.mesh_size = self.dcn * self.dp * self.pp * self.tp * self.sp
        # one mixer kind a layer; "attn" everywhere is the dense model
        self.kinds = c.pattern if c.pattern else ("attn",) * c.n_layers
        # and one feed-forward kind (a pattern's; the dense model has its own)
        self.ffn = c.ffn if c.pattern else ("mlp",) * c.n_layers
        self.has_experts = "moe" in self.ffn
        # a pattern's layers by (first layer, period, repeats): its
        # parameters are stacked by repeat and a prompt's forward scans them
        # (the dense model's are stacked by stage: a layer a segment here)
        self.segments = (_segments(tuple(zip(self.kinds, self.ffn)))
                         if c.pattern else
                         tuple((l, 1, 1) for l in range(c.n_layers)))
        if self.has_experts and (self.pp, self.tp) != (1, 1):
            raise ValueError(
                f"a 'moe' layer holds its experts {c.experts_held} whole on "
                f"every device of the grid: it is served with pp = tp = 1 "
                f"(experts under tp, or exchanged between devices, are not "
                f"built), got pp={self.pp} tp={self.tp}")
        if c.pattern and (self.pp, self.tp, self.sp) != (1, 1, 1):
            raise ValueError(
                f"pattern {self._pattern_name()} is served on dp-only grids "
                f"(pp = tp = sp = 1), got pp={self.pp} tp={self.tp} "
                f"sp={self.sp}")
        self._step_cache: Dict = {}

    @property
    def dp_world(self) -> int:
        """Total data-parallel replication: the dp axis times the
        optional dcn tier axis above it (batch rows shard over both)."""
        return self.dcn * self.dp

    # ------------------------------------------------------------- #
    # parameters                                                    #
    # ------------------------------------------------------------- #

    def _pattern_name(self) -> str:
        """The pattern, short enough for a message: runs of one kind."""
        runs = []
        for kind in self.kinds:
            if runs and runs[-1][0] == kind:
                runs[-1][1] += 1
            else:
                runs.append([kind, 1])
        return "(" + ", ".join(k if n == 1 else f"{k} x{n}"
                               for k, n in runs) + ")"

    def _needs_dense(self, what: str) -> None:
        if self.cfg.pattern:
            raise NotImplementedError(
                f"{what} supports the dense pattern only; the pattern "
                f"{self._pattern_name()} is served: serve_transformer("
                f"model, params, max_seq_len, decode=True)")

    def pattern_param_shapes(self) -> Dict[str, Any]:
        """The parameter tree of a PATTERN model as ``ShapeDtypeStruct``s:
        ``embed`` (the head is tied to it), the final LayerNorm, and
        ``segments``: for each run ``(first, period, repeats)`` of
        ``self.segments`` a list of ``period`` dicts, each holding what its
        layer's mixer kind has with a leading axis of ``repeats`` (entry i
        of dict j is layer ``first + i * period + j``;
        :meth:`layer_params` picks one, :meth:`stack_layers` builds them).
        Matrices and their biases in ``param_dtype``; norm scales, a
        state-space layer's ``A_log``, ``D_skip``, ``b_dt`` / ``dt_bias`` and
        the differential layers' lambda vectors and pair-norm scale in
        float32. A "moe" layer's routed experts are stacked ``(repeats,
        count, ...)``: the ``count`` of ``experts_held`` alone, beside the
        router over all ``n_experts``."""
        c = self.cfg
        D, F, H, Hkv, d = (c.d_model, c.d_ff, c.n_heads, c.n_kv_heads,
                           c.head_dim)
        di, N, K, R = c.d_inner, c.d_state, c.d_conv, c.dt_rank
        w, f = jnp.dtype(c.param_dtype), jnp.dtype(jnp.float32)
        norms = {"ln1": ((D,), f), "ln2": ((D,), f)}
        if c.norm_kind == "layernorm":
            norms.update(ln1_b=((D,), f), ln2_b=((D,), f))
        held, Fe, Fs = c.experts_held[1], c.d_expert, c.d_shared
        ffn = {"mlp": {"w_gate_up": ((D, 2 * F), w), "w_down": ((F, D), w)},
               "moe": {"router": ((D, c.n_experts), w),
                       "we1": ((held, D, 2 * Fe), w),
                       "we2": ((held, Fe, D), w)}}
        if Fs:
            ffn["moe"].update(ws1=((D, 2 * Fs), w), ws2=((Fs, D), w))
        attn = {"wo": ((H * d, D), w), "bo": ((D,), w), "lam": ((4, d), f),
                "subln": ((2 * d,), f)}
        own = {
            "mamba": {"w_in": ((D, 2 * di), w), "conv_w": ((K, di), w),
                      "conv_b": ((di,), w), "w_x": ((di, R + 2 * N), w),
                      "w_dt": ((R, di), w), "b_dt": ((di,), f),
                      "A_log": ((N, di), f), "D_skip": ((di,), f),
                      "w_out": ((di, D), w)},
            "gmu": {"w1": ((D, di), w), "w2": ((di, D), w)},
            "cross": dict(attn, wq=((D, H * d), w), bq=((H * d,), w)),
            "mamba2": {"w_in": ((D, 2 * di + 2 * N + c.ssm_heads), w),
                       "conv_w": ((K, di + 2 * N), w),
                       "conv_b": ((di + 2 * N,), w),
                       "dt_bias": ((c.ssm_heads,), f),
                       "A_log": ((c.ssm_heads,), f),
                       "D_skip": ((c.ssm_heads,), f), "gnorm": ((di,), f),
                       "w_out": ((di, D), w)},
            "gqa": {"wqkv": ((D, (H + 2 * Hkv) * d), w),
                    "wo": ((H * d, D), w)},
        }
        own["window"] = own["full"] = dict(
            attn, wqkv=((D, (H + 2 * Hkv) * d), w),
            bqkv=(((H + 2 * Hkv) * d,), w))
        tree = {"embed": ((c.vocab, D), w), "final_ln": ((D,), f),
                "segments": [
                    [{n: ((reps,) + shape, dt) for n, (shape, dt) in
                      dict(norms, **ffn[self.ffn[first + j]],
                           **own[self.kinds[first + j]]).items()}
                     for j in range(period)]
                    for first, period, reps in self.segments]}
        if c.norm_kind == "layernorm":
            tree["final_ln_b"] = ((D,), f)
        return jax.tree.map(lambda sd: jax.ShapeDtypeStruct(*sd), tree,
                            is_leaf=lambda sd: isinstance(sd, tuple))

    def param_specs(self) -> Dict[str, Any]:
        c, Ls = self.cfg, self.layers_per_stage
        if c.pattern:       # dp-only grids: every leaf whole on every device
            return jax.tree.map(lambda _: P(), self.pattern_param_shapes())
        stages = {
            "ln1": P("pp", None, None),
            # (pp, Ls, D, 3, H, Dh): heads sharded over tp
            "wqkv": P("pp", None, None, None, "tp", None),
            # (pp, Ls, H, Dh, D): row-parallel output projection
            "wproj": P("pp", None, "tp", None, None),
            "ln2": P("pp", None, None),
        }
        if c.moe_experts:
            stages.update({
                "router": P("pp", None, None, None),
                # experts over dp AND the expert hidden dim over tp, so the
                # expert FLOPs split over tp like the dense branch (psum in
                # _block) instead of replicating the full FFN per tp rank
                "w_up": P("pp", None, "dp", None, "tp"),    # (pp, Ls, E, D, F)
                "w_down": P("pp", None, "dp", "tp", None),  # (pp, Ls, E, F, D)
            })
        else:
            stages.update({
                "w_up": P("pp", None, None, "tp"),          # (pp, Ls, D, F)
                "w_down": P("pp", None, "tp", None),        # (pp, Ls, F, D)
            })
        return {
            "embed": P(None, None),
            "final_ln": P(None),
            "unembed": P(None, None),
            "stages": stages,
        }

    # the dense QKV weights as a decode engine holds them: (pp, Ls, 3, H, D,
    # Dh), each head's (D, Dh) matrix contiguous. Out of `wqkv`'s (D, 3, H,
    # Dh) the product re-lays the whole stack to this every time a program
    # runs (a parameter's layout is fixed at the program's edge)
    HELD_QKV = "wqkv_ohdk"

    @property
    def _holds_a_copy(self) -> bool:
        """A decode engine of this model holds a tree of its own making
        (the dense model under a ``compute_dtype`` other than float32)."""
        return not self.cfg.pattern and (
            jnp.dtype(self.cfg.compute_dtype) != jnp.float32)

    def serving_param_specs(self) -> Dict[str, Any]:
        """:meth:`param_specs` of the tree :meth:`serving_params` returns."""
        specs = self.param_specs()
        if not self._holds_a_copy:
            return specs
        stages = dict(specs["stages"])
        del stages["wqkv"]
        stages[self.HELD_QKV] = P("pp", None, None, "tp", None, None)
        return dict(specs, stages=stages)

    def serving_params(self, params) -> Dict[str, Any]:
        """``params`` as a decode engine HOLDS them: what its step reads, so
        that no program of the engine casts or re-lays a weight. For a
        pattern that is its matrices in ``param_dtype`` beside float32 norm
        scales and state-space vectors (:meth:`pattern_param_shapes`),
        sharded as they came. For the dense model it is every floating leaf
        in ``compute_dtype`` (what :meth:`_cast_params` would make of it in
        every step) with ``wqkv`` as ``HELD_QKV``, placed by
        :meth:`serving_param_specs`; under a float32 ``compute_dtype`` the
        tree itself, whatever it holds. A tree that is held already comes
        back as it is; the dense model's copy is made by ONE jitted program.
        The caller keeps the masters."""
        if self.cfg.pattern:
            want = jax.tree.map(lambda sd: sd.dtype,
                                self.pattern_param_shapes())
            if all(a.dtype == d for a, d in zip(jax.tree.leaves(params),
                                                jax.tree.leaves(want))):
                return params
            return jax.tree.map(lambda a, d: a.astype(d), params, want)
        if not self._holds_a_copy or self.HELD_QKV in params["stages"]:
            return params

        def held(tree):
            tree = self._cast_params(tree)
            stages = dict(tree["stages"])
            stages[self.HELD_QKV] = jnp.moveaxis(stages.pop("wqkv"), 2, 4)
            return dict(tree, stages=stages)

        return jax.jit(held, out_shardings=jax.tree.map(
            lambda s: NamedSharding(self.grid.mesh, s),
            self.serving_param_specs(),
            is_leaf=lambda s: isinstance(s, P)))(params)

    def stack_layers(self, layer_of) -> list:
        """The ``segments`` of a pattern's parameter tree from
        ``layer_of(l)``, layer ``l``'s own dict of arrays. One place of one
        run's period at a time, so that no more than ``repeats`` layers
        exist twice while a model that fills the chip is built."""
        # one program a place, not one a leaf op by op
        stack = jax.jit(lambda layers: jax.tree.map(
            lambda *a: jnp.stack(a), *layers))
        return [[stack([layer_of(first + i * period + j)
                        for i in range(reps)])
                 for j in range(period)]
                for first, period, reps in self.segments]

    def layer_params(self, params, l: int) -> Dict[str, Any]:
        """Layer ``l``'s own parameters out of a pattern's ``segments``."""
        for s, (first, period, reps) in enumerate(self.segments):
            if l < first + period * reps:
                i, j = divmod(l - first, period)
                return jax.tree.map(lambda a: a[i], params["segments"][s][j])
        raise IndexError(l)

    def shard_params(self, params) -> Dict[str, Any]:
        """Place a (host or differently-placed) parameter tree onto this
        grid's shardings — e.g. after ``load_checkpoint``, whose restored
        leaves are host arrays."""
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.grid.mesh, s), self.param_specs(),
            is_leaf=lambda s: isinstance(s, P))
        # device_put handles the pytree-of-shardings form natively and
        # batches the transfers (one placement, not one per leaf)
        return jax.device_put(params, shardings)

    def _init_pattern(self, seed: int) -> Dict[str, Any]:
        """Matrices N(0, init_scale) rounded ONCE to ``param_dtype``; norm
        scales 1, biases 0; Mamba's own init for what a normal draw would
        make unstable: ``A_log`` = log(1..N) a channel, ``D_skip`` 1,
        ``b_dt`` the inverse softplus of a log-uniform step in [1e-3, 1e-1],
        the convolution's filter uniform within 1/sqrt(K); the lambda
        vectors N(0, 0.1). Mamba-2 (a scalar a head): ``A`` uniform in
        [1, 16], ``dt_bias`` as ``b_dt``."""
        c = self.cfg
        rng = np.random.default_rng(seed)

        def leaf(path, sd):
            name = path[-1].key
            if name in ("ln1", "ln2", "final_ln", "subln", "D_skip",
                        "gnorm"):
                a = np.ones(sd.shape)
            elif name.endswith("_b") or name in ("bo", "bq", "bqkv"):
                a = np.zeros(sd.shape)
            elif name == "A_log" and len(sd.shape) == 2:    # (repeats, heads)
                a = np.log(rng.uniform(1.0, 16.0, sd.shape))
            elif name == "A_log":
                a = np.broadcast_to(
                    np.log(np.arange(1, c.d_state + 1))[:, None], sd.shape)
            elif name in ("b_dt", "dt_bias"):
                dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), sd.shape))
                a = dt + np.log(-np.expm1(-dt))
            elif name == "conv_w":
                a = rng.uniform(-1, 1, sd.shape) / math.sqrt(c.d_conv)
            elif name == "lam":
                a = 0.1 * rng.standard_normal(sd.shape)
            else:
                a = c.init_scale * rng.standard_normal(sd.shape)
            return jnp.asarray(a, jnp.float32).astype(sd.dtype)

        return self.shard_params(jax.tree_util.tree_map_with_path(
            leaf, self.pattern_param_shapes()))

    def init(self, seed: int = 0) -> Dict[str, Any]:
        c, Ls, pp = self.cfg, self.layers_per_stage, self.pp
        if c.pattern:
            return self._init_pattern(seed)
        H, Dh, D, F, V = c.n_heads, c.head_dim, c.d_model, c.d_ff, c.vocab
        rng = np.random.default_rng(seed)
        s = c.init_scale

        def norm(*shape):
            return (s * rng.standard_normal(shape)).astype(np.float32)

        stages = {
            "ln1": np.ones((pp, Ls, D), np.float32),
            "wqkv": norm(pp, Ls, D, 3, H, Dh),
            "wproj": norm(pp, Ls, H, Dh, D),
            "ln2": np.ones((pp, Ls, D), np.float32),
        }
        if c.moe_experts:
            E = c.moe_experts
            stages["router"] = norm(pp, Ls, D, E)
            stages["w_up"] = norm(pp, Ls, E, D, F)
            stages["w_down"] = norm(pp, Ls, E, F, D)
        else:
            stages["w_up"] = norm(pp, Ls, D, F)
            stages["w_down"] = norm(pp, Ls, F, D)
        host = {
            "embed": norm(V, D),
            "final_ln": np.ones((D,), np.float32),
            "unembed": norm(D, V),
            "stages": stages,
        }
        return self.shard_params(host)

    # ------------------------------------------------------------- #
    # the per-device program                                        #
    # ------------------------------------------------------------- #

    def _block(self, p, x, sp_comm, pos):
        """One transformer layer on a local microbatch (mb, S_local, D).
        ``pos``: global positions of this device's S_local tokens (layout-
        aware, computed once per forward in ``_loss_device``)."""
        c = self.cfg
        Hs = c.n_heads // self.tp
        mb, S_local, D = x.shape

        p = self._cast_params(p)
        q, k, v = self._qkv(p, x, pos)
        scale = 1.0 / math.sqrt(c.head_dim)
        with scope("attn.core"):
            if c.attn_schedule == "zigzag" and sp_comm.size > 1:
                # load-balanced causal ring: every sp device does identical
                # live work per step. The token stream is ALREADY in zigzag
                # layout — _loss_device relayouts once after embedding and
                # inverts once before the loss, so each layer pays zero
                # layout ppermutes (every non-attention op in the block is
                # positionwise)
                attn = _zigzag_core(q, k, v, comm=sp_comm, scale=scale)
            elif c.attn_schedule == "ulysses" and sp_comm.size > 1:
                # all_to_all head-parallel: two collectives per layer
                # instead of sp-1 ppermute steps — often wins at moderate S
                # on fast ICI
                attn = _ulysses_core(q, k, v, comm=sp_comm, scale=scale,
                                     causal=True)
            else:
                attn = _ring_body(q, k, v, comm=sp_comm, scale=scale,
                                  causal=True)
        x = self._attn_residual(p, x, attn)

        if c.moe_experts:
            with scope("moe"):
                flat = _rmsnorm(x, p["ln2"]).reshape(mb * S_local, D)
                # expert hidden dim is tp-sharded: partial down-projections
                # sum over tp (one psum, mirroring the dense Megatron block)
                moe_out = self._psum_tp(
                    switch_moe(
                        flat, p["router"], p["w_up"], p["w_down"], axis="dp",
                        capacity_factor=c.capacity_factor))
                return x + moe_out.reshape(mb, S_local, D)
        return self._dense_mlp_residual(p, x)

    # shared layer math — _block (training), the prefill pass and the
    # cached decode step (generate) all call these, so an architecture
    # change lands everywhere at once

    @scope("cast")
    def _stage_params(self, params):
        """This device's stage out of the pp-stacked parameters (inside
        shard_map the leading axis is 1). Under ``cast``: XLA fuses it with
        the per-layer slice and cast that follow."""
        return jax.tree.map(lambda a: a[0], params["stages"])

    @scope("cast")
    def _cast_params(self, p, layer=None):
        """Mixed precision: master params stay f32 in the optimizer; compute
        runs in compute_dtype (bf16 on real TPUs for MXU rate). Without this
        cast f32 params silently promote every activation back to f32 and
        compute_dtype never takes effect. ``layer``: ``p`` is a stage's
        stacked parameters, take that layer's out of the stack first (the
        slice and the cast are one pass over the weights, under one scope)."""
        c = self.cfg
        if layer is not None:
            p = jax.tree.map(lambda a: a[layer], p)
        dt = jnp.dtype(c.compute_dtype)
        if dt == jnp.float32:
            return p
        # a leaf that is held in `dt` already (a decode engine's tree,
        # `serving_params`) is read as it lies
        return jax.tree.map(
            lambda a: a.astype(dt) if a.dtype != dt
            and jnp.issubdtype(a.dtype, jnp.floating) else a, p)

    @scope("attn.qkv")
    def _qkv(self, p, x, pos):
        """Pre-norm qkv projection for the local head subset, with rotary
        rotation by the GLOBAL positions ``pos``. The weights as ``init``
        makes them, ``wqkv`` (D, 3, H, Dh), or as a decode engine holds
        them (:meth:`serving_params`), ``HELD_QKV`` (3, H, D, Dh)."""
        c = self.cfg
        a_in = _rmsnorm(x, p["ln1"])
        if "wqkv" in p:
            qkv = jnp.einsum("bsd,dohk->bsohk", a_in, p["wqkv"])
        else:
            qkv = jnp.einsum("bsd,ohdk->bsohk", a_in, p[self.HELD_QKV])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if c.rope:
            q = rope_apply(q, pos, c.rope_theta)
            k = rope_apply(k, pos, c.rope_theta)
        return q, k, v

    def _psum_tp(self, x, wire=None):
        """The Megatron-block tp reduction. On tp=1 grids the psum is a
        value identity (a singleton-group all-reduce) and stays:
        ``check_vma=True`` needs it to clear the tp-varying type, as in
        ``pipeline_apply``'s pp==1 branch.

        ``wire``: a ``(quant_key, chunk_key, hier_key)`` triple pinned by
        a builder that cache-keyed on it (the serving decode engine) —
        the psum then rides :func:`heat_tpu.core.fusion.packed_psum` so
        the opt-in wire codecs apply; the exact-codec emission is
        bitwise the plain ``lax.psum`` (PR 4 probe). Wire bodies are
        always ``check_vma=False``, so tp=1 emits nothing."""
        if wire is not None:
            if self.tp <= 1:
                return x
            from ..core import fusion

            qk, ck, hk = wire
            return fusion.packed_psum([x], ("tp",), quant=qk, chunks=ck,
                                      hier=hk)[0]
        return lax.psum(x, "tp")

    @scope("attn.proj")
    def _attn_residual(self, p, x, attn, wire=None):
        """Row-parallel output projection (one tp psum) + residual."""
        return x + self._psum_tp(
            jnp.einsum("bshk,hkd->bsd", attn, p["wproj"]), wire=wire)

    @scope("mlp")
    def _dense_mlp_residual(self, p, x, wire=None):
        """Pre-norm dense MLP (one tp psum) + residual."""
        h = jax.nn.gelu(_rmsnorm(x, p["ln2"]) @ p["w_up"])
        return x + self._psum_tp(h @ p["w_down"], wire=wire)

    @scope("head")
    def _head(self, params, h):
        """Final norm + unembed; logits upcast to f32 only after the GEMM —
        an f32 norm scale would push the largest matmul off the bf16 path."""
        c = self.cfg
        if c.pattern:       # the head tied to the embedding
            h = self._pre_norm(params, "final_ln", h)
            logits = jnp.einsum("bsd,vd->bsv", h,
                                params["embed"].astype(c.compute_dtype),
                                preferred_element_type=jnp.float32)
            if c.logits_scaling != 1.0:
                logits = logits / c.logits_scaling
            return logits
        h = _rmsnorm(h, params["final_ln"].astype(c.compute_dtype))
        return (h @ params["unembed"].astype(c.compute_dtype)).astype(jnp.float32)

    def _forward_device(self, params, toks):
        """Per-device forward: toks (B_local, S_local) -> f32 logits
        (B_local, S_local, vocab). Shared by the training loss and the
        serving forward (:meth:`logits_fn`)."""
        c = self.cfg
        sp_comm = self.grid.axis("sp")
        B_local, S_local = toks.shape
        if B_local % c.n_micro:
            raise ValueError(
                f"local batch ({B_local}) must divide into n_micro ({c.n_micro})")
        mb = B_local // c.n_micro

        with scope("embed"):
            x = params["embed"][toks].astype(c.compute_dtype)
        zigzag = c.attn_schedule == "zigzag" and sp_comm.size > 1
        sp_idx = lax.axis_index("sp")
        if zigzag:
            # one layout round-trip per forward: into zigzag here, back to
            # contiguous before the loss — the layers in between are either
            # positionwise (layout-agnostic) or zigzag-aware (_zigzag_core)
            x = zigzag_layout(x, sp_comm)
            # global positions of the zigzag-resident tokens: chunk sp_idx
            # and chunk 2n-1-sp_idx
            half = S_local // 2
            n_sp = sp_comm.size
            pos = jnp.concatenate([
                sp_idx * half + jnp.arange(half),
                (2 * n_sp - 1 - sp_idx) * half + jnp.arange(half),
            ])
        else:
            pos = sp_idx * S_local + jnp.arange(S_local)
        x_micro = x.reshape(c.n_micro, mb, S_local, c.d_model)

        stage_params = self._stage_params(params)

        def block(p_l, xm):
            return self._block(p_l, xm, sp_comm, pos)

        if c.remat:
            # rematerialise each layer on the backward pass: activation HBM
            # drops from O(n_layers) to O(1) blocks per stage at the cost of
            # one extra forward — the standard deep-model memory trade
            # (jax.checkpoint per the TPU HBM playbook)
            # prevent_cse=False: every call site is inside a lax.scan (the
            # pipeline tick / microbatch scan), where the CSE barriers the
            # default inserts are documented as unnecessary overhead
            block = jax.checkpoint(block, prevent_cse=False)

        def stage_fn(sp_params, xm):
            for l in range(self.layers_per_stage):
                with scope("cast"):
                    p_l = jax.tree.map(lambda a: a[l], sp_params)
                xm = block(p_l, xm)
            return xm

        # what the schedule itself costs (the scan's stacked residuals and
        # gradient accumulators, the pp psum) reads under `pipeline`; the
        # layers inside keep their own, inner scopes
        with scope("pipeline"):
            out = pipeline_apply(stage_fn, stage_params, x_micro, axis="pp")
        h = out.reshape(B_local, S_local, c.d_model)
        if zigzag:
            h = zigzag_unlayout(h, sp_comm)
        return self._head(params, h)

    def _local_loss_device(self, params, toks):
        """Per-device code: toks (B_local, S_local) -> this device's SHARE
        of the global loss (local masked NLL sum over the static global
        count). ``psum(local, ("dp", "sp")) == global loss`` — the
        :meth:`_loss_device` form the check_vma path compiles — and
        because the share is collective-free past the forward, the packed
        train step can differentiate it per device and combine every
        parameter cotangent in ONE flattened all-reduce
        (:func:`heat_tpu.core.fusion.packed_psum`)."""
        B_local, S_local = toks.shape
        logits = self._forward_device(params, toks)

        # next-token targets across the sharded sequence: local shift plus
        # the neighbour shard's first token via ppermute (the halo pattern,
        # reference dndarray.py:360-433)
        sp, sp_axis = self.sp, "sp"
        first = toks[:, :1]
        if sp > 1:
            nxt = lax.ppermute(
                first, sp_axis, [(i, (i - 1) % sp) for i in range(sp)])
        else:
            nxt = first
        targets = jnp.concatenate([toks[:, 1:], nxt], axis=1)
        with scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
            # the global last position has no next token
            is_last_shard = lax.axis_index(sp_axis) == sp - 1
            pos_mask = jnp.arange(S_local) < S_local - 1
            mask = jnp.where(is_last_shard, pos_mask, jnp.ones_like(pos_mask))
            mask = jnp.broadcast_to(mask[None, :], nll.shape).astype(nll.dtype)

            # the count is static — B_global rows each lose one position —
            # which also keeps it out of the vma system (a mask-sum would be
            # invarying over dp and unreducible there)
            count = B_local * self.dp_world * (S_local * sp - 1)
            return jnp.sum(nll * mask) / count

    def _data_axes(self):
        """The data axes (the loss psum scope): dp and sp, plus the dcn
        tier axis when the grid declares one."""
        return (("dcn", "dp", "sp") if self._has_dcn else ("dp", "sp"))

    def _loss_device(self, params, toks):
        """Per-device code: toks (B_local, S_local) -> replicated global loss."""
        return lax.psum(self._local_loss_device(params, toks),
                        self._data_axes())

    # ------------------------------------------------------------- #
    # jitted steps                                                  #
    # ------------------------------------------------------------- #

    def _data_spec(self):
        if self._has_dcn:
            # batch rows shard over BOTH data-parallel tiers (dcn-major,
            # like jax.devices() orders a real pod's hosts)
            return P(("dcn", "dp"), "sp")
        return P("dp", "sp")

    def shard_batch(self, toks: np.ndarray) -> jax.Array:
        """Place a (B, S) int32 token batch dp x sp sharded on the grid."""
        return jax.device_put(
            jnp.asarray(toks, jnp.int32),
            NamedSharding(self.grid.mesh, self._data_spec()))

    @property
    def packed_step_supported(self) -> bool:
        """Whether the packed-collective train step applies to this grid:
        pp == tp == 1 and a dense MLP. Those are exactly the layouts
        whose forward has no collective the ``check_vma=False`` AD
        transpose mishandles — ppermute/all_to_all (the sp attention
        schedules) transpose exactly without replication typing, while a
        forward tp psum or the pipeline's masked psum broadcast needs vma
        tracking for factor-free cotangents of replicated parameters."""
        return self.pp == 1 and self.tp == 1 and not self.cfg.moe_experts

    def _batch_axes(self):
        """Non-trivial data axes — the reduction scope of the packed
        gradient all-reduce (empty on a 1-device grid: no collective).
        The dcn tier axis leads: packed_psum's tier split sees it as the
        slow tier and dp/sp as the fast one."""
        return tuple(a for a, n in (("dcn", self.dcn), ("dp", self.dp),
                                    ("sp", self.sp))
                     if n > 1)

    def _packed_loss_and_grad_body(self, qinfo=None, quant=None,
                                   chunks=None, hier=None):
        """Per-device (params, toks) -> (loss, grads) with every gradient
        cotangent — and the loss — combined in ONE flattened all-reduce:
        local value_and_grad of the device's loss share, then
        :func:`heat_tpu.core.fusion.packed_psum` over the data axes (the
        generalized-allreduce packing, arXiv:2004.09362), instead of the
        one-psum-per-parameter GSPMD emits for the transposed broadcast.
        Under ``HEAT_TPU_QUANT_COLLECTIVES`` the qualifying gradient
        payloads ride the quantized exchange (the scalar loss is below
        the size floor and stays exact); ``qinfo`` collects the rewrite
        counts at trace time for the step wrapper's counters; ``quant``
        and ``chunks`` pin the configurations the builder cache-keyed on
        (jax traces at first dispatch — a codec or chunk-count toggle in
        between must not change the traced wire format or leg structure
        out from under the key)."""
        from ..core import fusion

        axes = self._batch_axes()

        def loss_and_grad(params, toks):
            if qinfo is not None:
                fusion.reset_qinfo(qinfo)
            lval, grads = jax.value_and_grad(
                self._local_loss_device)(params, toks)
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            with scope("grad_psum"):
                packed = fusion.packed_psum(leaves + [lval], axes,
                                            qinfo=qinfo, quant=quant,
                                            chunks=chunks, hier=hier)
            return packed[-1], jax.tree_util.tree_unflatten(
                treedef, packed[:-1])

        return loss_and_grad

    def loss_and_grad_fn(self):
        """jitted (params, toks) -> (loss, grads) over the full grid.

        On grids the packed step supports (and with
        ``HEAT_TPU_FUSION_STEP`` on) the gradient collectives are packed
        into one flattened all-reduce under ``check_vma=False``; other
        grids keep the check_vma path (vma tracking makes every
        collective transpose exact for pipeline/tensor parallelism)."""
        from ..core import fusion

        self._needs_dense("loss_and_grad_fn")
        packed = self.packed_step_supported and fusion.step_enabled()
        # the quant codec changes the packed program's collective wire
        # format, the chunk count its leg structure and the hier config
        # its collective decomposition, so all three key the cache —
        # toggling compiles a sibling program instead of poisoning the
        # exact/unchunked/flat one (the legacy key stays 2-tuple: the
        # check_vma path never quantizes, chunks or decomposes)
        qk = fusion.quant_key()
        ck = fusion.chunk_key()
        hk = fusion.hier_key()
        key = ("loss_and_grad", True, qk, ck, hk) if packed \
            else ("loss_and_grad", False)
        fn = self._step_cache.get(key)
        if fn is None:
            specs = self.param_specs()
            if packed:
                qinfo = {}
                sm = shard_map(
                    self._packed_loss_and_grad_body(qinfo=qinfo, quant=qk,
                                                    chunks=ck, hier=hk),
                    mesh=self.grid.mesh,
                    in_specs=(specs, self._data_spec()),
                    out_specs=(P(), specs),
                    check_vma=False)
                jitted = jax.jit(sm)

                def fn(params, toks, _jitted=jitted, _qinfo=qinfo):
                    out = _jitted(params, toks)
                    # per-dispatch counters, like the step wrappers —
                    # runtime_stats must show quantization ran on THIS
                    # surface too (doc/fusion.md counter contract)
                    fusion.tick_quant(_qinfo)
                    return out

                fn.lower = jitted.lower
                self._step_cache[key] = fn
                return fn
            else:
                def loss_and_grad(params, toks):
                    return jax.value_and_grad(self._loss_device)(params, toks)

                # check_vma=True: replication (varying-across-mesh-axes)
                # types are tracked, so collective transposes are exact —
                # gradients of replicated parameters are psum'd across
                # exactly the axes they are replicated over, with no
                # seed-count factors
                sm = shard_map(
                    loss_and_grad, mesh=self.grid.mesh,
                    in_specs=(specs, self._data_spec()),
                    out_specs=(P(), specs),
                    check_vma=True)
            fn = jax.jit(sm)
            self._step_cache[key] = fn
        return fn

    def logits_fn(self):
        """jitted ``(params, toks) -> (B, S, vocab) f32 logits`` over the
        full grid — the serving forward (``heat_tpu.serve.adapters``).

        Same per-device program as the training loss up to the head
        (:meth:`_forward_device`), compiled once and cached; runs with
        ``check_vma=False`` (inference needs no replication-type tracking,
        and the forward then traces on every supported jax)."""
        self._needs_dense("logits_fn")
        key = "logits"
        fn = self._step_cache.get(key)
        if fn is None:
            def logits(params, toks):
                return self._forward_device(params, toks)

            sm = shard_map(
                logits, mesh=self.grid.mesh,
                in_specs=(self.param_specs(), self._data_spec()),
                out_specs=P("dp", "sp", None),
                check_vma=False)
            fn = jax.jit(sm)
            self._step_cache[key] = fn
        return fn

    def make_train_step(self, tx):
        """jitted (params, opt_state, toks) -> (params, opt_state, loss)
        with an optax transform ``tx``, parameter/optimizer state donated.

        On grids :attr:`packed_step_supported` covers (and with
        ``HEAT_TPU_FUSION_STEP`` on) the WHOLE step — forward, backward,
        packed gradient all-reduce, optimizer update — is one
        ``shard_map`` program: the collective count is the packed plan's
        (one flattened all-reduce over the data axes carrying every
        parameter cotangent plus the loss), not one-per-parameter, and
        repeat calls are a single donated program dispatch with zero host
        round-trips. Other grids compose the check_vma loss-and-grad
        program with a GSPMD optimizer update under one outer jit (the
        historic path)."""
        import optax

        from ..core import fusion

        self._needs_dense("make_train_step")
        if self.packed_step_supported and fusion.step_enabled():
            specs = self.param_specs()
            qinfo = {}
            lg_body = self._packed_loss_and_grad_body(
                qinfo=qinfo, quant=fusion.quant_key(),
                chunks=fusion.chunk_key(), hier=fusion.hier_key())

            def train_step(params, opt_state, toks):
                loss, grads = lg_body(params, toks)
                with scope("optimizer"):
                    updates, opt_state = tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, loss

            # opt_state rides as a replicated pytree (P() spec prefix):
            # the update math is identical on every device, like params
            sm = shard_map(
                train_step, mesh=self.grid.mesh,
                in_specs=(specs, P(), self._data_spec()),
                out_specs=(specs, P(), P()),
                check_vma=False)
            jitted = jax.jit(sm, donate_argnums=(0, 1))
            replicated = NamedSharding(self.grid.mesh, P())

            def step(params, opt_state, toks):
                # ``tx.init(params)`` makes its scalars (adam's count) off
                # the mesh and its moments laid out like the params; this
                # program emits the state replicated ON the mesh. jax >= 0.9
                # types arrays by their mesh, so without this placement
                # step 2 re-traces and compiles the whole step a second
                # time. Already-placed leaves pass through untouched.
                with span("train_step"):
                    with span("train_step.place"):
                        opt_state = jax.device_put(opt_state, replicated)
                    with span("train_step.dispatch"):
                        out = jitted(params, opt_state, toks)
                    # the model-level fused step counts like a traced step
                    # (DataParallel's packed path does the same), so the
                    # ladder's per-test fusion_step_flushes line shows the
                    # packed path actually ran
                    from ..utils import metrics

                    metrics.inc("op_engine.fusion_step_flushes")
                    fusion.tick_quant(qinfo)
                    return out

            # the audit/steady-state surface of the underlying program
            step.lower = jitted.lower
            if hasattr(jitted, "_cache_size"):
                step._cache_size = jitted._cache_size
            return step

        lg = self.loss_and_grad_fn()

        # donate params/opt_state: both are consumed and re-emitted every
        # step, so XLA updates them in place — halves their HBM footprint
        # (matches nn/data_parallel.py's train step)
        @partial(jax.jit, donate_argnums=(0, 1))
        def train_step(params, opt_state, toks):
            loss, grads = lg(params, toks)
            with scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        def step(params, opt_state, toks):
            with span("train_step"):
                with span("train_step.dispatch"):
                    return train_step(params, opt_state, toks)

        step.lower = train_step.lower
        if hasattr(train_step, "_cache_size"):
            step._cache_size = train_step._cache_size
        return step

    # ------------------------------------------------------------- #
    # generation (KV-cached autoregressive decode)                  #
    # ------------------------------------------------------------- #
    # what a layer does with one token and what it keeps between tokens is
    # said ONCE, in `prefill`, `decode_step_logits`, `cache_layout` and
    # `cache_store` below: generate()'s batch program and the serving
    # engine (heat_tpu.serve.decode.DecodeEngine) are both clients of those
    # four, so an architecture change lands in every decoder at once

    PROMPT_BUCKET_MIN = 8

    @classmethod
    def prompt_bucket(cls, s0: int) -> int:
        """The prompt-length bucket: smallest power of two >= ``s0``
        (floored at :data:`PROMPT_BUCKET_MIN`) — the Pow2Buckets ladder
        applied to sequence length. Prompts pad onto the bucket so one
        compiled program serves every prompt length in it; the padded
        rows' K/V stay masked (``col < n_valid``) until overwritten."""
        s0 = int(s0)
        if s0 < 1:
            raise ValueError(f"prompt length must be >= 1, got {s0}")
        return max(cls.PROMPT_BUCKET_MIN, 1 << (s0 - 1).bit_length())

    def serving_bucket(self, s0: int) -> int:
        """The rung a served prompt of ``s0`` tokens pads onto:
        :meth:`prompt_bucket`, and for a pattern with window layers no rung
        under the window's. NOT for correctness: a prompt shorter than the
        window fills its ring rows from a program of any length
        (``mixers.ring_rows``; ``tests/test_pattern_lm.py`` runs prompts
        unpadded under the window). It is the engine's count of programs:
        every rung is one more prefill program of every layer to compile and
        hold (20 s each at 2,560 wide on a v5e's compiler, whatever the
        rung: ``PERF.md`` section 6, PR 33), and a prompt under the window
        is the cheapest to pad. It overrides ``PROMPT_BUCKET_MIN`` upward
        for such a model, whatever a caller set it to."""
        rung = self.prompt_bucket(s0)
        if "window" in self.kinds:
            rung = max(rung, self.prompt_bucket(self.cfg.window))
        return rung

    def check_decode_grid(self) -> None:
        """Decode is token-recurrent: a pipelined or sequence-sharded
        layout would idle on the single live token, and Switch-MoE's
        capacity routing at S=1 degenerates (a pattern's "moe" layers have
        no capacity and are served). Shared guard for generate() and
        DecodeEngine."""
        if self.pp != 1 or self.sp != 1:
            raise ValueError(
                "generate requires a pp=1, sp=1 grid (token-recurrent "
                "decode); use dp x tp for inference")
        if self.cfg.moe_experts:
            raise NotImplementedError(
                "decode supports a dense MLP (GELU, or gated SiLU under a "
                "pattern), not Switch-MoE routing")

    @scope("attn.core")
    def _attn_from_cache(self, q, ck, cv, upto):
        """q (Bl, 1, Hs, Dh) against cached rows < ``upto`` (a scalar, or
        a (Bl,) vector when every row is at its own decode depth — the
        serving engine's per-slot live positions)."""
        s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) / math.sqrt(self.cfg.head_dim)
        col = jnp.arange(ck.shape[1])[None, None, None, :]
        lim = upto if jnp.ndim(upto) == 0 else upto[:, None, None, None]
        s = jnp.where(col < lim, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", w, cv.astype(jnp.float32))
        return out.astype(q.dtype)

    # ------------------------------------------------------------- #
    # serving: ONE layer function over (mixer kind, cache view)     #
    # ------------------------------------------------------------- #
    # `prefill` and `decode_step_logits` are what the decode engine
    # compiles. Each walks the layers once and hands every layer to the
    # function of its kind; the dense model is the one-kind case ("attn":
    # fused-QKV causal attention with rotary, a GELU MLP, RMSNorm). What a
    # layer keeps between tokens is the model's to say (`cache_layout`):
    # the engine holds that tree, donates it, and never looks inside.

    # a "moe" layer's routed experts: read by grouped products, which take a
    # run's whole stack and the layer's place in it (`routed_experts`)
    EXPERT_STACKS = ("we1", "we2")

    def _layer_picker(self, params):
        """``pick(l)``: (layer ``l``'s parameters in the dtype it computes
        in, its place in its run's stack). Out of a pattern's stacked
        ``segments`` a leaf is a static slice, which a product reads in
        place; the routed experts stay STACKED, ``(repeats, count, ...)``,
        for the grouped products to read in place too."""
        if not self.cfg.pattern:
            stage = self._stage_params(params)
            return lambda l: (self._cast_params(stage, l), None)

        def pick(l):
            for s, (first, period, reps) in enumerate(self.segments):
                if l < first + period * reps:
                    i, j = divmod(l - first, period)
                    return {n: a if n in self.EXPERT_STACKS else a[i]
                            for n, a in params["segments"][s][j].items()}, i
            raise IndexError(l)

        return pick

    def _pre_norm(self, p, name, x):
        if self.cfg.norm_kind == "rmsnorm":
            return mixers.rmsnorm(x, p[name], self.cfg.norm_eps)
        return mixers.layernorm(x, p[name], p[name + "_b"], self.cfg.norm_eps)

    def _embed(self, params, toks):
        c = self.cfg
        with scope("embed"):
            x = params["embed"][toks].astype(c.compute_dtype)
            if c.pattern and c.embedding_multiplier != 1.0:
                x = x * jnp.asarray(c.embedding_multiplier, x.dtype)
            return x

    def _residual(self, x, y):
        """x + m y, m the residual multiplier (1: no product at all)."""
        m = self.cfg.residual_multiplier
        return x + y if m == 1.0 else x + jnp.asarray(m, y.dtype) * y

    @scope("mlp")
    def _gated_mlp_residual(self, p, x):
        """Pre-norm gated SiLU MLP + residual: (silu(g) * u) W_down,
        [g, u] = LN(x) W_gate_up."""
        F = p["w_down"].shape[0]
        gu = mixers.mm(self._pre_norm(p, "ln2", x), p["w_gate_up"])
        h = (jax.nn.silu(gu[..., :F].astype(jnp.float32))
             * gu[..., F:].astype(jnp.float32)).astype(x.dtype)
        return self._residual(x, mixers.mm(h, p["w_down"]))

    @scope("moe")
    def _experts_residual(self, p, x, counted, at=None):
        """Pre-norm expert layer + residual: this device's part of the routed
        experts (:func:`routed_experts`: top-k of all, the held ones
        computed, no capacity) beside the shared expert, which every token
        passes. ``counted`` (B, S) bool: the tokens whose pairs are counted;
        ``at``: the layer's place in ``p``'s stacked experts (None: they are
        its own). Returns (x, pairs by held expert (count,) int32)."""
        c = self.cfg
        B, S, D = x.shape
        u = self._pre_norm(p, "ln2", x).reshape(B * S, D)
        out, pairs = routed_experts(
            u, p["router"], p["we1"], p["we2"], k=c.experts_per_token,
            held=c.experts_held, valid=counted.reshape(B * S), at=at)
        if c.d_shared:
            with scope("moe.shared"):
                out = out + gated_ffn(u, p["ws1"], p["ws2"])
        return self._residual(x, out.reshape(B, S, D)), pairs

    def _ffn_residual(self, ffn, p, x, carry, at=None):
        """A layer's feed-forward by its kind ``ffn``; a "moe" layer adds the
        pairs by held expert of the tokens ``carry["counted"]`` to
        ``carry["pairs"]`` (:meth:`_fresh_carry`)."""
        if ffn == "mlp":
            return self._gated_mlp_residual(p, x), carry
        x, pairs = self._experts_residual(p, x, carry["counted"], at)
        return x, dict(carry, pairs=carry["pairs"] + pairs)

    def _gqa_qkv(self, p, u):
        """Query, key and value heads of ``u`` (B, S, D): one product, no
        bias, no positions."""
        c = self.cfg
        H, Hkv = c.n_heads, c.n_kv_heads
        with scope("attn.qkv"):
            qkv = lax.optimization_barrier(mixers.mm(u, p["wqkv"]))
            qkv = qkv.reshape(*u.shape[:2], -1, c.head_dim)
            return qkv[:, :, :H], qkv[:, :, H:H + Hkv], qkv[:, :, H + Hkv:]

    @scope("attn.proj")
    def _gqa_out(self, p, a):
        """The heads' outputs ``a`` (B, S, H, Dh) -> the layer's (B, S, D)."""
        return mixers.mm(a.reshape(*a.shape[:2], -1).astype(
            self.cfg.compute_dtype), p["wo"])

    def _diff_qkv(self, p, u, kind):
        """Query heads (and, but for a cross layer, this layer's own key and
        value heads) of ``u`` (B, S, D)."""
        c = self.cfg
        H, Hkv = c.n_heads, c.n_kv_heads
        with scope("attn.qkv"):
            w, b = ("wq", "bq") if kind == "cross" else ("wqkv", "bqkv")
            qkv = (jnp.dot(u, p[w].astype(u.dtype),
                           preferred_element_type=jnp.float32)
                   + p[b].astype(jnp.float32)).astype(u.dtype)
            # the product stays a plain matrix product that reads its
            # weights where they lie in the segment's stack: seen together
            # with the split into heads, XLA re-lays the weights a step
            qkv = lax.optimization_barrier(qkv)
            qkv = qkv.reshape(*u.shape[:2], -1, c.head_dim)
            if kind == "cross":
                return qkv, None, None
            return qkv[:, :, :H], qkv[:, :, H:H + Hkv], qkv[:, :, H + Hkv:]

    def _diff_out(self, p, a, l, dtype):
        """The maps' outputs ``a`` (B, S, H, 2 Dh) -> the layer's (B, S, D)."""
        o = mixers.diff_finish(a, p["lam"], p["subln"], l, self.cfg.norm_eps,
                               dtype)
        with scope("attn.proj"):
            return mixers.mm(o, p["wo"]) + p["bo"].astype(dtype)

    def _prompt_layer(self, kind, ffn, l, p_l, x, pos0, n_valid, carry,
                      wire, at=None):
        """Layer ``l`` (mixer ``kind``, feed-forward ``ffn``; the index may be
        traced: a scanned segment's) over a padded prompt ``x`` (Bl, Sp, D)
        whose rows >= ``n_valid`` are pad. Returns (x, what the cache keeps
        of it, carry); ``carry`` hands later layers the last state-space
        output (``m``) and the full layer's keys and values (``k``, ``v``),
        and sums the expert layers' routed pairs (``pairs``). ``at``: the
        layer's place in ``p_l``'s stacked experts (None: they are its own)."""
        c, dtype = self.cfg, self.cfg.compute_dtype
        if kind == "attn":
            q, k, v = self._qkv(p_l, x, pos0)
            kept = {"k": k.astype(dtype), "v": v.astype(dtype)}
            with scope("attn.core"):
                attn = jnp.moveaxis(local_attention(
                    jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                    jnp.moveaxis(v, 2, 1), causal=True), 1, 2)
            x = self._attn_residual(p_l, x, attn, wire=wire)
            return self._dense_mlp_residual(p_l, x, wire=wire), kept, carry
        with scope("attn.qkv"):
            u = self._pre_norm(p_l, "ln1", x)
        kept = {}
        if kind == "mamba":
            mixed, mem, s_end, tail = mixers.mamba_prompt(
                p_l, u, n_valid, c.d_state)
            kept = {"s": s_end, "conv": tail}
            carry = dict(carry, m=mem)
        elif kind == "gmu":
            mixed = mixers.gmu(p_l, u, carry["m"])
        elif kind == "mamba2":
            mixed, s_end, tail = mixers.mamba2_prompt(
                p_l, u, n_valid, c.d_state, c.ssm_chunk, c.norm_eps)
            kept = {"s": s_end, "conv": tail}
        elif kind == "gqa":
            q, k, v = self._gqa_qkv(p_l, u)
            kept = {"k": mixers.lanes(k), "v": mixers.lanes(v)}
            # causal flash attention, each key/value head under its queries
            with scope("attn.core"), scope("attn.gqa"):
                r = c.n_heads // c.n_kv_heads
                a = jnp.moveaxis(local_attention(
                    jnp.moveaxis(q, 2, 1),
                    jnp.moveaxis(jnp.repeat(k, r, axis=2), 2, 1),
                    jnp.moveaxis(jnp.repeat(v, r, axis=2), 2, 1),
                    scale=c.attention_multiplier, causal=True), 1, 2)
            mixed = self._gqa_out(p_l, a)
        else:
            q, k, v = self._diff_qkv(p_l, u, kind)
            if kind == "window":
                with scope("attn.core"), scope("attn.window"):
                    a = mixers.window_attention_prompt(q, k, v, c.window)
                kept = {"k": mixers.ring_rows(mixers.lanes(k), n_valid,
                                              c.window),
                        "v": mixers.ring_rows(mixers.lanes(v), n_valid,
                                              c.window)}
            else:
                if kind == "full":
                    kept = {"k": mixers.lanes(k), "v": mixers.lanes(v)}
                    carry = dict(carry, k=k, v=v)
                # causal flash attention, one plain head a (query head,
                # value half): the kernel the dense prompt runs
                with scope("attn.core"), scope("attn." + kind):
                    kk, vv = mixers.diff_heads(carry["k"], carry["v"],
                                               c.n_heads)
                    a = jnp.moveaxis(local_attention(
                        jnp.moveaxis(jnp.repeat(q, 2, axis=2), 2, 1),
                        jnp.moveaxis(kk, 2, 1), jnp.moveaxis(vv, 2, 1),
                        causal=True), 1, 2).astype(jnp.float32)
                    a = a.reshape(a.shape[0], a.shape[1], c.n_heads, -1)
            mixed = self._diff_out(p_l, a, l, dtype)
        x, carry = self._ffn_residual(ffn, p_l, self._residual(x, mixed),
                                      carry, at)
        return x, kept, carry

    def _prompt_segment(self, segment, stacked, x, pos0, n_valid, carry,
                        wire):
        """A run of ``repeats`` periods over the padded prompt as ONE scan
        over its parameters' leading axis: the period's layers are traced
        (and compiled) once, not once a repeat. Returns what
        :meth:`_prompt_layer` does, ``kept`` a list in layer order."""
        first, period, reps = segment
        kinds = self.kinds[first:first + period]
        ffns = self.ffn[first:first + period]
        c, (B, S, _) = self.cfg, x.shape
        # a scan's carry keeps one structure: what the period's layers hand
        # on is there from the start
        if "mamba" in kinds:
            carry = dict({"m": jnp.zeros((B, S, c.d_inner), jnp.float32)},
                         **carry)
        if "full" in kinds:
            z = jnp.zeros((B, S, c.n_kv_heads, c.head_dim), x.dtype)
            carry = dict({"k": z, "v": z}, **carry)

        # the routed experts are not scanned over (a scan hands its body a
        # COPY of each repeat's slice): the body takes the stack and `i`
        whole = [{n: a for n, a in place.items() if n in self.EXPERT_STACKS}
                 for place in stacked]
        stacked = [{n: a for n, a in place.items() if n not in whole[j]}
                   for j, place in enumerate(stacked)]

        def period_of(xc, inp):
            x, carry = xc
            i, p_i = inp
            kept = []
            for j, kind in enumerate(kinds):
                x, kept_j, carry = self._prompt_layer(
                    kind, ffns[j], first + i * period + j,
                    dict(p_i[j], **whole[j]), x, pos0, n_valid, carry, wire,
                    at=i)
                kept.append(kept_j)
            return (x, carry), kept

        (x, carry), kept = lax.scan(period_of, (x, carry),
                                    (jnp.arange(reps), stacked))
        return x, [jax.tree.map(lambda a: a[i], kept[j])
                   for i in range(reps) for j in range(period)], carry

    def prefill(self, params, toks, n_valid, wire=None):
        """Padded-prompt prefill forward: ``toks`` (Bl, Sp) int32 with
        rows >= ``n_valid`` (a traced scalar) being pad. Returns what each
        layer's cache keeps of the prompt (a list, one dict a layer, leaves
        (Bl, rows, ...); see :meth:`cache_layout`) and the f32 logits at
        position ``n_valid - 1``; a model with "moe" layers returns a third:
        the valid rows' routed pairs by held expert, summed over its layers
        ((count,) int32). Causal attention never reads a later
        column and the state-space scan stops at ``n_valid``, so valid rows
        are exactly the unpadded forward's; padded K/V rows carry garbage
        the caller must keep masked (col < upto) until its own decode
        writes overwrite them."""
        c = self.cfg
        pick = self._layer_picker(params)
        Sp = toks.shape[1]
        x = self._embed(params, toks)
        pos0 = jnp.arange(Sp)
        kept, carry = [], self._fresh_carry(
            jnp.broadcast_to(pos0 < n_valid, toks.shape))
        for s, (first, period, reps) in enumerate(self.segments):
            if reps == 1:
                p_l, at = pick(first)
                x, kept_l, carry = self._prompt_layer(
                    self.kinds[first], self.ffn[first], first, p_l, x, pos0,
                    n_valid, carry, wire, at)
                kept.append(kept_l)
            else:
                x, kept_s, carry = self._prompt_segment(
                    (first, period, reps), params["segments"][s], x, pos0,
                    n_valid, carry, wire)
                kept.extend(kept_s)
        h_last = lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = self._head(params, h_last)[:, 0]
        if self.has_experts:
            return kept, logits, carry["pairs"]
        return kept, logits

    def _fresh_carry(self, counted) -> dict:
        """What a walk over the layers hands from layer to layer, before the
        first: the expert layers' count of pairs by held expert, and the
        tokens it is a count of (``counted`` (B, S) bool: not a bucket's pad
        rows, not a dead slot)."""
        if not self.has_experts:
            return {}
        return {"pairs": jnp.zeros(self.cfg.experts_held[1], jnp.int32),
                "counted": counted}

    def _step_layer(self, l, p_l, x, cache, pos, carry, wire, at=None):
        """Layer ``l`` on a single-token batch ``x`` (Bl, 1, D), every row
        at its own position ``pos`` (Bl,). Reads and writes only what its
        kind keeps: the dense layer row ``pos`` of its own lanes; a window
        layer ring row ``pos mod window``; a full or grouped-query layer lane
        row ``pos``; a state-space layer its state and convolution tail, in
        place; cross and gated-memory layers nothing of their own. ``at``:
        the layer's place in ``p_l``'s stacked experts (None: they are its
        own)."""
        c, kind, dtype = self.cfg, self.kinds[l], self.cfg.compute_dtype
        (per_layer,) = cache
        mine = per_layer[l]
        if kind == "attn":
            # the layer's own leaves in, the same leaves out: the row
            # scatter is the only write and the attention reads the leaf
            # where it lies (no lane is copied out of or into an arena). A
            # row whose position the caller does not advance (a dead slot)
            # overwrites the same masked row: harmless by col < pos + 1
            Bl = x.shape[0]
            q, k, v = self._qkv(p_l, x, pos[:, None])
            with scope("cache.write"):
                ck = mine["k"].at[jnp.arange(Bl), pos].set(
                    k[:, 0].astype(mine["k"].dtype))
                cv = mine["v"].at[jnp.arange(Bl), pos].set(
                    v[:, 0].astype(mine["v"].dtype))
            x = self._attn_residual(
                p_l, x, self._attn_from_cache(q, ck, cv, pos + 1), wire=wire)
            x = self._dense_mlp_residual(p_l, x, wire=wire)
            mine = {"k": ck, "v": cv}
            return x, (per_layer[:l] + [mine] + per_layer[l + 1:],), carry
        with scope("attn.qkv"):
            u = self._pre_norm(p_l, "ln1", x)
        if kind == "mamba":
            mixed, mem, s, tail = mixers.mamba_step(
                p_l, u, mine["s"], mine["conv"], c.d_state)
            # the donated state IS the update's output: no copy to name
            mine = {"s": s, "conv": tail.astype(mine["conv"].dtype)}
            carry = dict(carry, m=mem)
        elif kind == "gmu":
            mixed = mixers.gmu(p_l, u, carry["m"])
        elif kind == "mamba2":
            mixed, s, tail = mixers.mamba2_step(
                p_l, u, mine["s"], mine["conv"], c.d_state, c.norm_eps)
            with scope("cache.write"), scope("state"):
                mine = {"s": s, "conv": tail.astype(mine["conv"].dtype)}
        elif kind == "gqa":
            q, k, v = self._gqa_qkv(p_l, u)
            rows = jnp.arange(x.shape[0])
            with scope("cache.write"), scope("lane"):
                mine = {"k": mine["k"].at[rows, pos].set(
                            mixers.lanes(k)[:, 0].astype(mine["k"].dtype)),
                        "v": mine["v"].at[rows, pos].set(
                            mixers.lanes(v)[:, 0].astype(mine["v"].dtype))}
            with scope("attn.core"), scope("attn.gqa"):
                a = mixers.gqa_lanes(q, mine["k"], mine["v"], pos + 1,
                                     c.attention_multiplier)
            mixed = self._gqa_out(p_l, a)
        else:
            q, k, v = self._diff_qkv(p_l, u, kind)
            if kind == "cross":
                ck, cv, seen = carry["k"], carry["v"], pos + 1
            else:
                ring = kind == "window"
                rows = jnp.arange(x.shape[0])
                at = pos % c.window if ring else pos
                with scope("cache.write"), scope("ring" if ring else "lane"):
                    mine = {"k": mine["k"].at[rows, at].set(
                                mixers.lanes(k)[:, 0].astype(mine["k"].dtype)),
                            "v": mine["v"].at[rows, at].set(
                                mixers.lanes(v)[:, 0].astype(mine["v"].dtype))}
                # read in place by the maps below: no copy, so no op of
                # its own for a scope to name
                ck, cv = mine["k"], mine["v"]
                # a ring holds the last `window` positions in any order (no
                # positional encoding): before it wraps, rows <= pos
                seen = jnp.minimum(pos + 1, c.window) if ring else pos + 1
                if kind == "full":
                    carry = dict(carry, k=ck, v=cv)
            with scope("attn.core"), scope("attn." + kind):
                a = mixers.diff_attention_lanes(q, ck, cv, seen)
            mixed = self._diff_out(p_l, a, l, dtype)
        cache = (per_layer[:l] + [mine] + per_layer[l + 1:],)
        x, carry = self._ffn_residual(
            self.ffn[l], p_l, self._residual(x, mixed), carry, at)
        return x, cache, carry

    def decode_step_logits(self, params, cache, toks, pos, wire=None,
                           live=None):
        """One token a row: ``toks`` (Bl,) at positions ``pos`` (Bl,) against
        ``cache`` (:meth:`cache_layout`'s tuple of trees). Returns (f32 logits
        (Bl, vocab), the cache with this token written); a model with "moe"
        layers returns a third: the routed pairs by held expert of the rows
        that are ``live`` ((Bl,) bool, default all), summed over its layers
        ((count,) int32)."""
        c = self.cfg
        pick = self._layer_picker(params)
        x = self._embed(params, toks)[:, None, :]
        carry = self._fresh_carry(
            jnp.ones(x.shape[:2], bool) if live is None else live[:, None])
        for l in range(c.n_layers):
            p_l, at = pick(l)
            x, cache, carry = self._step_layer(
                l, p_l, x, cache, pos, carry, wire, at)
        logits = self._head(params, x)[:, 0]
        if self.has_experts:
            return logits, cache, carry["pairs"]
        return logits, cache

    # what the cache holds, by the name `DecodeEngine.stats()` reports it
    # under: the dense layers' lanes (together the arena), a window layer's
    # ring, the full layer's lane, a state-space layer's state and
    # convolution tail
    CACHE_KINDS = {"attn": "arena", "window": "ring", "full": "lane",
                   "mamba": "state", "mamba2": "state", "gqa": "lane"}

    def cache_layout(self, slots: int, s_cap: int, dp_axes="dp"):
        """What a decode engine of ``slots`` lanes and ``s_cap`` positions
        keeps between tokens, as the MODEL describes it: (a tuple of trees of
        ``ShapeDtypeStruct``s, each an argument the engine's programs take
        and donate; the matching ``PartitionSpec``s: slots over the
        data-parallel axes, heads over tp; bytes by kind). One list, a dict
        a layer, whatever the model: a dense layer its K and V lanes
        ``(slots, s_cap, H, Dh)`` (together the "arena"), a window layer a
        ring of ``window`` rows, the full layer a lane of ``s_cap`` rows (the
        cross layers read it and keep nothing), a state-space layer its
        float32 state ``(slots, d_state, d_inner)`` and convolution tail
        ``(slots, d_conv - 1, d_inner)``, a gated memory unit nothing; a
        Mamba-2 layer its float32 state ``(slots, heads, d_head, d_state)``
        (the 128-wide axis last) and a tail over the convolved channels
        ``(slots, d_conv - 1, d_inner + 2 d_state)``, a grouped-query layer a
        lane of its own like the full layer's. A leaf a layer is what lets a program write a row and read a lane where
        they lie: a layer's lane inside one arena of all layers had to be
        sliced out and written back whole, every layer of every step."""
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        sds = jax.ShapeDtypeStruct

        def kv(n_rows):     # a position a row: mixers.lanes
            return {n: sds((slots, n_rows, c.n_kv_heads * c.head_dim),
                           dtype) for n in ("k", "v")}

        lane = sds((slots, s_cap, c.n_heads, c.head_dim), dtype)
        per_kind = {
            "attn": {"k": lane, "v": lane},
            "window": kv(c.window), "full": kv(s_cap),
            "gqa": kv(s_cap),
            "mamba": {"s": sds((slots, c.d_state, c.d_inner), jnp.float32),
                      "conv": sds((slots, c.d_conv - 1, c.d_inner), dtype)}}
        if c.ssm_heads:
            per_kind["mamba2"] = {
                "s": sds((slots, c.ssm_heads, c.d_inner // c.ssm_heads,
                          c.d_state), jnp.float32),
                "conv": sds((slots, c.d_conv - 1, c.d_inner + 2 * c.d_state),
                            dtype)}
        shapes = ([per_kind.get(kind, {}) for kind in self.kinds],)
        # a dense lane's heads go over tp; a pattern runs on dp-only grids
        spec_of = {"attn": P(dp_axes, None, "tp", None)}
        specs = ([{n: spec_of.get(kind, P(dp_axes)) for n in tree}
                  for kind, tree in zip(self.kinds, shapes[0])],)
        nbytes = {}
        for kind, tree in zip(self.kinds, shapes[0]):
            for leaf in tree.values():
                name = self.CACHE_KINDS[kind]
                nbytes[name] = nbytes.get(name, 0) + math.prod(
                    leaf.shape) * leaf.dtype.itemsize
        return shapes, specs, nbytes

    @scope("cache.write")
    def cache_store(self, cache, kept, slot, ok):
        """Write ``kept`` (:meth:`prefill`'s: one prompt from the engine,
        every row of the batch from ``generate``) into this device's
        ``cache`` from lane ``slot`` on; ``ok`` false (the slot lives on
        another dp shard) writes the lanes' OWN current rows back: the
        select is block-sized, never a full-cache copy. A state-space
        layer's state and tail are written WHOLE: whatever the lane's last
        tenant left there is gone."""
        def put(buf, new, idx):
            new = new.astype(buf.dtype)
            cur = lax.dynamic_slice(buf, idx, new.shape)
            return lax.dynamic_update_slice(
                buf, jnp.where(ok, new, cur), idx)

        zero = jnp.int32(0)
        return ([{name: put(buf, kept_l[name],
                            (slot,) + (zero,) * (buf.ndim - 1))
                  for name, buf in mine.items()}
                 for mine, kept_l in zip(cache[0], kept)],)

    def generate(self, params, prompts, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0):
        """Autoregressive decode with a per-layer KV cache.

        ``prompts``: ``(B, S0)`` int tokens; returns ``(B, S0 +
        max_new_tokens)`` (prompt included). ``temperature=0`` is greedy,
        otherwise softmax sampling at that temperature. Runs on the model's
        grid with the batch sharded over dp and heads/features over tp;
        decode is a single compiled program (prefill pass + a
        ``lax.scan`` over steps). Requires ``pp == sp == 1`` (decode is
        token-recurrent: a pipelined or sequence-sharded layout would idle
        on the single live token) and a dense MLP (no MoE routing at S=1).

        The prompt length is BUCKETED (:meth:`prompt_bucket`): prompts
        pad to the power-of-two ladder and the true length rides as a
        traced scalar, so repeated calls with varying ``S0`` share one
        compiled program per ``(B, bucket, max_new_tokens, temperature)``
        instead of recompiling per exact prompt length (program-key
        hygiene; steady-state compiles 0, pinned in
        ``tests/test_serve_decode.py``).

        K/V are cached post-RoPE, so each cache row is rotated by its own
        absolute position exactly as in the training forward.
        """
        self._needs_dense("generate")
        self.check_decode_grid()
        prompts = jnp.asarray(prompts, jnp.int32)
        B, S0 = prompts.shape
        if B % self.dp_world:
            raise ValueError(
                f"prompt batch ({B}) must divide over the data-parallel "
                f"world ({self.dp_world})")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        Sb = self.prompt_bucket(S0)
        S_max = Sb + max_new_tokens

        dp_axes = ("dcn", "dp") if self._has_dcn else "dp"
        shapes, cache_specs, _ = self.cache_layout(B, S_max, dp_axes)

        def decode(params, *rest):
            *cache, toks, n_valid, key = rest
            Bl = toks.shape[0]
            # independent sampling noise per data-parallel shard — a
            # replicated key would draw IDENTICAL continuations for equal
            # logits across the batch shards (both dp tiers count)
            dp_idx = lax.axis_index("dp")
            if self._has_dcn:
                dp_idx = lax.axis_index("dcn") * self.dp + dp_idx
            key = jax.random.fold_in(key, dp_idx)

            # ---- prefill: causal pass over the padded prompt, every row
            # of the batch kept at once ---- #
            kept, logits0 = self.prefill(params, toks, n_valid)
            cache = self.cache_store(tuple(cache), kept, jnp.int32(0), True)

            def sample(logits, key):
                with scope("sample"):
                    if temperature == 0.0:
                        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return jax.random.categorical(
                        key, logits / temperature, axis=-1).astype(jnp.int32)

            key0, key = jax.random.split(key)
            first = sample(logits0, key0)

            # ---- decode scan: the engine's step body, the whole batch at
            # position t, the cache as the carry ---- #
            def step(carry, key_t):
                cache, tok, t = carry
                logits, cache = self.decode_step_logits(
                    params, cache, tok, jnp.full((Bl,), t, jnp.int32))
                return (cache, sample(logits, key_t), t + 1), tok

            # first came from the prefill; N-1 scan steps yield the rest
            # (each step consumes the previous token and emits the next)
            keys = jax.random.split(key, max_new_tokens)[1:]
            (_, last, _), toks_out = lax.scan(
                step, (cache, first, n_valid), keys)
            # toks_out: (N-1, Bl) tokens FED at each step; append the final
            return jnp.concatenate(
                [jnp.swapaxes(toks_out, 0, 1), last[:, None]], axis=1)

        data_spec = P(dp_axes, None)
        cache_key = ("generate", B, Sb, max_new_tokens, float(temperature))
        fn = self._step_cache.get(cache_key)
        if fn is None:
            sharded = shard_map(
                decode, mesh=self.grid.mesh,
                in_specs=(self.param_specs(), *cache_specs, data_spec, P(),
                          P()),
                out_specs=data_spec, check_vma=False)

            @jax.jit
            def generate(params, toks, n_valid, key):
                # the tree `cache_layout` describes for B rows and S_max
                # positions, zeroed: a temporary of this one program
                cache = jax.tree.map(
                    lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes)
                return sharded(params, *cache, toks, n_valid, key)

            fn = self._step_cache[cache_key] = generate
        padded = jnp.pad(prompts, ((0, 0), (0, Sb - S0)))
        toks_sharded = jax.device_put(
            padded, NamedSharding(self.grid.mesh, data_spec))
        key = jax.random.key(seed)
        gen = fn(params, toks_sharded, jnp.int32(S0), key)
        return jnp.concatenate([jnp.asarray(prompts), jnp.asarray(gen)],
                               axis=1)
