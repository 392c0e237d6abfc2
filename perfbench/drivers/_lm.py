"""What the two LM drivers share: the configuration file's (Hugging Face)
keys mapped onto `TransformerLMConfig`, and the weights made by the
BENCHMARK on the device from the seed, in one jitted call, in the tree layout
and shardings that `TransformerLM` takes (`model.param_specs()`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

AXES = ("dp", "pp", "tp", "sp")


def seed_key(seed: int):
    key = jax.random.key(seed % (2 ** 31))
    return jax.random.fold_in(key, seed // (2 ** 31))


def build_model(config: dict, devices, memo: dict):
    """The `TransformerLM` on a (1,1,1,1) grid of one chip.
    `memo` is the caller's (`ctx.memo`): a tool that runs many seeds through
    one context builds the model, and compiles, once."""
    if "model" in memo:
        return memo["model"]
    import heat_tpu as ht
    from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig

    cfg = TransformerLMConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"], d_ff=config["intermediate_size"],
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        init_scale=config["initializer_range"], rope=True,
        rope_theta=float(config["rotary_emb_base"]), remat=False)
    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=list(devices)[:1])
    memo["model"] = TransformerLM(grid, cfg)
    return memo["model"]


def param_shapes(config: dict) -> dict:
    D, F, V = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    H, L = config["num_attention_heads"], config["num_hidden_layers"]
    Dh = D // H
    return {"embed": (V, D), "final_ln": (D,), "unembed": (D, V),
            "stages": {"ln1": (1, L, D), "wqkv": (1, L, D, 3, H, Dh),
                       "wproj": (1, L, H, Dh, D), "ln2": (1, L, D),
                       "w_up": (1, L, D, F), "w_down": (1, L, F, D)}}


def make_params(key, config: dict, shardings=None):
    """Float32 weights from `key`: N(0, initializer_range) matrices, unit
    norm scales. ONE jitted call, every leaf born on the device."""
    shapes = param_shapes(config)
    scale = config["initializer_range"]

    def gen(key):
        flat, treedef = jax.tree.flatten(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        paths = jax.tree.leaves_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, ((path, _), shape) in enumerate(zip(paths, flat)):
            name = str(path[-1])
            if "ln" in name:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(scale * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(gen, out_shardings=shardings)(key)


def shardings_of(model):
    return jax.tree.map(lambda s: NamedSharding(model.grid.mesh, s),
                        model.param_specs(),
                        is_leaf=lambda s: isinstance(s, P))
