"""The model as the benchmark runs it: its config, and its weights drawn
from the seed on the device in one jitted call.

The weights are made here and handed to the program already in the form
it serves them: every projection an {"q": int8, "scale": f32} pair with
one scale per output channel, everything else in the parameter dtype.
The plain reference reads the same arrays, so neither side takes weights
or scales that the other made.  Values follow the published
initialisation where it matters to the numbers: the rules for a
family's own parameters are in its families/ module.  Projections are
uniform int8 with scales that give unit-variance outputs.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .spec import family


def model_config(conf: dict):
    """The program's ModelConfig for a benchmark config file.

    Each ModelConfig field is read from the file's key of the same name.
    A key the file leaves out takes the ModelConfig dataclass's default,
    never the registry's value, just as a key left out of a sub-config
    group (`ssm`, `moe`) takes its dataclass's default: a file written
    before the program added a field reads as it was written.  A field
    with no default is required, and a file without it raises
    ValueError.  Keys that differ from the registered architecture, left
    out ones included, must be listed in `reduced`: the cell runs the
    registry's model and says how it was cut."""
    from repro.configs import ARCHS
    from repro.configs.base import ModelConfig
    fields = [f for f in dataclasses.fields(ModelConfig) if f.name != "name"]
    missing = [f.name for f in fields if f.name not in conf
               and f.default is dataclasses.MISSING
               and f.default_factory is dataclasses.MISSING]
    if missing:
        raise ValueError(f"config file configs/{conf['name']}.json lacks"
                         f" {missing}, which ModelConfig requires")
    cfg = ModelConfig(name=conf["arch"],
                      **{f.name: conf[f.name] for f in fields
                         if f.name in conf})
    reg = ARCHS[cfg.name]
    changed = {f.name for f in dataclasses.fields(ModelConfig)
               if getattr(cfg, f.name) != getattr(reg, f.name)}
    unlisted = changed - set(conf["reduced"])
    if unlisted:
        raise ValueError(f"config {conf['name']!r} changes {sorted(unlisted)}"
                         f" from the registered {cfg.name!r} without listing"
                         f" them in `reduced`")
    return cfg


# XLA's own bit generator: the weights of a 4-billion-parameter model
# compile and draw in a fraction of what threefry takes
KEY_IMPL = "unsafe_rbg"


def seed_key(seed: int) -> jax.Array:
    """The key data for any whole number (JAX's PRNGKey keeps only the
    low 32 bits of a large seed)."""
    words = np.random.SeedSequence(seed % 2 ** 64).generate_state(4)
    return jnp.asarray(words, jnp.uint32)


def served_shapes(cfg):
    """The parameter tree the program serves (projections quantized),
    as shapes: the structure make_params fills."""
    from repro.models import init
    from repro.quant import quantize_model_params
    return jax.eval_shape(
        lambda: quantize_model_params(init(jax.random.PRNGKey(0), cfg)))


def _uniform(key, sds, lo, hi):
    return jax.random.uniform(key, sds.shape, jnp.float32, lo, hi
                              ).astype(sds.dtype)


def _leaf(name: str, sds, key, rules: dict):
    if name == "embed":
        return (0.02 * jax.random.normal(key, sds.shape, jnp.float32)
                ).astype(sds.dtype)
    if name in ("scale", "norm_scale"):            # RMSNorm gains
        return _uniform(key, sds, 0.8, 1.2)
    if name in rules:
        return rules[name](key, sds)
    raise ValueError(f"no rule to draw parameter {name!r}; a family's "
                     f"own parameters are drawn by its families/ module")


def _is_projection(node) -> bool:
    return isinstance(node, dict) and set(node) == {"q", "scale"}


def _projection(node, key):
    kq, ks = jax.random.split(key)
    q = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, node["q"].shape, jnp.uint8), jnp.int8)
    q = jnp.maximum(q, jnp.int8(-127))
    k_in = node["q"].shape[-2]
    # uniform int8 has variance 127^2 / 3: this scale gives each output
    # channel unit variance for unit-variance inputs, within +-25%
    base = math.sqrt(3.0 / k_in) / 127.0
    scale = base * jax.random.uniform(ks, node["scale"].shape, jnp.float32,
                                      0.75, 1.25)
    return {"q": q, "scale": scale}


def _fill(node, key, rules, name=None):
    if _is_projection(node):
        return _projection(node, key)
    if isinstance(node, dict):
        return {k: _fill(v, jax.random.fold_in(key, i), rules, k)
                for i, (k, v) in enumerate(sorted(node.items()))}
    if isinstance(node, (list, tuple)):
        return type(node)(_fill(v, jax.random.fold_in(key, i), rules, name)
                          for i, v in enumerate(node))
    return _leaf(name, node, key, rules)


def make_params(cfg, seed: int):
    """The served parameter tree for `seed`, made on the device by one
    jitted call (compiled once per config, any seed)."""
    shapes = served_shapes(cfg)
    rules = family(cfg.family).LEAVES
    return jax.jit(lambda data: _fill(shapes, jax.random.wrap_key_data(
        data, impl=KEY_IMPL), rules))(seed_key(seed))


def requantized(params, precision: str):
    """The same weights requantized by the program's own quantizer to a
    lower precision ("int4"): the input of the control, the program with
    its lower-precision path switched on.  One jitted call, so the
    dequantized weights only live one leaf at a time."""
    from repro.quant import quantize_model_params_lowbit

    def dequant(t):
        if not _is_projection(t):
            return t
        return (t["q"].astype(jnp.float32)
                * t["scale"][..., None, :]).astype(jnp.bfloat16)
    return jax.jit(lambda p: quantize_model_params_lowbit(
        jax.tree.map(dequant, p, is_leaf=_is_projection), precision))(params)
