"""Dense weights of a configuration, drawn on the device from its
``weight_seed``.

The benchmark makes the weights itself, so that its reference can draw the
same ones again without taking anything from the program. Each layer is one
jitted call in the dtype the configuration serves; layers are drawn one at a
time because the dense model and its packing do not fit one chip together.
Norm gains are the configuration's ``norm_gain``, stored as ``gain - 1``
because the program's norms scale by ``1 + g``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PROJECTIONS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any non-negative whole number (more than 32 bits)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def shapes(conf: dict) -> dict:
    """(d_in, d_out) of each projection of one layer."""
    d = conf["hidden_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    ff = conf["intermediate_size"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


@functools.partial(jax.jit, static_argnames=("shp", "dtype", "gain"))
def _layer(key, shp, dtype, gain):
    sh = dict(shp)
    d = sh["wq"][0]
    ks = jax.random.split(key, len(PROJECTIONS))
    scale = {"wq": d ** -0.5, "wk": d ** -0.5, "wv": d ** -0.5,
             "wo": sh["wo"][0] ** -0.5, "w_gate": d ** -0.5,
             "w_up": d ** -0.5, "w_down": sh["w_down"][0] ** -0.5}
    out = {p: (jax.random.normal(k, sh[p], jnp.float32) * scale[p]
               ).astype(dtype) for p, k in zip(PROJECTIONS, ks)}
    out["ln1"] = jnp.full((d,), gain - 1.0, jnp.float32)
    out["ln2"] = jnp.full((d,), gain - 1.0, jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("vocab", "d", "dtype", "gain"))
def _globals(key, vocab, d, dtype, gain):
    ke, kh = jax.random.split(key)
    return {"embed": (jax.random.normal(ke, (vocab, d), jnp.float32) * 0.02
                      ).astype(dtype),
            "head": (jax.random.normal(kh, (d, vocab), jnp.float32) * 0.02
                     ).astype(dtype),
            "final_ln": jnp.full((d,), gain - 1.0, jnp.float32)}


def layer(conf: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s dense weights (projections in the served dtype)."""
    key = jax.random.fold_in(seed_key(seed, 1), i)
    return _layer(key, tuple(sorted(shapes(conf).items())),
                  jnp.dtype(conf["dtype"]), float(conf["norm_gain"]))


def globals_(conf: dict, seed: int) -> dict:
    """Embedding, output head and final norm gain."""
    return _globals(seed_key(seed, 2), conf["vocab_size"],
                    conf["hidden_size"], jnp.dtype(conf["dtype"]),
                    float(conf["norm_gain"]))
