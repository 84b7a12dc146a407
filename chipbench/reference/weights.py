"""The benchmark's weights, drawn from ``--seed`` by this recipe alone.

The harness hands the program a parameter tree built from these functions,
and the reference builds its own copy from the same seed, layer by layer;
it never reads what the program holds.  Every value is rounded to bfloat16
as it is drawn, so the served copy (bfloat16) and the reference's float32
copy hold the same numbers.

Model sizes come from the configuration file's published keys
(``hidden_size``, ``num_attention_heads``, ...), read by :func:`dims`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: ``fold_in`` data of the tensors outside the layers
_EMBED, _UNEMBED, _FINAL_NORM = 1_000_001, 1_000_002, 1_000_003
BIAS_SCALE = 0.5           # q/k/v biases: half the size of a projection
NORM_SPREAD = 0.1          # norm scales: 1 + 0.1 N(0, 1)


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    nh = model["num_attention_heads"]
    return {
        "d": d, "nh": nh, "nk": model.get("num_key_value_heads", nh),
        "hd": model.get("head_dim") or d // nh,
        "f": model["intermediate_size"], "vocab": model["vocab_size"],
        "layers": model["num_hidden_layers"],
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "bias": bool(model.get("qkv_bias", False)),
    }


def seed_key(seed: int) -> jax.Array:
    """A threefry key holding 64 bits of the seed (seeds run past 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, scale):
    return _bf16(jax.random.normal(key, shape, jnp.float32) * scale)


def layer(dm: dict, key: jax.Array, i) -> dict:
    """Layer ``i``'s tensors (float32, bfloat16-exact).  ``i`` may be traced."""
    d, nh, nk, hd, f = dm["d"], dm["nh"], dm["nk"], dm["hd"], dm["f"]
    ks = jax.random.split(jax.random.fold_in(key, i), 12)
    p = {
        "ln1": _bf16(1.0 + NORM_SPREAD * jax.random.normal(ks[0], (d,))),
        "wq": _normal(ks[1], (d, nh * hd), d ** -0.5),
        "wk": _normal(ks[2], (d, nk * hd), d ** -0.5),
        "wv": _normal(ks[3], (d, nk * hd), d ** -0.5),
        "wo": _normal(ks[4], (nh * hd, d), (nh * hd) ** -0.5),
        "ln2": _bf16(1.0 + NORM_SPREAD * jax.random.normal(ks[5], (d,))),
        "wg": _normal(ks[6], (d, f), d ** -0.5),
        "wu": _normal(ks[7], (d, f), d ** -0.5),
        "wd": _normal(ks[8], (f, d), f ** -0.5),
    }
    if dm["bias"]:
        p["bq"] = _normal(ks[9], (nh * hd,), BIAS_SCALE)
        p["bk"] = _normal(ks[10], (nk * hd,), BIAS_SCALE)
        p["bv"] = _normal(ks[11], (nk * hd,), BIAS_SCALE)
    return p


def outside(dm: dict, key: jax.Array) -> dict:
    """Token embedding, output head and final norm scale."""
    d, v = dm["d"], dm["vocab"]
    return {
        "embed": _normal(jax.random.fold_in(key, _EMBED), (v, d), 1.0),
        "unembed": _normal(jax.random.fold_in(key, _UNEMBED), (d, v),
                           d ** -0.5),
        "ln_f": _bf16(1.0 + NORM_SPREAD * jax.random.normal(
            jax.random.fold_in(key, _FINAL_NORM), (d,))),
    }
