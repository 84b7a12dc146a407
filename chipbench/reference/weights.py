"""Shared pieces of the benchmark's weight recipes, drawn from ``--seed``.

Each architecture's reference module (``attn_mlp``, ``hybrid``, ...) draws
its layers with these helpers; the harness hands the program a parameter
tree built from that module's recipe, and the reference builds its own copy
from the same seed, layer by layer; it never reads what the program holds.
Every value is rounded to bfloat16 as it is drawn, so a served copy
(bfloat16) and the reference's float32 copy hold the same numbers.

The tensors outside the layers (embedding, output head, final norm) are the
same for every architecture: :func:`outside`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: ``fold_in`` data of the tensors outside the layers
_EMBED, _UNEMBED, _FINAL_NORM = 1_000_001, 1_000_002, 1_000_003
BIAS_SCALE = 0.5           # q/k/v biases: half the size of a projection
NORM_SPREAD = 0.1          # norm scales: 1 + 0.1 N(0, 1)


def seed_key(seed: int) -> jax.Array:
    """A threefry key holding 64 bits of the seed (seeds run past 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _normal(key, shape, scale):
    return _bf16(jax.random.normal(key, shape, jnp.float32) * scale)


def _norm_scale(key, d):
    return _bf16(1.0 + NORM_SPREAD * jax.random.normal(key, (d,)))


def outside(dm: dict, key: jax.Array) -> dict:
    """Token embedding, output head and final norm scale."""
    d, v = dm["d"], dm["vocab"]
    return {
        "embed": _normal(jax.random.fold_in(key, _EMBED), (v, d), 1.0),
        "unembed": _normal(jax.random.fold_in(key, _UNEMBED), (d, v),
                           d ** -0.5),
        "ln_f": _norm_scale(jax.random.fold_in(key, _FINAL_NORM), d),
    }


def program_outside(o: dict) -> dict:
    """The tensors outside the layers under the program's names."""
    return {"embed": {"tok": o["embed"], "out": o["unembed"]},
            "ln_f": {"scale": o["ln_f"]}}
