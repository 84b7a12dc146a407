"""Shared pieces of the benchmark's weight recipes, drawn from ``--seed``.

Each architecture's reference module (``attn_mlp``, ``hybrid``, ...) draws
its layers with these helpers; the harness hands the program a parameter
tree built from that module's recipe, and the reference builds its own copy
from the same seed, layer by layer; it never reads what the program holds.
Every value is rounded to bfloat16 as it is drawn, so a served copy
(bfloat16) and the reference's float32 copy hold the same numbers.

The tensors outside the layers (embedding, output head, final norm) are the
same for every architecture that ties nothing: :func:`outside`, named for the
program by :func:`program_outside`.  :func:`stacks` and :func:`outside_tree`
read a module's own layer stacks and names of the outside tensors, or give
these defaults where it has none.
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


def program_outside(dm: dict, o: dict) -> dict:
    """The tensors outside the layers under the program's names."""
    return {"embed": {"tok": o["embed"], "out": o["unembed"]},
            "ln_f": {"scale": o["ln_f"]}}


def outside_tree(ref, dm: dict, o: dict) -> dict:
    """The module ``ref``'s outside tensors ``o`` under the program's names:
    its own ``program_outside``, or :func:`program_outside`."""
    return getattr(ref, "program_outside", program_outside)(dm, o)


def stacks(ref, dm: dict) -> dict:
    """The module ``ref``'s layer stacks, ``{key: (indices, kind)}`` in
    order: its own ``program_groups(dm)``, or the one stack ``layers`` of
    every layer with the module itself as the kind.  A kind gives
    ``layer(dm, key, i)``, ``program_layer(dm, p)`` and
    ``block(dm, p, x, low)``; each layer index belongs to one stack."""
    if hasattr(ref, "program_groups"):
        groups = ref.program_groups(dm)
    else:
        groups = {"layers": (range(dm["layers"]), ref)}
    out = {k: (tuple(int(i) for i in idx), kind)
           for k, (idx, kind) in groups.items()}
    every = sorted(i for idx, _ in out.values() for i in idx)
    if every != list(range(dm["layers"])):
        raise ValueError(f"{ref.__name__}: the stacks {sorted(out)} hold the "
                         f"layers {every}, not each of 0..{dm['layers'] - 1} "
                         "once")
    return out


def layer_stacks(groups: dict) -> list:
    """The key of the stack of each layer, in the order of the layers."""
    return [k for _, k in sorted((i, k) for k, (idx, _) in groups.items()
                                 for i in idx)]
