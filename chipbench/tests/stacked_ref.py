"""A reference module for the tests alone: two layer stacks of different
kinds and an output head tied to the embedding.

Layer 0 is ``attn_mlp``'s decoder layer, under the key ``first``; layers
1 and up are a second kind, a pre-norm SwiGLU layer of another width with
no attention, under the key ``rest``.  The output head is the transposed
embedding, so the tensors outside the layers are the embedding and the
final norm alone.  No program model has this tree: the tests hand the
harness a target tree of their own.
"""
from __future__ import annotations

import sys
from types import SimpleNamespace

import jax

from reference import attn_mlp
from reference import weights as W
from reference.attn_mlp import mm, rmsnorm

#: ``fold_in`` data of the second kind's layers
_GATED = 2_000_001


def dims(model: dict) -> dict:
    return dict(attn_mlp.dims(model), f2=model["rest_intermediate_size"])


def _gated_layer(dm: dict, key: jax.Array, i) -> dict:
    d, f = dm["d"], dm["f2"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, _GATED),
                                             i), 4)
    return {"ln": W._norm_scale(ks[0], d),
            "wg": W._normal(ks[1], (d, f), d ** -0.5),
            "wu": W._normal(ks[2], (d, f), d ** -0.5),
            "wd": W._normal(ks[3], (f, d), f ** -0.5)}


def _gated_program_layer(dm: dict, p: dict) -> dict:
    return {"ln": {"scale": p["ln"]},
            "mlp": {"wi": p["wu"], "wg": p["wg"], "wo": p["wd"]}}


def _gated_block(dm: dict, p: dict, x: jax.Array, low: bool = False):
    h = rmsnorm(x, p["ln"], dm["eps"])
    g = mm(h, p["wg"], low)
    return x + mm(g * jax.nn.sigmoid(g) * mm(h, p["wu"], low), p["wd"], low)


GATED = SimpleNamespace(layer=_gated_layer, program_layer=_gated_program_layer,
                        block=_gated_block)


def program_groups(dm: dict) -> dict:
    return {"first": (range(1), attn_mlp),
            "rest": (range(1, dm["layers"]), GATED)}


def outside(dm: dict, key: jax.Array) -> dict:
    o = W.outside(dm, key)
    return {"embed": o["embed"], "ln_f": o["ln_f"]}


def program_outside(dm: dict, o: dict) -> dict:
    return {"embed": {"tok": o["embed"]}, "ln_f": {"scale": o["ln_f"]}}


def logits(dm: dict, out: dict, x: jax.Array, low: bool = False):
    return mm(rmsnorm(x, out["ln_f"], dm["eps"]), out["embed"].T, low)


def served_gaps(model: dict, seed: int, tokens, control: bool = False):
    return attn_mlp.teacher_forced_gaps(sys.modules[__name__], model, seed,
                                        tokens, control)


def train_check(model: dict, seed: int, batches, job: dict,
                control: bool = False, devices=None) -> dict:
    from reference import training
    return training.train_check(sys.modules[__name__], model, seed, batches,
                                job, control, devices)
