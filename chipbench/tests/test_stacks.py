"""A reference module with two layer stacks of different kinds and a tied
output head (``stacked_ref``) goes through the harness's tree builder and
the shared reference passes as they stand: the module alone says how its
weights become the program's tree.

Run with an explicit path: ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m
pytest chipbench/tests``.
"""
from __future__ import annotations

import math
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import serve                                                      # noqa: E402
import stacked_ref as ref                                         # noqa: E402
from conftest import TINY_JOB, TINY_MODEL, TINY_TRAIN             # noqa: E402
from reference import attn_mlp                                    # noqa: E402
from reference import weights as W                                # noqa: E402

MODEL = dict(TINY_MODEL, num_hidden_layers=4, rest_intermediate_size=96,
             qkv_bias=False)
DM = ref.dims(MODEL)
SEED = 2 ** 31 + 91


def target() -> dict:
    """The program's tree for ``MODEL``, written out: one decoder layer
    under ``first``, three SwiGLU layers under ``rest`` (both in bfloat16),
    the embedding and the final norm (float32), and no output head."""
    d, v, f, f2 = DM["d"], DM["vocab"], DM["f"], DM["f2"]
    q, kv = DM["nh"] * DM["hd"], DM["nk"] * DM["hd"]

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    return {
        "embed": {"tok": s(v, d, dtype=jnp.float32)},
        "ln_f": {"scale": s(d, dtype=jnp.float32)},
        "first": {"ln1": {"scale": s(1, d)}, "ln2": {"scale": s(1, d)},
                  "attn": {"wq": s(1, d, q), "wk": s(1, d, kv),
                           "wv": s(1, d, kv), "wo": s(1, q, d)},
                  "mlp": {"wi": s(1, d, f), "wg": s(1, d, f),
                          "wo": s(1, f, d)}},
        "rest": {"ln": {"scale": s(3, d)},
                 "mlp": {"wi": s(3, d, f2), "wg": s(3, d, f2),
                         "wo": s(3, f2, d)}},
    }


def test_each_leaf_of_both_stacks_and_the_tied_head_is_its_recipe():
    got = serve.build_params(target(), ref, MODEL, SEED)
    key = W.seed_key(SEED)
    want = ref.program_outside(DM, ref.outside(DM, key))
    want["first"] = jax.tree.map(
        lambda a: a[None],
        attn_mlp.program_layer(DM, attn_mlp.layer(DM, key, 0)))
    want["rest"] = jax.tree.map(
        lambda *a: jnp.stack(a),
        *[ref.GATED.program_layer(DM, ref.GATED.layer(DM, key, i))
          for i in (1, 2, 3)])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w, t in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree.leaves(want), jax.tree.leaves(target())):
        assert g.dtype == t.dtype, path
        # every drawn value is exact in bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32), w,
                                      err_msg=jax.tree_util.keystr(path))
    # the three layers of ``rest`` differ from each other
    wg = np.asarray(got["rest"]["mlp"]["wg"], np.float32)
    assert not np.array_equal(wg[0], wg[1])


def _renamed(tree, path, new):
    *head, last = path
    node = tree
    for k in head:
        node = node[k]
    node[new] = node.pop(last)
    return tree


def _reshaped(tree, path):
    *head, last = path
    node = tree
    for k in head:
        node = node[k]
    s = node[last]
    node[last] = jax.ShapeDtypeStruct(s.shape[:-1] + (s.shape[-1] + 1,),
                                      s.dtype)
    return tree


def _keystr(*path):
    return "".join(f"[{k!r}]" for k in path)


@pytest.mark.parametrize("case", [
    ("rename", ("rest", "mlp", "wi"), "w_in", _keystr("rest", "mlp", "w_in")),
    ("rename", ("embed", "tok"), "out", _keystr("embed", "out")),
    ("rename", ("first",), "layers", _keystr("first", "attn", "wk")),
    ("reshape", ("rest", "mlp", "wo"), None, _keystr("rest", "mlp", "wo")),
])
def test_a_tree_that_differs_fails_naming_the_first_path(case):
    how, path, new, first = case
    tree = target()
    tree = _renamed(tree, path, new) if how == "rename" \
        else _reshaped(tree, path)
    with pytest.raises(ValueError, match="stacked_ref") as e:
        serve.build_params(tree, ref, MODEL, SEED)
    assert f"at {first}:" in str(e.value)


def test_stacks_must_hold_each_layer_once():
    lone = SimpleNamespace(
        __name__="overlapping", dims=ref.dims,
        program_groups=lambda dm: {"first": (range(2), attn_mlp),
                                   "rest": (range(1, 4), ref.GATED)})
    with pytest.raises(ValueError, match="not each of 0..3 once"):
        W.stacks(lone, DM)
    assert W.layer_stacks(W.stacks(ref, DM)) == ["first"] + ["rest"] * 3


def _forward(key, tokens):
    """The whole model over ``tokens``, written out: layer 0 of the first
    kind, then the SwiGLU layers, then the tied head."""
    out = ref.outside(DM, key)
    x = out["embed"][tokens]
    x = attn_mlp.block(DM, attn_mlp.layer(DM, key, 0), x)
    for i in (1, 2, 3):
        x = ref.GATED.block(DM, ref.GATED.layer(DM, key, i), x)
    return attn_mlp.rmsnorm(x, out["ln_f"], DM["eps"]) @ out["embed"].T


def test_served_gaps_run_each_layer_by_its_kind():
    tokens = np.random.default_rng(2).integers(
        0, DM["vocab"], (2, 64)).astype(np.int32)
    gap, low = ref.served_gaps(MODEL, SEED, tokens, True)
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(_forward(W.seed_key(SEED), jnp.asarray(tokens)))
    nxt = np.concatenate([tokens[:, 1:], tokens[:, :1]], 1)
    want = lg.max(-1) - np.take_along_axis(lg, nxt[..., None], -1)[..., 0]
    want[:, -1] = 0.0
    np.testing.assert_allclose(gap, want, atol=1e-4, rtol=1e-4)
    assert (low >= 0).all() and low.max() > 0


def test_training_names_each_leaf_by_its_stack_and_follows_the_plain_grad():
    """``train_check``'s leaves read ``<stack>/<path>`` and the outside
    tensors by the module's own names, and its first gradient (clipping
    off) and loss are those of ``jax.grad`` over the model written out:
    the tied embedding takes the head's gradient and its rows' together."""
    rng = np.random.default_rng(4)
    batches = [{"tokens": rng.integers(0, DM["vocab"], (2, 32)),
                "labels": rng.integers(0, DM["vocab"], (2, 32))}
               for _ in range(2)]
    job = dict(TINY_TRAIN["optimizer"], **TINY_TRAIN["objective"],
               **TINY_JOB["schedule"], clip_norm=1e9)
    got = ref.train_check(MODEL, SEED, batches, job)
    names = {"embed/tok", "ln_f/scale", "rest/ln/scale", "rest/mlp/wi",
             "rest/mlp/wg", "rest/mlp/wo", "first/ln1/scale",
             "first/ln2/scale", "first/attn/wq", "first/attn/wk",
             "first/attn/wv", "first/attn/wo", "first/mlp/wi",
             "first/mlp/wg", "first/mlp/wo"}
    assert set(got["grad_norms"]) == set(got["change_norms"]) == names
    key = W.seed_key(SEED)
    weights = {"out": ref.outside(DM, key),
               "layers": [attn_mlp.layer(DM, key, 0)]
               + [ref.GATED.layer(DM, key, i) for i in (1, 2, 3)]}
    tokens = jnp.asarray(batches[0]["tokens"])
    labels = jnp.asarray(batches[0]["labels"])

    def loss(w):
        x = w["out"]["embed"][tokens]
        x = attn_mlp.block(DM, w["layers"][0], x)
        for p in w["layers"][1:]:
            x = ref.GATED.block(DM, p, x)
        lg = ref.logits(DM, w["out"], x)
        logz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(logz - gold) + job["z_loss"] * jnp.mean(logz * logz)

    with jax.default_matmul_precision("highest"):
        value, g = jax.value_and_grad(loss)(weights)
    np.testing.assert_allclose(got["loss"][0], float(value), rtol=1e-5)
    want = {}
    for stack, layers in (("first", g["layers"][:1]),
                          ("rest", g["layers"][1:])):
        kind = W.stacks(ref, DM)[stack][1]
        for lay in layers:
            for path, a in jax.tree_util.tree_flatten_with_path(
                    {stack: kind.program_layer(DM, lay)})[0]:
                k = "/".join(p.key for p in path)
                want[k] = want.get(k, 0.0) + float(jnp.sum(a * a))
    for path, a in jax.tree_util.tree_flatten_with_path(
            ref.program_outside(DM, g["out"]))[0]:
        want["/".join(p.key for p in path)] = float(jnp.sum(a * a))
    assert set(want) == names
    for k in names:
        np.testing.assert_allclose(got["grad_norms"][k], math.sqrt(want[k]),
                                   rtol=1e-4, err_msg=k)
    np.testing.assert_allclose(got["grad_total"],
                               math.sqrt(sum(want.values())), rtol=1e-4)
