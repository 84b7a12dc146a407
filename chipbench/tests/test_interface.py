"""Each configuration names its architecture's reference module; the harness
calls that module's interface and names no architecture itself; moving the
attention + SwiGLU code into ``reference/attn_mlp.py``, and building the
program's tree from each module's layer stacks and outside names, kept every
number."""
from __future__ import annotations

import hashlib
import importlib
import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import cell as cells                                              # noqa: E402
import serve                                                      # noqa: E402
from conftest import TINY_MODEL, TINY_PROGRAM                     # noqa: E402
from conftest import TINY_HYBRID, TINY_HYBRID_PROGRAM             # noqa: E402
from reference import attn_mlp, hybrid, training                  # noqa: E402
from reference import weights as W                                # noqa: E402

INTERFACE = ("dims", "layer", "outside", "program_layer", "block", "logits",
             "served_gaps", "train_check", "step", "train_step")
#: names a module may leave out, each with a default (``weights.stacks``,
#: ``weights.outside_tree``)
OPTIONAL = ("program_groups", "program_outside")
SEED = 2 ** 31 + 77


def digest(tree) -> str:
    """sha256 of every leaf's path, type, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                             key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_a_whole_reference(name):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    ref = importlib.import_module(f"reference.{conf['reference']}")
    for fn in INTERFACE:
        assert callable(getattr(ref, fn)), (name, fn)
    for fn in OPTIONAL:
        assert not hasattr(ref, fn) or callable(getattr(ref, fn)), (name, fn)


@pytest.mark.parametrize("ref", [attn_mlp, hybrid], ids=lambda m: m.__name__)
def test_a_module_without_the_optional_names_gets_the_defaults(ref):
    """One stack ``layers`` of every layer, the module its own kind, and
    the outside tensors as ``embed.tok``, ``embed.out`` and ``ln_f.scale``."""
    for fn in OPTIONAL:
        assert not hasattr(ref, fn), fn
    dm = ref.dims(TINY_HYBRID)
    assert W.stacks(ref, dm) == {"layers": ((0, 1), ref)}
    o = {"embed": 1, "unembed": 2, "ln_f": 3}
    assert W.outside_tree(ref, dm, o) == W.program_outside(dm, o) == \
        {"embed": {"tok": 1, "out": 2}, "ln_f": {"scale": 3}}


@pytest.mark.parametrize("name", cells.all_cells())
def test_every_cell_reaches_its_configurations_reference(name):
    c = cells.load(name, False)
    assert cells.modules(c)[1].__name__ == \
        f"reference.{c.config['reference']}"


HARNESS = ("run.py", "serve.py", "train.py", "check.py", "work.py",
           "cell.py", "readers.py", "devtrace.py")


@pytest.mark.parametrize("module", HARNESS)
def test_the_harness_names_no_architecture(module):
    """Everything of an architecture is in its reference module: the
    harness names none, nor any of their tensors."""
    text = (HERE / module).read_text()
    for word in ("attn_mlp", "hybrid", "swiglu", "ssd", "ssm", "wq", "wk",
                 "mlp", "bias"):
        assert not re.search(rf"\b{word}\b", text, re.I), (module, word)


# Numbers the parent drew and counted before the move, at seed 2**31 + 77:
# full-size layers and outside tensors of both configurations, the tiny
# model's program tree and served gaps, and serving work counts.
PARENT = {
    "qwen1.5-4b": {"layer0": "db2c49e460f957e9", "layerL": "a8ca2802b3714b4e",
                   "outside": "182f0b10fc38bbcd",
                   "work": (3326523310080.0, 9854218240.0)},
    "yi-6b": {"layer0": "8c4db11ee0300e30", "layerL": "392e1f4661efbdbf",
              "outside": "acb869baa7b3ec4b",
              "work": (5776750936064.0, 12038602752.0)},
}
ROWS = [(0, 512), (100, 1), (4000, 1), (2047, 1)]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_move_keeps_the_published_configurations_weights(name):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    dm = attn_mlp.dims(conf)
    key = W.seed_key(SEED)
    got = {"layer0": digest(jax.jit(lambda k: attn_mlp.layer(dm, k, 0))(key)),
           "layerL": digest(jax.jit(lambda k: attn_mlp.layer(
               dm, k, dm["layers"] - 1))(key)),
           "outside": digest(jax.jit(lambda k: attn_mlp.outside(dm, k))(key)),
           "work": attn_mlp.step(dm, ROWS, 4)}
    assert got == PARENT[name]


def test_the_move_keeps_the_tiny_tree_gaps_and_work():
    cfg = serve.model_config({"program": TINY_PROGRAM})
    assert digest(serve.program_params(cfg, attn_mlp, TINY_MODEL, SEED)) \
        == "2256a479c2a8d12a"
    tokens = np.random.default_rng(5).integers(0, 512, (3, 512)
                                               ).astype(np.int32)
    gap, low = attn_mlp.served_gaps(TINY_MODEL, SEED, tokens, True)
    assert (digest(gap), digest(low)) == ("d013bb1332914a4a",
                                          "a981478614b79c7f")
    assert digest(attn_mlp.served_gaps(TINY_MODEL, SEED, tokens)[0]) \
        == "d013bb1332914a4a"
    assert attn_mlp.step(attn_mlp.dims(TINY_MODEL), ROWS, 4) == \
        (146590720.0, 1985536.0)


# Numbers the parent drew before each module built the program's whole tree,
# at seed 2**31 + 77: the tiny hybrid tree and served gaps; and hymba-1.5b's
# training leaf names.
HYMBA_LEAVES = [
    "embed/out", "embed/tok", "layers/attn/wk", "layers/attn/wo",
    "layers/attn/wq", "layers/attn/wv", "layers/ln1/scale",
    "layers/ln2/scale", "layers/lns/scale", "layers/mlp/wg",
    "layers/mlp/wi", "layers/mlp/wo", "layers/ssm/a_bias", "layers/ssm/wa",
    "layers/ssm/wb", "layers/ssm/wc", "layers/ssm/wo", "layers/ssm/wx",
    "ln_f/scale"]


def test_the_stacks_keep_the_hybrid_tree_gaps_and_training_names():
    cfg = serve.model_config({"program": TINY_HYBRID_PROGRAM})
    assert digest(serve.program_params(cfg, hybrid, TINY_HYBRID, SEED)) \
        == "52e10d8f6ef50a53"
    tokens = np.random.default_rng(5).integers(0, 512, (3, 512)
                                               ).astype(np.int32)
    gap, low = hybrid.served_gaps(TINY_HYBRID, SEED, tokens, True)
    assert (digest(gap), digest(low)) == ("9ac95a5aceb27329",
                                          "ef40ce015d27b4f4")
    conf = json.loads((HERE / "configs" / "hymba-1.5b.json").read_text())
    dm = hybrid.dims(conf)
    key = W.seed_key(SEED)
    names = set(training.named(hybrid, dm, None, jax.eval_shape(
        lambda: hybrid.outside(dm, key))))
    for stack, (idx, kind) in W.stacks(hybrid, dm).items():
        names |= set(training.named(hybrid, dm, stack, jax.eval_shape(
            lambda: kind.layer(dm, key, idx[0]))))
    assert sorted(names) == HYMBA_LEAVES
