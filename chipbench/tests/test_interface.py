"""Each configuration names its architecture's reference module; the harness
calls that module's interface and names no architecture itself; moving the
attention + SwiGLU code into ``reference/attn_mlp.py`` kept every number."""
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
from reference import attn_mlp                                    # noqa: E402
from reference import weights as W                                # noqa: E402

INTERFACE = ("dims", "layer", "outside", "program_layer", "block",
             "served_gaps", "train_check", "step", "train_step")
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
