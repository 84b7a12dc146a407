"""Plain float32 reference of the first training steps of an architecture.

``train_check`` follows the program's first steps from the same weights
(the architecture's recipe, drawn from the seed) on the same batches:
the loss as the configuration states it (mean next-token NLL plus
``z_loss`` times the mean squared log-normaliser), its gradient, clipping
by the global norm, and AdamW with the learning rate of the job's
schedule (linear warm-up, then a cosine to ``lr_floor`` of the peak).  It
returns what the harness compares with the program's own readings: each
step's loss, the norm of each leaf of the first (clipped) gradient, and
the norm of each leaf's change over the steps, under the program's leaf
names: ``<stack key>/<path>`` of each layer stack's ``program_layer``, each
stacked leaf taken over the layers of its stack, and the outside tensors
under the module's ``program_outside`` (``weights.stacks`` and
``weights.outside_tree`` give the defaults).

It imports nothing of the program.  The pass runs over the given devices,
rows split between them: layer by layer with the inputs of each layer
kept, each layer drawn and run by its stack's kind, then back through each
layer's gradient; the output head is the module's ``logits``, so a head
tied to the embedding passes its gradient to it; weights, moments and
gradients are kept split along their first axis between the devices.
Every matrix product runs at ``Precision.HIGHEST``.  With ``control`` the
architecture's blocks run their float8 form (``low=True``).
"""
from __future__ import annotations

import functools
import math
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import weights as W
from .attn_mlp import LOGIT_BLOCK


def lr_at(job: dict, step: int) -> float:
    peak, warm = job["lr"], job["warmup_steps"]
    if step < warm:
        return peak * min(1.0, step / max(1, warm))
    frac = min(1.0, max(0.0, (step - warm) / max(1, job["total_steps"] - warm)))
    floor = job["lr_floor"]
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))


def head_loss(arch, dm: dict, out: dict, x: jax.Array, labels: jax.Array,
              z_loss: float, low: bool = False) -> jax.Array:
    """Mean NLL + ``z_loss`` * mean log-normaliser squared, over every
    position; logits (``arch.logits`` over the outside tensors ``out``) in
    blocks of positions, recomputed for the gradient."""
    B, S, _ = x.shape
    blk = min(LOGIT_BLOCK, S)
    assert S % blk == 0, (S, blk)

    @jax.checkpoint
    def part(xb, lb):
        lg = arch.logits(dm, out, xb, low)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lb[..., None], -1)[..., 0]
        return jnp.stack([jnp.sum(logz - gold), jnp.sum(logz * logz)])

    xs = x.reshape(B, S // blk, blk, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, S // blk, blk).transpose(1, 0, 2)
    nll, z = jnp.sum(jax.lax.map(lambda a: part(*a), (xs, ls)), 0) / (B * S)
    return nll + z_loss * z


@functools.lru_cache(maxsize=None)
def _programs(arch, frozen: tuple, low: bool, mesh: Mesh, z_loss: float):
    dm = dict(frozen)
    rows = NamedSharding(mesh, P("rows"))
    rep = NamedSharding(mesh, P())
    n = mesh.devices.size

    def split(a):
        """Keep a tensor split along its first axis where it divides."""
        spec = P("rows") if a.ndim and a.shape[0] % n == 0 else P()
        return NamedSharding(mesh, spec)

    key0 = W.seed_key(0)
    out = jax.tree.map(split, jax.eval_shape(lambda: arch.outside(dm, key0)))

    def whole(t):
        return jax.lax.with_sharding_constraint(
            t, jax.tree.map(lambda _: rep, t))

    def stack(first, kind):
        """One stack's draw, forward and backward through its kind."""
        lay = jax.tree.map(split, jax.eval_shape(
            lambda: kind.layer(dm, key0, first)))

        def bwd(p, x, dy):
            _, vjp = jax.vjp(lambda p, x: kind.block(dm, p, x, low),
                             whole(p), x)
            return vjp(dy)

        return {
            "layer": jax.jit(lambda k, i: kind.layer(dm, k, i),
                             out_shardings=lay),
            "fwd": jax.jit(lambda p, x: kind.block(dm, whole(p), x, low),
                           out_shardings=rows),
            "bwd": jax.jit(bwd, out_shardings=(lay, rows)),
        }

    def head(o, x, labels):
        return jax.value_and_grad(
            lambda o, x: head_loss(arch, dm, o, x, labels, z_loss, low),
            argnums=(0, 1))(whole(o), x)

    def sq(t):
        return jax.tree.map(lambda a: jnp.sum(a * a), t)

    def adamw(p, m, v, g, scale, lr, c1, c2, hp):
        b1, b2, eps, wd = hp

        def one(p, m, v, g):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            return (p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p),
                    m, v, sq(g))

        out = jax.tree.map(one, p, m, v, g)
        pick = lambda k: jax.tree.map(lambda t: t[k], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), pick(3)

    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return {
        "rows": rows,
        "stacks": {name: stack(idx[0], kind)
                   for name, (idx, kind) in W.stacks(arch, dm).items()},
        "outside": jax.jit(lambda k: arch.outside(dm, k), out_shardings=out),
        "zeros": jax.jit(zeros),
        "embed": jax.jit(lambda e, t: e[t], out_shardings=rows),
        "head": jax.jit(head, out_shardings=(rep, (out, rows))),
        # the head's gradient of the embedding (zero where it is not tied)
        # and that of each token's row
        "embed_grad": jax.jit(lambda g, t, dx: g.at[t].add(dx),
                              out_shardings=out["embed"],
                              donate_argnums=(0,)),
        "sq": jax.jit(sq),
        "adamw": jax.jit(adamw, donate_argnums=(0, 1, 2)),
        "diff_sq": jax.jit(lambda a, b: sq(jax.tree.map(jnp.subtract, a, b))),
    }


def named(arch, dm: dict, stack, tree) -> dict:
    """The leaves of ``tree`` under the program's leaf names: one layer's
    tensors of the stack ``stack`` (``<stack>/<path>``), or with ``stack``
    None the tensors outside the layers."""
    if stack is None:
        tree = W.outside_tree(arch, dm, tree)
    else:
        tree = {stack: W.stacks(arch, dm)[stack][1].program_layer(dm, tree)}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(k.key for k in path): v for path, v in flat}


def _add(acc: dict, more: dict) -> None:
    for k, v in more.items():
        acc[k] = acc.get(k, 0.0) + float(v)


def train_check(arch, model: dict, seed: int, batches: List[dict], job: dict,
                control: bool = False, devices=None) -> dict:
    """Follow ``len(batches)`` steps of training from the weights of
    ``seed``; each batch holds ``tokens`` and ``labels`` (B, S).  Returns
    ``loss`` (one a step), ``grad_norms`` (each leaf of the first step's
    clipped gradient), ``change_norms`` (each leaf's change over all the
    steps), ``grad_total`` (the first gradient's global norm before clipping)
    and ``seconds``."""
    t0 = time.perf_counter()
    dm = arch.dims(model)
    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("rows",))
    fn = _programs(arch, tuple(sorted(dm.items())), bool(control), mesh,
                   float(job["z_loss"]))
    key = W.seed_key(seed)
    hp = (job["b1"], job["b2"], job["eps"], job["weight_decay"])
    kinds = W.layer_stacks(W.stacks(arch, dm))
    per = [fn["stacks"][k] for k in kinds]
    L = len(kinds)
    losses, grad_sq, change_sq = [], {}, {}
    with jax.default_matmul_precision("highest"):
        params = [per[i]["layer"](key, jnp.int32(i)) for i in range(L)]
        out = fn["outside"](key)
        m = [fn["zeros"](p) for p in params]
        v = [fn["zeros"](p) for p in params]
        m_out, v_out = fn["zeros"](out), fn["zeros"](out)
        for s, batch in enumerate(batches):
            tokens = jax.device_put(np.asarray(batch["tokens"]), fn["rows"])
            labels = jax.device_put(np.asarray(batch["labels"]), fn["rows"])
            xs = [fn["embed"](out["embed"], tokens)]
            for i in range(L):
                xs.append(per[i]["fwd"](params[i], xs[-1]))
            loss, (g_out, dx) = fn["head"](out, xs.pop(), labels)
            losses.append(float(loss))
            grads = [None] * L
            for i in reversed(range(L)):
                grads[i], dx = per[i]["bwd"](params[i], xs.pop(), dx)
            g_out["embed"] = fn["embed_grad"](g_out["embed"], tokens, dx)
            del dx
            total = sum(float(x) for t in [g_out] + grads
                        for x in jax.tree.leaves(fn["sq"](t)))
            scale = min(1.0, job["clip_norm"] / max(math.sqrt(total), 1e-9))
            if s == 0:
                grad_total = math.sqrt(total)
            c1 = 1.0 - job["b1"] ** (s + 1)
            c2 = 1.0 - job["b2"] ** (s + 1)
            lr = lr_at(job, s)
            for i in range(L):
                params[i], m[i], v[i], gsq = fn["adamw"](
                    params[i], m[i], v[i], grads[i], scale, lr, c1, c2, hp)
                grads[i] = None
                if s == 0:
                    _add(grad_sq, named(arch, dm, kinds[i], gsq))
            out, m_out, v_out, gsq = fn["adamw"](out, m_out, v_out, g_out,
                                                 scale, lr, c1, c2, hp)
            if s == 0:
                _add(grad_sq, named(arch, dm, None, gsq))
        for i in range(L):
            _add(change_sq, named(arch, dm, kinds[i], fn["diff_sq"](
                params[i], per[i]["layer"](key, jnp.int32(i)))))
        _add(change_sq, named(arch, dm, None, fn["diff_sq"](
            out, fn["outside"](key))))
    return {"loss": losses, "grad_total": grad_total,
            "grad_norms": {k: math.sqrt(v) for k, v in grad_sq.items()},
            "change_norms": {k: math.sqrt(v) for k, v in change_sq.items()},
            "seconds": time.perf_counter() - t0}
