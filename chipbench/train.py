"""The training runner: drive the program's sharded train step through one
cell's window (a traffic mix with ``"runner": "train"``).

The mix is the job: ``seq_len``, ``global_batch``, its ``generator``'s
``data`` settings and the learning-rate ``schedule``.  The
configuration gives the program's model (``program``), the microbatches
(``train``), the optimizer the program runs as it states it
(``optimizer``), and the limits (``check``).

Set-up builds one trainer (``repro.launch.train.build_trainer`` on a host
mesh over the cell's chips, FSDP over ``data``), fills its parameters from
the configuration's weight recipe in one jitted call with the state's
shardings, and runs the first steps (``check.steps``, and at least
``WARM_STEPS``) through the same call and the same batch stream as the
window: they compile (or load) the step, and the reference follows the
first ``check.steps`` of them.  From them it reads what the comparison
needs: each step's loss, each leaf's norm of the first gradient as AdamW's
first moment holds it (``m = (1 - b1) g`` after one step), and each leaf's
change over those steps against the recipe's weights.  The window then
runs whole steps for ``--seconds``, reading each step's loss as a training
job logs it; each step's start, end, tokens and loss go into the record.
A traced run traces ``TRACE_STEPS`` whole steps from the middle of the
window.

After the window, :func:`check` frees the program's state and runs the
reference (``train_check`` of the configuration's reference module) over
the same batches, at the timed sizes, on the same chips.
"""
from __future__ import annotations

import gc
import math
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import serve

#: share of the window after which a traced run starts its trace
TRACE_FROM = 0.5
TRACE_STEPS = 3
#: steps set-up runs at least: the first compiles or loads the step, the
#: second shows it runs again without
WARM_STEPS = 2


def job_settings(cell) -> dict:
    """What the reference needs of the job: the optimizer the
    configuration states and its objective, and the mix's schedule."""
    return dict(cell.config["optimizer"], **cell.config["objective"],
                **cell.traffic["schedule"])


class Feed:
    """Batches ``0, 1, 2, ...`` of ``ds``, each drawn on one host thread
    ``ahead`` steps before the step that takes it, as a training job's
    input pipeline prefetches."""

    def __init__(self, ds, ahead: int = 2):
        self.ds, self.ahead = ds, ahead
        self.pool = ThreadPoolExecutor(1)
        self.due = {i: self.pool.submit(ds.batch_at, i) for i in range(ahead)}

    def batch(self, i: int) -> dict:
        out = self.due.pop(i).result()
        self.due[i + self.ahead] = self.pool.submit(self.ds.batch_at,
                                                    i + self.ahead)
        return out

    def close(self) -> None:
        self.pool.shutdown(wait=True, cancel_futures=True)


def leaf_names(tree) -> List[str]:
    return ["/".join(k.key for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norms(tree) -> Dict[str, float]:
    sq = jax.jit(lambda t: [jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree.leaves(t)])(tree)
    return {k: math.sqrt(float(v)) for k, v in zip(leaf_names(tree), sq)}


def measure(cell, ref, seed: int, seconds: float, trace_dir, compiles,
            t_start: float, devices):
    from repro.distributed import sharding as dist
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_trainer

    conf, job = cell.config, cell.traffic
    cfg = serve.model_config(conf)
    mesh = make_host_mesh(devices=devices)
    n_check = conf["check"]["steps"]
    ds = cell.generator.dataset(job, seed, ref.dims(conf)["vocab"])
    feed = Feed(ds)
    tokens = job["global_batch"] * job["seq_len"]
    b1 = conf["optimizer"]["b1"]
    with mesh, dist.use_mesh_rules(mesh, dist.rules_for(cfg, mesh)):
        params, opt_state, step, (p_sh, _) = build_trainer(
            cfg, mesh, lr=job["schedule"]["lr"],
            total_steps=job["schedule"]["total_steps"],
            microbatches=conf["train"]["microbatches"], seed=0)
        # the weights are the recipe's, so the reference can draw them too
        del params
        params = serve.program_params(cfg, ref, conf, seed, shardings=p_sh)

        def run_step(i):
            nonlocal params, opt_state
            batch = feed.batch(i)
            params, opt_state, m = step(
                params, opt_state, {n: jnp.asarray(v) for n, v in batch.items()},
                jnp.asarray(i, jnp.int32))
            return m

        losses = []
        for i in range(max(n_check, WARM_STEPS)):
            m = run_step(i)
            losses.append(float(m["loss"]))
            if i == 0:
                grad_total = float(m["grad_norm"])
                grad_norms = {k: v / (1.0 - b1)
                              for k, v in _norms(opt_state["m"]).items()}
            if i == n_check - 1:
                start = serve.program_params(cfg, ref, conf, seed,
                                             shardings=p_sh)
                change_norms = _norms(jax.tree.map(jnp.subtract, params,
                                                   start))
                del start
        setup_s = time.perf_counter() - t_start

        steps, traced, i = [], None, len(losses)
        window_span = None
        compiles_at_start = compiles[0]
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            now = time.perf_counter()
            if traced is not None and traced[1] is None and (
                    i == traced_from + TRACE_STEPS or now >= end):
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                traced[1] = time.perf_counter() - t0
            if now >= end:
                break
            if trace_dir is not None and traced is None \
                    and now >= t0 + TRACE_FROM * seconds:
                called = now - t0
                jax.profiler.start_trace(trace_dir)
                window_span = jax.profiler.TraceAnnotation("chipbench.window")
                window_span.__enter__()
                traced, traced_from = [time.perf_counter() - t0, None,
                                       called], i
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.step"):
                loss = float(run_step(i)["loss"])
            te = time.perf_counter()
            steps.append({"step": i, "t0": ts - t0, "t1": te - t0,
                          "tokens": tokens, "batch": job["global_batch"],
                          "seq_len": job["seq_len"], "loss": loss})
            i += 1
        window_s = time.perf_counter() - t0
    feed.close()
    record = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
              "traced": traced}
    state = {"params": params, "opt_state": opt_state,
             "losses": losses[:n_check],
             "grad_norms": grad_norms, "grad_total": grad_total,
             "change_norms": change_norms,
             "warm_losses": losses,
             "window_losses": [s["loss"] for s in steps], "ds": ds,
             "compiles_in_window": compiles[0] - compiles_at_start}
    return record, state


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              counted: List[str]) -> Dict[str, float]:
    """Each leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median([ref[k] for k in counted]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in counted}


def compare(prog: dict, ref: dict, limits: dict, clip: float) -> tuple:
    """Every reading, and the numbers compared with a limit: those the
    configuration's ``check`` gives a limit.  Of the gradient's and the
    change's leaf gaps both the worst (``*_norm_gap``) and the median
    (``*_median_gap``) are read.  A leaf counts where its reference
    gradient is at least ``MOVED`` of the median leaf's (a leaf whose
    gradient is nought to rounding moves under AdamW by round-off alone)."""
    med = float(np.median(list(ref["grad_norms"].values())))
    counted = sorted(k for k, v in ref["grad_norms"].items()
                     if v >= MOVED * med)
    readings = {"loss_gap": max(abs(a - b) / abs(b) for a, b
                                in zip(prog["loss"], ref["loss"]))}
    worst = {}
    parts = [("grad", "grad_norms")]
    if any(ref["change_norms"].values()):   # the first step's rate is 0
        parts.append(("change", "change_norms"))
    for part, key in parts:
        gaps = leaf_gaps(prog[key], ref[key], counted)
        worst[part] = max(gaps, key=gaps.get)
        readings[f"{part}_norm_gap"] = gaps[worst[part]]
        readings[f"{part}_median_gap"] = float(np.median(list(gaps.values())))
    # the first gradient before clipping (at ``clip``): its global norm,
    # and each leaf with the clipping, which scales every leaf alike, taken
    # out
    if "grad_total" in prog and "grad_total" in ref:
        t_prog, t_ref = prog["grad_total"], ref["grad_total"]
        readings["grad_total_gap"] = abs(t_prog - t_ref) / t_ref
        unclipped = [{k: v * max(t, clip) for k, v
                      in side["grad_norms"].items()}
                     for side, t in ((prog, t_prog), (ref, t_ref))]
        gaps = leaf_gaps(*unclipped, counted)
        readings["grad_unclipped_median_gap"] = float(
            np.median(list(gaps.values())))
        readings["grad_unclipped_norm_gap"] = max(gaps.values())
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in readings.items() if k in limits}
    return checks, {"readings": readings, "grad_worst_leaf": worst["grad"],
                    "change_worst_leaf": worst.get("change"),
                    "leaves_counted": len(counted),
                    "leaves_left_out": sorted(set(ref["grad_norms"])
                                              - set(counted))}


#: a leaf counts where its reference gradient is at least this share of the
#: median leaf's
MOVED = 1e-3


def check(cell, ref, state: dict, seed: int, control: bool, devices):
    """After the window: the reference follows the first steps on the same
    batches, and the program's readings (or, under ``control``, the float8
    control's) are held to the limits.  Every step's loss, in set-up and
    window, has to be finite."""
    conf, lim = cell.config, cell.config["check"]
    del state["params"], state["opt_state"]
    gc.collect()
    batches = [state["ds"].batch_at(i) for i in range(lim["steps"])]
    job = job_settings(cell)
    t_ref = time.perf_counter()
    want = ref.train_check(conf, seed, batches, job, False, devices)
    prog = {"loss": state["losses"], "grad_norms": state["grad_norms"],
            "grad_total": state["grad_total"],
            "change_norms": state["change_norms"]}
    got = ref.train_check(conf, seed, batches, job, True, devices) \
        if control else prog
    t_ref = time.perf_counter() - t_ref
    window = state["window_losses"]
    failed = sum(1 for v in state["warm_losses"] + window
                 if not math.isfinite(v))
    clip = conf["optimizer"]["clip_norm"]
    program, seen = compare(prog, want, lim, clip)
    checks, got_seen = (compare(got, want, lim, clip) if control
                        else (program, seen))
    for c in (program, checks):
        c["failed_steps"] = {"value": failed, "limit": 0}
    info = dict(seen, program_checks={k: c["value"]
                                      for k, c in program.items()},
                checked_readings=got_seen["readings"],
                reference_s=t_ref, reference_loss=want["loss"],
                program_loss=prog["loss"], checked_loss=got["loss"],
                compiles_in_window=state["compiles_in_window"],
                steps=len(window))
    return checks, len(window), \
        sum(1 for v in window if not math.isfinite(v)), info
