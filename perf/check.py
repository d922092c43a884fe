"""How ``correct`` is decided: the timed step against a plain reference.

Set-up drives the program's compiled step, with its state, through its
first three steps on the window's own call and feed; the window then
continues from that same state.  The readings kept from those steps:

  * ``loss``: the loss each step reports (its monitoring forward on
    shard 0, before the update);
  * the first gradient as the optimizer got it: AdamW's first moment
    after one step is (1 - b1) * g, so g = m / (1 - b1), per leaf;
  * the parameters' change after three steps, per leaf.

The reference (``perf/configs/<config>.py`` with ``perf/reference.py``'s
optimizer) starts from the same seed-made weights and follows the same
three steps on the same shards, in float32 with its matrix products at
the precision the configuration states (``matmul_precision``).
It computes the gradient the cell's ranks own: the decoded mean over all
N shards where the cell holds every rank, and rank 0's decode-weighted
coded contribution where it holds rank 0's share alone.  The latter
takes the plan's coding rows, its leaf-to-level map and the step's
decode weights as inputs, after checking that those rows and weights
decode every shard exactly (``decode_residual``).

Numbers compared (each against a limit from the cell's file):

  * ``loss_gap``: max over the steps of |loss - ref| / |ref|;
  * ``grad_gap`` and ``change_gap``: max over leaves of
    | |a_leaf| - |r_leaf| | / max(|r_leaf|, median leaf |r|), where |.|
    is the leaf's L2 norm.  ``change_gap`` leaves out leaves whose
    reference gradient is under a thousandth of the median leaf's: Adam
    moves those by round-off alone;
  * ``decode_residual`` (one-rank cells): max over levels and shards of
    |sum of the holders' weight - 1|.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from perf import reference as R
from perf.traffic import ShardTokens, frame_ids, make_frame_pool
from perf.weights import make_params

#: leaves whose reference gradient norm is under this share of the
#: median leaf's are left out of ``change_gap``
STILL_LEAF = 1e-3


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                                  - y.astype(jnp.float32))))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


@dataclass
class Readings:
    losses: list = field(default_factory=list)
    grad_norms: Optional[np.ndarray] = None
    change_norms: Optional[np.ndarray] = None


@dataclass
class StepInputs:
    """What the reference needs to follow the cell's checked steps."""

    model: dict            # configuration sizes (perf/configs/<c>.json)
    traffic: dict
    seed: int
    param_shapes: object   # ShapeDtypeStruct tree (the program's layout)
    n_workers: int
    k_shards: int
    ranks: int             # ranks the cell holds: N, or 1 (rank 0's share)
    b_rows: np.ndarray     # (N, n_used, K) coding rows
    leaf_level: np.ndarray  # per-leaf index into the levels
    dec_w: list = field(default_factory=list)  # (n_used, N) per step


def decode_residual(inp: StepInputs) -> float:
    """Largest |sum over a shard's holders of a_n * b_n - 1|."""
    n, k = inp.n_workers, inp.k_shards
    worst = 0.0
    for dw in inp.dec_w:
        dw = np.asarray(dw, np.float64)
        for lvl in range(dw.shape[0]):
            for j in range(n):
                tot = sum(dw[lvl, w] * inp.b_rows[w, lvl, (j - w) % n]
                          for w in range(n) if (j - w) % n < k)
                worst = max(worst, abs(tot - 1.0))
    return float(worst)


def _weights(inp: StepInputs, step: int, fault: Optional[str]) -> dict:
    """{shard id: per-leaf weight vector} of the gradient the cell owns."""
    n, k = inp.n_workers, inp.k_shards
    out = {}
    if inp.ranks == n and fault != "no_exchange":
        for j in range(n):
            out[j] = np.full(len(inp.leaf_level), 1.0 / n)
    else:  # rank 0's coded contribution (or, as a fault, rank 0's alone)
        dw = np.asarray(inp.dec_w[step], np.float64)
        lvl = inp.leaf_level
        for slot in range(k):
            out[slot % n] = out.get(slot % n, 0.0) + (
                dw[lvl, 0] * inp.b_rows[0, lvl, slot] / n)
    if fault == "half_batch" and inp.traffic["rows_per_shard"] < 2:
        kept = sorted(out)[::2]
        out = {j: 2.0 * out[j] for j in kept}
    return out


@jax.jit
def _axpy(acc, grads, w):
    return jax.tree.map(lambda a, g, wi: a + wi * g, acc, grads,
                        jax.tree.unflatten(jax.tree.structure(grads),
                                           list(w)))


def _batch(inp: StepInputs, tokens: ShardTokens, pool, step: int, shard: int,
           fault: Optional[str]) -> dict:
    rows = inp.traffic["rows_per_shard"]
    toks = tokens.shard(step, shard, inp.n_workers)
    batch = {"tokens": jnp.asarray(toks)}
    if pool is not None:
        ids = frame_ids(step, shard, inp.n_workers, rows, pool.shape[0])
        batch["aux_inputs"] = pool[jnp.asarray(ids)]
    if fault == "half_batch" and rows >= 2:
        batch = {key: val[: rows // 2] for key, val in batch.items()}
    return batch


def reference_readings(inp: StepInputs, ref_module, *,
                       nm: Optional[R.Numerics] = None,
                       fault: Optional[str] = None) -> Readings:
    """Follow the checked steps with the plain reference, at the
    configuration's stated numerics unless ``nm`` names others.

    ``fault`` plants one fault in the reference put in the program's
    place (for reading a fault's numbers): ``half_batch`` (half of the
    rows, or of the shards, left out; the mean over the rest) or
    ``no_exchange`` (rank 0's contribution without the other ranks').
    """
    traffic, model = inp.traffic, inp.model
    nm = nm or R.stated(model)
    opt = traffic["optimizer"]
    tokens = ShardTokens(inp.seed, model["vocab"], traffic["rows_per_shard"],
                         traffic["seq_len"])
    pool = None
    if traffic.get("frames"):
        pool = make_frame_pool(inp.seed, traffic["frames"]["pool"],
                               model["encoder_frames"], model["d_model"],
                               traffic["frames"]["std"])
    p0 = make_params(inp.param_shapes, inp.seed)
    p0 = jax.tree.map(lambda x: x.astype(jnp.float32), p0)
    value_grad = jax.jit(jax.value_and_grad(
        lambda p, b: ref_module.loss(model, p, b, nm)))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    clip = jax.jit(lambda g: R.clip_global_norm(g, opt["clip_norm"]))
    adam = jax.jit(lambda p, g, m, v, c, lr: R.adamw(p, g, m, v, c, lr, opt),
                   static_argnums=(4,))
    out = Readings()
    p, m, v = p0, zeros(p0), zeros(p0)
    for step in range(len(inp.dec_w)):
        acc = zeros(p)
        loss = None
        for shard, w in _weights(inp, step, fault).items():
            val, g = value_grad(p, _batch(inp, tokens, pool, step, shard,
                                          fault))
            if shard == 0:
                loss = float(val)
            acc = _axpy(acc, g, jnp.asarray(w, jnp.float32))
            del g
        if loss is None:  # shard 0 left out by the fault: read it anyway
            loss = float(value_grad(p, _batch(inp, tokens, pool, step, 0,
                                              fault))[0])
        out.losses.append(loss)
        g = clip(acc)
        if step == 0:
            out.grad_norms = np.asarray(leaf_norms(g))
        p, m, v = adam(p, g, m, v, step + 1,
                       jnp.float32(R.learning_rate(step, opt)))
        del acc, g
    out.change_norms = np.asarray(change_norms(p, p0))
    del p, m, v, p0
    gc.collect()
    return out


def _leaf_gap(a: np.ndarray, r: np.ndarray, keep=None) -> float:
    a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
    floor = max(float(np.median(r)), np.finfo(np.float64).tiny)
    gaps = np.abs(a - r) / np.maximum(r, floor)
    if keep is not None:
        gaps = gaps[keep]
    return float(gaps.max())


def gaps(prog: Readings, ref: Readings) -> dict:
    """The compared numbers of ``prog`` against ``ref``."""
    lp, lr = np.asarray(prog.losses, np.float64), np.asarray(ref.losses,
                                                             np.float64)
    moving = ref.grad_norms >= STILL_LEAF * np.median(ref.grad_norms)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _leaf_gap(prog.grad_norms, ref.grad_norms),
        "change_gap": _leaf_gap(prog.change_norms, ref.change_norms, moving),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over every limited number."""
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
