"""Block coordinate gradient coding integrated into the training loop.

The plan math (solve -> assign -> code, the straggler simulator, eq.(2)
ledger) lives in ``repro.core.plan``/``repro.core.schemes``; this module
is the jax integration:

  * ``make_coded_grad_fn`` — the worker-side compute: (s_max+1)
    per-shard gradients (the redundancy work), then the coded combine
    that replaces the data-parallel all-reduce (DESIGN.md §3).  Two
    combine pipelines share the math:

      - ``pipeline='flat'`` (default when the plan carries a
        ``FlatLayout``): the FUSED path.  Per leaf, encode row and
        decode weight fold into ONE weighted sum (kernels/gc_fused
        math — a single streaming pass over the per-shard gradients,
        no separate scale pass, no per-leaf reduction bookkeeping).
        In spmd mode each rank's weighted contributions land in the
        plan's packed per-level flat buffers (lane-aligned,
        N-divisible — ``Plan.flat_layout``), so the decode-weighted
        reduction is ONE collective per redundancy level instead of
        one per leaf, ``psum_scatter`` is unconditionally available,
        and bf16 ``grad_dtype`` casts happen once on the packed
        buffer.  The optimizer tree is unflattened once, at the end.
      - ``pipeline='tree'``: the legacy per-leaf loop (encode
        tensordot + decode-weight scale per leaf, one collective per
        leaf) — kept as the baseline the flat path is benchmarked
        against (benchmarks/coded_step.py) and parity-tested against
        (tests/test_flat_pipeline.py).

  * ``combine_grads`` — the combine stage alone (stacked per-shard
    grads -> decoded mean gradient), the bench/test surface for both
    pipelines.
  * legacy shims — ``CodingPlan``/``build_plan``/``solve_blocks``/
    ``StragglerSim``/``tau_weighted`` keep the pre-registry entry points
    working; new code should use ``Plan.build`` and
    ``repro.core.solve_scheme``.  Direct importers of the old tree-loop
    helpers ``_encode_tree``/``_scale_tree`` get a one-shot
    ``DeprecationWarning`` pointing at ``combine_grads``.

Two execution modes share the math:
  * ``mode='spmd'``  — jax.shard_map, manual over every mesh axis:
                       coding runs across the 'data' ranks and the
                       decoded gradient materializes as a weighted psum.
  * ``mode='sim'``   — single-device simulation: lax.map over workers
                       (examples, CPU tests).

Exactness invariant (tested): for EVERY straggler realization, the
decoded gradient equals the plain data-parallel gradient over the same
global batch, to float tolerance — on both pipelines.

Each layer of the step runs under a ``jax.named_scope``, which lands in
the compiled HLO's ``op_name`` metadata and so names the layer of every
device op in a profiler trace (docs/PERF.md, "Profiling a run"):
``per_shard_grad`` (the K forward/backward passes), ``gc_pack`` (the
combined leaves packed into the level buffers),
``gc_combine`` (the encode⊙decode kernel), ``level_collective`` (the
per-level reduction) and ``gc_unpack`` (level buffers back to leaves).
Scopes are metadata only: no computed value depends on them.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import Plan, PlanSimulator, UNIT_RESOLUTION, solve_scheme
from repro.core.runtime import CostModel, DEFAULT_COST
from repro.core.schemes import get_scheme
from repro.deprecation import reset_warned, warn_once
from repro.kernels import ops
from repro.models.model import train_loss

__all__ = ["CodingPlan", "build_plan", "solve_blocks", "StragglerSim",
           "make_coded_grad_fn", "uncoded_grad_fn", "combine_grads",
           "combine_level", "tau_weighted", "UNIT_RESOLUTION"]

#: Legacy name — ``CodingPlan`` was promoted to ``repro.core.plan.Plan``.
CodingPlan = Plan

# One-shot deprecations: each legacy entry point (and each legacy
# scheme key spelling) warns once per process, naming its registry-API
# replacement.  The machinery (and the ReproDeprecationWarning category
# tier-1 promotes to an error for repro.* callers) is shared with the
# other shim modules in ``repro.deprecation``.
_warn_once = warn_once


def _reset_deprecation_warnings() -> None:
    """Forget which one-shot deprecation warnings already fired (tests)."""
    reset_warned()


def _warn_legacy_key(name: str) -> None:
    """Legend-string / legacy solver keys resolve via registry aliases;
    nudge callers toward the canonical scheme name.  stacklevel=4 skips
    this extra frame so the warning attributes to the shim's caller."""
    try:
        canonical = get_scheme(name).name
    except KeyError:
        return  # unknown scheme: let the registry raise its own error
    if canonical != name:
        warn_once(f"key:{name}",
                  f"legacy scheme key {name!r} is deprecated; use the "
                  f"canonical registry name {canonical!r} "
                  "(repro.core.available_schemes())", stacklevel=4)


def __getattr__(name: str):
    """One-shot deprecation shim for direct importers of the old
    per-leaf tree-loop helpers (the flat fused pipeline replaced them
    in the training hot path)."""
    if name in ("_encode_tree", "_scale_tree"):
        _warn_once(f"treeloop:{name}",
                   f"repro.train.coded.{name} is deprecated; use "
                   "repro.train.coded.combine_grads(plan, grads, dec_w, "
                   "pipeline='flat') — the fused flat pipeline")
        return {"_encode_tree": _tree_encode, "_scale_tree": _tree_scale}[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def solve_blocks(solver: str, dist, n_workers: int, total: int, rng=0,
                 s_cap=None) -> np.ndarray:
    """Deprecated shim — routes through the ``repro.core`` scheme
    registry (``solve_scheme``); every legacy solver string is a
    registered name or alias there."""
    _warn_once("solve_blocks",
               "repro.train.coded.solve_blocks is deprecated; use "
               "repro.core.solve_scheme(name, env, n_workers, total)")
    _warn_legacy_key(solver)
    return solve_scheme(solver, dist, n_workers, total, rng=rng, s_cap=s_cap)


def build_plan(params, dist, n_workers: int, solver: str = "xf", rng: int = 0,
               prefer_fractional: bool = False, s_cap=None) -> Plan:
    """Deprecated shim for ``Plan.build`` (old keyword ``solver`` is the
    registry's ``scheme``)."""
    _warn_once("build_plan",
               "repro.train.coded.build_plan is deprecated; use "
               "repro.core.Plan.build(params, env, scheme=...)")
    _warn_legacy_key(solver)
    return Plan.build(params, dist, n_workers, scheme=solver, rng=rng,
                      prefer_fractional=prefer_fractional, s_cap=s_cap)


def tau_weighted(plan: Plan, times: np.ndarray,
                 cost: CostModel = DEFAULT_COST) -> float:
    """Deprecated shim for ``Plan.tau`` (eq. (2) on the leaf layout)."""
    _warn_once("tau_weighted",
               "repro.train.coded.tau_weighted is deprecated; use "
               "plan.tau(times, cost)")
    return plan.tau(times, cost)


class StragglerSim(PlanSimulator):
    """Deprecated shim for ``plan.simulator(...)`` /
    ``plan.simulate(...)``; keeps the old jnp return type of step()."""

    def __init__(self, *args, **kw):
        _warn_once("StragglerSim",
                   "repro.train.coded.StragglerSim is deprecated; use "
                   "plan.simulator(env) / plan.simulate(env, steps)")
        super().__init__(*args, **kw)

    def step(self):
        dec_w, rec = super().step()
        return jnp.asarray(dec_w, jnp.float32), rec


# ------------------------------------------------------------------ grads
def _per_shard_grads(cfg, params, shards_tokens, shards_aux=None):
    """shards_tokens: (K, rows, S+1) -> gradient leaves stacked (K, ...).

    Sequential lax.map = the honest (s_max+1)-fold redundancy work with
    flat memory (one backward at a time), matching eq. (2)'s cost model.
    shards_aux: optional (K, rows, ...) modality embeddings (VLM/audio).
    """

    def one(args):
        tok, aux = args
        batch = {"tokens": tok}
        if aux is not None:
            batch["aux_inputs"] = aux
        loss_fn = lambda p: train_loss(cfg, p, batch)[0]
        return jax.grad(loss_fn)(params)

    with jax.named_scope("per_shard_grad"):
        return jax.lax.map(one, (shards_tokens, shards_aux))


# ------------------------------------------------- tree combine (baseline)
def _tree_encode(grads_stacked, rows, level_idx):
    """Per-leaf encode: c_j = sum_k rows[level(j), k] * g_j[k]."""
    leaves, treedef = jax.tree.flatten(grads_stacked)
    out = []
    for leaf, li in zip(leaves, level_idx):
        r = rows[li].astype(leaf.dtype)  # (K,)
        out.append(jnp.tensordot(r, leaf, axes=(0, 0)))
    return treedef.unflatten(out)


def _tree_scale(tree, dec_w_rank, level_idx):
    """Per-leaf decode weight a[level(j)] for this rank."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef.unflatten(
        [leaf * dec_w_rank[li].astype(leaf.dtype) for leaf, li in zip(leaves, level_idx)]
    )


# --------------------------------------------------- flat fused combine
def _fused_level_leaves(layout, leaves_nk, b_rows, dec_w_row, li, n_workers,
                        grad_dtype):
    """Fused combine of ONE redundancy level's leaves: per leaf, the
    weighted sum ``Σ (dec_w ⊙ rows / N) · g`` over the (N*K, *shape)
    shard-gradient stack — encode, decode weight, worker sum, and the
    1/N mean in a single streaming pass.

    This is the independently-triggerable unit of the wave-pipelined
    loop (``repro.train.wave``): level ``li`` combines the instant its
    block decodes, without waiting for higher-redundancy levels.
    ``dec_w_row`` is that level's (N,) decode-weight row.  Returns
    ``{leaf_id: decoded mean grad}`` for the level's leaves.
    """
    inv_n = jnp.float32(1.0 / n_workers)
    w = (dec_w_row[:, None] * b_rows[:, li, :]).reshape(-1)         # (N*K,)
    out = {}
    for j in layout.level_leaves[li]:
        shape = tuple(layout.leaf_shapes[j])
        g = leaves_nk[j].reshape(w.shape + shape)              # (N*K, *shape)
        with jax.named_scope("gc_combine"):
            y = ops.encode_decode(inv_n, w, g)
        if grad_dtype is not None:
            y = y.astype(grad_dtype)
        out[j] = y
    return out


def _fused_leaf_combine(layout, leaves_nk, b_rows, dec_w, n_workers,
                        grad_dtype):
    """All-workers fused combine across every level (one
    ``_fused_level_leaves`` per level — identical per-leaf math).

    leaves_nk: flat-order leaves shaped (N, K, *shape).  Returns the
    decoded mean gradient leaves in flat order.
    """
    out = [None] * layout.n_leaves
    for li in range(layout.n_levels):
        for j, y in _fused_level_leaves(layout, leaves_nk, b_rows, dec_w[li],
                                        li, n_workers, grad_dtype).items():
            out[j] = y
    return out


def combine_level(plan: Plan, grads_stacked, level_idx: int, dec_w_row, *,
                  grad_dtype=None) -> dict:
    """Decode ONE redundancy level of already-computed per-shard grads.

    The per-level combine stage of the wave-pipelined loop: callable the
    instant level ``level_idx`` (an index into ``plan.used_levels``)
    reaches its (N - s)-th delivery, before higher levels land.
    ``grads_stacked``: pytree with leaves (N, K, *shape); ``dec_w_row``:
    that level's (N,) decode-weight row.  Returns ``{flat leaf id:
    decoded mean gradient}`` covering exactly the level's leaves; the
    union over all levels equals ``combine_grads(..., pipeline='flat')``.
    """
    leaves, _ = jax.tree.flatten(grads_stacked)
    layout = _require_layout(plan)
    if not 0 <= level_idx < layout.n_levels:
        raise ValueError(f"level_idx {level_idx} out of range "
                         f"[0, {layout.n_levels})")
    return _fused_level_leaves(
        layout, leaves, jnp.asarray(plan.b_rows, jnp.float32),
        jnp.asarray(dec_w_row, jnp.float32), level_idx, plan.n_workers,
        grad_dtype)


def _fused_rank_levels(layout, leaves_k, rows_rank, dec_w_rank, denom,
                       grad_dtype):
    """One rank's decode-weighted coded contribution, packed into the
    plan's per-level flat buffers (the collective's data structure).

    leaves_k: flat-order leaves shaped (K, *shape) — this rank's
    per-shard grads.  Per leaf, the fused kernel streams the (K, *shape)
    stack once, in the layout the backward wrote it; the results are
    laid out at the layout's static offsets (lane-aligned, N-divisible
    zero tail), ready for one psum / psum_scatter per level.  bf16
    ``grad_dtype`` is applied to the packed buffer, halving the
    collective bytes.

    Named scopes: ``gc_combine`` is the kernel call alone; the flatten
    of each result, the zero tail, the concatenation and the cast are
    ``gc_pack``.
    """
    bufs = []
    for li in range(layout.n_levels):
        a = dec_w_rank[li] / denom           # decode weight, mean folded
        row = rows_rank[li]                  # (K,) coding row
        parts = []
        for j in layout.level_leaves[li]:
            with jax.named_scope("gc_combine"):
                y = ops.encode_decode(a, row, leaves_k[j])
            with jax.named_scope("gc_pack"):
                parts.append(y.reshape(-1))
        with jax.named_scope("gc_pack"):
            pad = layout.level_sizes[li] - layout.level_used[li]
            if pad:
                parts.append(jnp.zeros((pad,), parts[0].dtype))
            buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if grad_dtype is not None:
                buf = buf.astype(grad_dtype)
        bufs.append(buf)
    return bufs


def combine_grads(plan: Plan, grads_stacked, dec_w, *, pipeline: str = "flat",
                  grad_dtype=None):
    """Decode-weighted mean combine of already-computed per-shard grads.

    grads_stacked: pytree with leaves (N, K, *shape) — worker-major
    stack of the (s_max+1) per-shard gradients.  dec_w: (n_used, N).
    Returns the decoded mean gradient pytree (== the uncoded mean
    gradient for any straggler realization dec_w encodes).

    This is the combine stage alone — the bench/test surface for the
    ``flat`` (fused single-pass) vs ``tree`` (per-leaf loop) pipelines;
    the training grad fns interleave it with the per-shard backward.
    """
    leaves, treedef = jax.tree.flatten(grads_stacked)
    n_workers = plan.n_workers
    b_rows = jnp.asarray(plan.b_rows, jnp.float32)
    dec_w = jnp.asarray(dec_w, jnp.float32)
    if pipeline == "flat":
        layout = _require_layout(plan)
        out = _fused_leaf_combine(layout, leaves, b_rows, dec_w, n_workers,
                                  grad_dtype)
        return treedef.unflatten(out)
    if pipeline != "tree":
        raise ValueError(f"unknown pipeline {pipeline!r}; "
                         "expected 'flat' or 'tree'")
    level_idx = plan.level_index()

    def worker(n):
        per_worker = treedef.unflatten([l[n] for l in leaves])
        with jax.named_scope("gc_combine"):
            c = _tree_encode(per_worker, b_rows[n], level_idx)
            c = _tree_scale(c, dec_w[:, n], level_idx)
        if grad_dtype is not None:  # mirror the spmd reduce: cast, then sum
            c = jax.tree.map(lambda l: l.astype(grad_dtype), c)
        return c

    contribs = jax.lax.map(worker, jnp.arange(n_workers))
    summed = jax.tree.map(lambda l: l.sum(0), contribs)
    return jax.tree.map(lambda l: l / n_workers, summed)


def _require_layout(plan: Plan):
    if plan.flat_layout is None:
        raise ValueError(
            "pipeline='flat' needs plan.flat_layout — build the plan from "
            "a parameter pytree (Plan.build(params, env, ...)); plans built "
            "from bare cost vectors carry no leaf shapes (use "
            "pipeline='tree')")
    return plan.flat_layout


def _resolve_pipeline(pipeline: str, plan: Plan) -> str:
    if pipeline == "auto":
        return "flat" if plan.flat_layout is not None else "tree"
    if pipeline == "flat":
        _require_layout(plan)
        return "flat"
    if pipeline == "tree":
        return "tree"
    raise ValueError(f"unknown pipeline {pipeline!r}; "
                     "expected 'auto', 'flat', or 'tree'")


def _scatter_dims(param_shapes, param_axes, n_workers: int):
    """Per-leaf dimension for psum_scatter: prefer the fsdp 'embed' axis,
    else the first dim divisible by N; None -> plain psum for that leaf.
    (tree pipeline only — the flat pipeline scatters the N-divisible
    level buffers, no per-leaf divisibility hunt.)"""
    shapes = jax.tree.leaves(param_shapes)
    if param_axes is not None:
        axes = jax.tree.leaves(param_axes,
                               is_leaf=lambda v: hasattr(v, "axes") or isinstance(v, tuple))
    else:
        axes = [None] * len(shapes)
    out = []
    for shp, ax in zip(shapes, axes):
        dims = tuple(shp.shape if hasattr(shp, "shape") else shp)
        pick = None
        if ax is not None:
            for i, name in enumerate(tuple(ax)):
                if name == "embed" and dims[i] % n_workers == 0:
                    pick = i
                    break
        if pick is None:
            for i, dsz in enumerate(dims):
                if dsz % n_workers == 0 and dsz >= n_workers:
                    pick = i
                    break
        out.append(pick)
    return out


def make_coded_grad_fn(cfg, plan: CodingPlan, *, mesh=None, data_axis: str = "data",
                       mode: str = "sim", reduce_mode: str = "psum",
                       grad_dtype=None, param_shapes=None,
                       param_axes=None, pipeline: str = "auto") -> Callable:
    """Returns grad_fn(params, worker_batches, dec_w, worker_aux=None)
    -> decoded mean grads.

    worker_batches: (N, K, rows, S+1) tokens — the cyclic allocation from
    ``data.pipeline.coded_worker_batches`` (sharded P(data_axis) on axis
    0 in spmd mode).  dec_w: (n_used, N) decode weights for this step's
    straggler realization.  worker_aux: optional (N, K, rows, ...)
    modality embeddings for VLM/audio archs.

    pipeline: 'flat' (fused single-pass combine through the plan's
    ``FlatLayout`` — the hot path), 'tree' (legacy per-leaf loop), or
    'auto' (flat when the plan carries a layout, i.e. it was built from
    a parameter pytree).

    Beyond-paper options (spmd mode):
      reduce_mode='psum_scatter' — the decode-weighted reduction emits
        grads SHARDED over the data axis (reduce-scatter instead of
        all-reduce: (N-1)/N less collective traffic; exact).  On the
        flat pipeline the N-divisible level buffers make this
        unconditionally available (no param_shapes needed); the tree
        pipeline still needs param_shapes (+ optionally param_axes for
        fsdp alignment) to hunt per-leaf divisible dims.
      grad_dtype=jnp.bfloat16 — cast the coded contribution before the
        reduction (halves collective bytes; small stochastic rounding
        error).  Flat pipeline: one cast of the packed level buffer.
    """
    level_idx = plan.level_index()
    b_rows = jnp.asarray(plan.b_rows, jnp.float32)  # (N, n_used, K)
    n_workers = plan.n_workers
    pipeline = _resolve_pipeline(pipeline, plan)
    layout = plan.flat_layout if pipeline == "flat" else None

    if mode == "sim":
        if pipeline == "flat":

            def grad_fn(params, worker_batches, dec_w, worker_aux=None):
                def worker(n):
                    aux_n = None if worker_aux is None else worker_aux[n]
                    return _per_shard_grads(cfg, params, worker_batches[n],
                                            aux_n)

                g_all = jax.lax.map(worker, jnp.arange(n_workers))
                leaves, treedef = jax.tree.flatten(g_all)  # (N, K, *shape)
                out = _fused_leaf_combine(layout, leaves, b_rows,
                                          jnp.asarray(dec_w, jnp.float32),
                                          n_workers, grad_dtype)
                return treedef.unflatten(out)

            return grad_fn

        def grad_fn(params, worker_batches, dec_w, worker_aux=None):
            def worker(n):
                aux_n = None if worker_aux is None else worker_aux[n]
                g = _per_shard_grads(cfg, params, worker_batches[n], aux_n)
                with jax.named_scope("gc_combine"):
                    c = _tree_encode(g, b_rows[n], level_idx)
                    return _tree_scale(c, dec_w[:, n], level_idx)

            contribs = jax.lax.map(worker, jnp.arange(n_workers))
            summed = jax.tree.map(lambda l: l.sum(0), contribs)
            return jax.tree.map(lambda l: l / n_workers, summed)

        return grad_fn

    # ---- spmd: coding runs across the data-parallel ranks, plain
    # summation across pods.  The region is manual over EVERY mesh axis:
    # XLA's SPMD partitioner aborts the process (``Invalid binary
    # instruction opcode copy``) partitioning this backward under a
    # partial-manual subgroup (data manual, model auto).  Axes beyond
    # data/pod therefore carry replicated copies inside the coded region
    # (no tensor parallelism there) — numerically identical.
    assert mesh is not None
    from repro.dist.sharding import use_mesh

    extra_axes = tuple(a for a in ("pod",) if a in mesh.shape)
    extra_size = 1
    for a in extra_axes:
        extra_size *= mesh.shape[a]
    denom = n_workers * extra_size

    if pipeline == "flat":
        return _make_flat_spmd_grad_fn(
            cfg, layout, b_rows, n_workers, mesh=mesh, data_axis=data_axis,
            extra_axes=extra_axes, denom=denom, reduce_mode=reduce_mode,
            grad_dtype=grad_dtype)

    scatter = None
    out_specs = P()
    if reduce_mode == "psum_scatter":
        if param_shapes is None:
            raise ValueError("psum_scatter needs param_shapes")
        scatter = _scatter_dims(param_shapes, param_axes, n_workers)
        treedef = jax.tree.structure(param_shapes)
        specs = []
        for sd, shp in zip(scatter, jax.tree.leaves(param_shapes)):
            nd = len(shp.shape if hasattr(shp, "shape") else shp)
            if sd is None:
                specs.append(P())
            else:
                entries = [None] * nd
                entries[sd] = data_axis
                specs.append(P(*entries))
        out_specs = jax.tree.unflatten(treedef, specs)

    @jax.named_scope("level_collective")
    def _reduce(tree):
        if grad_dtype is not None:
            tree = jax.tree.map(lambda l: l.astype(grad_dtype), tree)
        if extra_axes:  # sum the pod halves of each shard first
            tree = jax.lax.psum(tree, extra_axes)
        if scatter is None:
            return jax.lax.psum(tree, data_axis)
        leaves, treedef = jax.tree.flatten(tree)
        out = []
        for leaf, sd in zip(leaves, scatter):
            if sd is None:
                out.append(jax.lax.psum(leaf, data_axis))
            else:
                out.append(jax.lax.psum_scatter(leaf, data_axis,
                                                scatter_dimension=sd, tiled=True))
        return treedef.unflatten(out)

    # worker_batches (N, K, rows, S+1): workers over data, rows over pod —
    # each (data, pod) rank holds its shard-half; encode is linear, so
    # c_n = (1/P) * sum_p c_n^p and the decode-weighted psum over
    # (data, pod) recovers the exact global-batch gradient.
    batch_spec = P(data_axis, None, extra_axes if extra_axes else None)

    def manual_fn(params, my_batches, dec_w, my_rows, my_aux=None):
        # my_batches: (1, K, rows/P, S+1); my_rows: (1, n_used, K)
        with use_mesh(mesh, {}, manual=True):
            rank = jax.lax.axis_index(data_axis)
            aux0 = None if my_aux is None else my_aux[0]
            g = _per_shard_grads(cfg, params, my_batches[0], aux0)
            with jax.named_scope("gc_combine"):
                c = _tree_encode(g, my_rows[0], level_idx)
                contrib = _tree_scale(c, dec_w[:, rank], level_idx)
            decoded = _reduce(contrib)
            return jax.tree.map(lambda l: l / denom, decoded)

    def grad_fn(params, worker_batches, dec_w, worker_aux=None):
        if worker_aux is None:
            smapped = jax.shard_map(
                lambda p, wb, dw, rows: manual_fn(p, wb, dw, rows),
                mesh=mesh,
                in_specs=(P(), batch_spec, P(), P(data_axis)),
                out_specs=out_specs,
                check_vma=False,
            )
            return smapped(params, worker_batches, dec_w, b_rows)
        smapped = jax.shard_map(
            manual_fn,
            mesh=mesh,
            in_specs=(P(), batch_spec, P(), P(data_axis), batch_spec),
            out_specs=out_specs,
            check_vma=False,
        )
        return smapped(params, worker_batches, dec_w, b_rows, worker_aux)

    return grad_fn


def _make_flat_spmd_grad_fn(cfg, layout, b_rows, n_workers, *, mesh,
                            data_axis, extra_axes, denom, reduce_mode,
                            grad_dtype) -> Callable:
    """The flat fused spmd path: each rank streams its per-shard grads
    through the fused encode⊙decode matmul into the plan's packed
    per-level buffers, the reduction is ONE collective per level over
    the flat contiguous buffer, and the optimizer tree is unflattened
    once, outside the manual region."""
    from repro.dist.sharding import use_mesh

    if reduce_mode not in ("psum", "psum_scatter"):
        raise ValueError(f"unknown reduce_mode {reduce_mode!r}")
    scatter = reduce_mode == "psum_scatter"
    # level buffers come out replicated (psum) or sharded over the data
    # axis (psum_scatter: layout sizes are N-divisible by construction)
    buf_specs = [P(data_axis) if scatter else P()
                 for _ in range(layout.n_levels)]
    batch_spec = P(data_axis, None, extra_axes if extra_axes else None)

    def manual_fn(params, my_batches, dec_w, my_rows, my_aux=None):
        with use_mesh(mesh, {}, manual=True):
            rank = jax.lax.axis_index(data_axis)
            aux0 = None if my_aux is None else my_aux[0]
            g = _per_shard_grads(cfg, params, my_batches[0], aux0)
            leaves, _ = jax.tree.flatten(g)  # (K, *shape) each
            bufs = _fused_rank_levels(layout, leaves, my_rows[0],
                                      dec_w[:, rank], denom, grad_dtype)
            with jax.named_scope("level_collective"):
                if extra_axes:  # sum the pod halves of each shard first
                    bufs = list(jax.lax.psum(tuple(bufs), extra_axes))
                if scatter:
                    return [jax.lax.psum_scatter(b, data_axis,
                                                 scatter_dimension=0,
                                                 tiled=True)
                            for b in bufs]
                return list(jax.lax.psum(tuple(bufs), data_axis))

    def grad_fn(params, worker_batches, dec_w, worker_aux=None):
        treedef = jax.tree.structure(params)
        dec_w = jnp.asarray(dec_w, jnp.float32)
        if worker_aux is None:
            smapped = jax.shard_map(
                lambda p, wb, dw, rows: manual_fn(p, wb, dw, rows),
                mesh=mesh,
                in_specs=(P(), batch_spec, P(), P(data_axis)),
                out_specs=buf_specs,
                check_vma=False,
            )
            bufs = smapped(params, worker_batches, dec_w, b_rows)
        else:
            smapped = jax.shard_map(
                manual_fn,
                mesh=mesh,
                in_specs=(P(), batch_spec, P(), P(data_axis), batch_spec),
                out_specs=buf_specs,
                check_vma=False,
            )
            bufs = smapped(params, worker_batches, dec_w, b_rows, worker_aux)
        # one unflatten into the optimizer (GSPMD re-shards sliced leaves
        # of scattered buffers as consumers demand)
        with jax.named_scope("gc_unpack"):
            return treedef.unflatten(layout.unpack(bufs))

    return grad_fn


def uncoded_grad_fn(cfg, n_workers: int) -> Callable:
    """Plain data-parallel mean gradient over the same global batch
    (shards stacked (N, rows, S+1)); reference for exactness tests."""

    def grad_fn(params, shards):
        def one(tok):
            loss_fn = lambda p: train_loss(cfg, p, {"tokens": tok})[0]
            return jax.grad(loss_fn)(params)

        g = jax.lax.map(one, shards)
        return jax.tree.map(lambda l: l.sum(0) / n_workers, g)

    return grad_fn
