"""Logical-axis sharding: rules, pspecs, and the (mesh, rules) context.

Model code names *logical* axes ("batch", "embed", "heads", ...); the
mapping onto *mesh* axes ("pod", "data", "model") lives here, in one
rules dict, so a config switch (fsdp, shard_vocab, ...) never touches a
layer.  The active (mesh, rules) pair is ambient state installed with
``use_mesh`` around tracing; ``shard`` reads it and emits a sharding
constraint, or is the identity when no mesh is active (single-device
tests, examples).

  rules: dict logical-name -> tuple of candidate mesh axes, in order of
  preference.  ``pspec_for_axes`` consumes them greedily per dim, skipping
  mesh axes that are absent, already used by an earlier dim, or that do
  not divide the dim size (GSPMD would force replication anyway).

Partial-manual regions (shard_map over 'data'/'pod') re-enter with
``strip_rules(rules, manual_axes)`` so inner constraints only mention the
remaining auto axes.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "make_rules",
    "strip_rules",
    "pspec_for_axes",
    "shard",
    "use_mesh",
    "current_mesh",
    "current_rules",
]


# --------------------------------------------------------------------- rules
def make_rules(cfg=None) -> dict:
    """Logical-axis -> mesh-axes rules for a config (or the defaults).

    * activations batch over ("pod", "data") — whichever exist in the mesh;
    * contraction/width dims over "model" (tensor parallel);
    * params replicated unless ``cfg.fsdp`` (then 'embed' shards over
      'data' — the fsdp axis — wherever divisible);
    * 'vocab'/'experts' over 'model' unless the config opts out.
    """
    rules = {
        "batch": ("pod", "data"),
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "expert_mlp": ("model",),
        "d_inner": ("model",),
        "experts": ("model",),
        "vocab": ("model",),
        "embed": (),
    }
    if cfg is not None:
        if getattr(cfg, "fsdp", False):
            rules["embed"] = ("data",)
        if not getattr(cfg, "shard_vocab", True):
            rules["vocab"] = ()
        if not getattr(cfg, "shard_experts", True):
            rules["experts"] = ()
    return rules


def strip_rules(rules: dict, axes: set) -> dict:
    """Drop the given *mesh* axes from every rule (for manual regions)."""
    axes = set(axes)
    return {k: tuple(a for a in v if a not in axes) for k, v in rules.items()}


# ------------------------------------------------------------------- context
class _Ctx(threading.local):
    def __init__(self):
        self.stack: list = []


_CTX = _Ctx()


@contextmanager
def use_mesh(mesh, rules: dict, *, manual: bool = False):
    """Install (mesh, rules) as the ambient sharding context.

    ``manual=True`` marks a shard_map region: ``shard`` becomes the
    identity inside it — its constraints are layout hints, not
    semantics.
    """
    _CTX.stack.append((mesh, dict(rules), manual))
    try:
        yield
    finally:
        _CTX.stack.pop()


def current_mesh():
    return _CTX.stack[-1][0] if _CTX.stack else None


def current_rules() -> dict:
    return _CTX.stack[-1][1] if _CTX.stack else {}


def _in_manual_region() -> bool:
    return bool(_CTX.stack) and _CTX.stack[-1][2]


# --------------------------------------------------------------------- specs
def pspec_for_axes(axes, shape) -> P:
    """PartitionSpec for logical ``axes`` of an array of ``shape``.

    Consults the ambient (mesh, rules).  Per dim, candidate mesh axes are
    taken in rule order and accepted while present in the mesh, unused by
    an earlier dim, and dividing the dim size; multiple accepted axes
    form a tuple entry (e.g. batch over ('pod', 'data')).
    """
    mesh = current_mesh()
    rules = current_rules()
    if mesh is None:
        return P(*([None] * len(tuple(axes))))
    used: set = set()
    entries = []
    for name, dim in zip(tuple(axes), tuple(shape)):
        picked = []
        size = 1
        for mesh_axis in rules.get(name, ()):
            if mesh_axis not in mesh.shape or mesh_axis in used:
                continue
            nxt = size * mesh.shape[mesh_axis]
            if int(dim) % nxt != 0:
                continue
            picked.append(mesh_axis)
            size = nxt
        used.update(picked)
        if not picked:
            entries.append(None)
        elif len(picked) == 1:
            entries.append(picked[0])
        else:
            entries.append(tuple(picked))
    return P(*entries)


def shard(x, *axes):
    """Constrain ``x`` to the rules' sharding for its logical ``axes``.

    Identity when no mesh is active or the spec is fully replicated.
    Under tracing this is a sharding constraint; on concrete arrays it
    places the value (cache/state init under ``use_mesh``).
    """
    mesh = current_mesh()
    if mesh is None or _in_manual_region():
        return x
    spec = pspec_for_axes(axes, x.shape)
    if all(e is None for e in spec):
        return x
    sharding = NamedSharding(mesh, spec)
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)
