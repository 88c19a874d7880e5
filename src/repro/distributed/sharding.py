"""Logical-axis sharding: model code names axes, a rule table maps them to mesh.

Model code annotates tensors with *logical* axis names via :func:`lshard`
(e.g. ``lshard(x, 'batch', 'seq', 'embed')``).  A launcher installs a mesh
and a rule table with :func:`use_rules`; outside that context the
annotations are no-ops, so the same model runs unsharded on one CPU device
(smoke tests) and sharded on a 512-chip mesh (dry-run) with zero code
changes.

Two built-in rule tables (see DESIGN.md §5):

  * ``FSDP_SP_RULES`` — the universal baseline: parameters/optimizer state
    2D-sharded over (data, model) [ZeRO-3-style], activations
    batch-sharded over 'data' and sequence-sharded over 'model'
    (Megatron-SP-flavoured).  Legal for every assigned arch regardless of
    head-count divisibility.
  * ``TP_RULES`` — classic tensor parallelism: heads/ffn/experts on
    'model', batch on ('pod','data').  Used by archs whose head counts
    divide the model axis; explored in §Perf hillclimbs.

A logical axis missing from the table (or mapped to None) is replicated.
Mesh axes that do not exist on the installed mesh are dropped from specs,
so the same tables serve the single-pod (data, model) and multi-pod
(pod, data, model) meshes.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


# logical axis -> mesh axis (or tuple of mesh axes)
#
# Parameters are 2D-sharded: contraction-side dims ('embed', 'kv_lora') over
# ('pod','data') [ZeRO-3-style] and output-side dims ('ffn','heads','vocab',
# 'expert') over 'model' — 512-way total on the multi-pod mesh.  Activations
# are batch-sharded over ('pod','data') and sequence-sharded over 'model'
# (Megatron-SP flavour); inside einsums the duplicate-mesh-axis guard in
# _resolve keeps specs legal.
FSDP_SP_RULES = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    "embed": ("pod", "data"),
    "ffn": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "capacity": ("pod", "data"),
    "kv_lora": ("model",),
    "cache_seq": ("model",),
    "cache_batch": ("pod", "data"),
    # the paged serving pool's page axis: physical pages are striped
    # page-aligned over the seq mesh axes (a page lives wholly on one
    # shard), so paged decode can run the same seq-sharded flash-decoding
    # combine as the contiguous cache instead of replicating the pool.
    "pages": ("model",),
    "layers": None,
    "state": None,
}

TP_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "fsdp": ("pod", "data"),
    "ffn": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "capacity": ("pod", "data"),
    "kv_lora": None,
    "cache_seq": None,
    "cache_batch": ("pod", "data"),
    "pages": None,       # TP does not seq-shard: the pool stays replicated
    "layers": None,
    "state": ("model",),
}

RULE_SETS = {"fsdp_sp": FSDP_SP_RULES, "tp": TP_RULES}


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules):
    """Install (mesh, logical rule table) for lshard/make_sharding."""
    if isinstance(rules, str):
        rules = RULE_SETS[rules]
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, dict(rules))
    try:
        yield
    finally:
        _ctx.state = prev


def current_mesh() -> Optional[Mesh]:
    st = getattr(_ctx, "state", None)
    return st[0] if st else None


def mesh_axes_for(name: str) -> Tuple[Optional[Mesh], Tuple[str, ...]]:
    """(mesh, mesh axes) a logical axis maps to under the installed rules.

    Returns (None, ()) outside a rules context, and (mesh, ()) when the
    axis is unmapped/replicated or its mesh axes are absent.  The layers
    use this to decide whether an array family is sharded at all (e.g.
    whether the paged pool gets the shard_map flash-decoding path).
    """
    st = getattr(_ctx, "state", None)
    if st is None:
        return None, ()
    mesh, rules = st
    spec = _resolve((name,), mesh, rules)
    ax = spec[0] if len(spec) else None
    if ax is None:
        return mesh, ()
    return mesh, (ax,) if isinstance(ax, str) else tuple(ax)


def _resolve(names: Sequence[Optional[str]], mesh: Mesh, rules) -> P:
    """Map logical names to a PartitionSpec, dropping absent mesh axes and
    never assigning one mesh axis twice (first logical axis wins)."""
    used = set()
    spec = []
    for nm in names:
        if nm is None:
            spec.append(None)
            continue
        target = rules.get(nm)
        if target is None:
            spec.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        axes = tuple(a for a in target
                     if a in mesh.axis_names and a not in used)
        used.update(axes)
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(axes)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def make_spec(names: Sequence[Optional[str]]) -> Optional[P]:
    st = getattr(_ctx, "state", None)
    if st is None:
        return None
    return _resolve(names, st[0], st[1])


def make_sharding(names: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    st = getattr(_ctx, "state", None)
    if st is None:
        return None
    mesh, rules = st
    return NamedSharding(mesh, _resolve(names, mesh, rules))


def make_array_sharding(shape, names) -> Optional[NamedSharding]:
    """Like make_sharding but with the per-dim divisibility fallback
    (dims that don't divide their mesh axes are replicated)."""
    st = getattr(_ctx, "state", None)
    if st is None:
        return None
    mesh, rules = st
    spec = _resolve(names, mesh, rules)
    spec = P(*[
        ax if ax is not None and _divisible((shape[i],), P(ax), mesh)
        else None
        for i, ax in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec)))])
    return NamedSharding(mesh, spec)


def _divisible(shape, spec: P, mesh: Mesh) -> bool:
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            continue
        axes = (ax,) if isinstance(ax, str) else ax
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if dim % size:
            return False
    return True


def lshard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op w/o context).

    Falls back to replication on any dim whose size does not divide the
    assigned mesh axes (e.g. 2 KV heads on a 16-way model axis) — the rule
    tables stay total over every assigned architecture.
    """
    st = getattr(_ctx, "state", None)
    if st is None:
        return x
    mesh, rules = st
    assert len(names) == x.ndim, (names, x.shape)
    spec = _resolve(names, mesh, rules)
    if not _divisible(x.shape, spec, mesh):
        spec = P(*[
            ax if ax is not None and _divisible(
                (x.shape[i],), P(ax), mesh) else None
            for i, ax in enumerate(
                tuple(spec) + (None,) * (x.ndim - len(spec)))])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
