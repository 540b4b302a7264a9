"""Sharding rules: logical-to-mesh layout for params, optimizer state,
activations and KV caches.

Everything here is *rule-based with divisibility fallbacks*: a dimension
is sharded on a mesh axis only when it divides the axis size product;
otherwise the rule degrades (expert dim -> expert-internal ff; sharded ->
replicated) rather than failing. That is what lets one set of rules cover
every (arch x shape x mesh) cell of the dry-run grid.

Activation constraints (``constrain``) use logical axis names:
  "B" — global batch     -> the mesh batch axes for the active context
  "S" — sequence         -> "model" under sequence parallelism, else none
  "M" — memory/cache seq -> "model" (the serving cache layout)
  None — unsharded

Outside an ``activation_context`` (tests, single-device smoke runs)
``constrain`` is the identity, so model code can call it unconditionally.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


# ----------------------------------------------------------------- mesh utils
def axis_size(mesh, name: str) -> int:
    """Size of a mesh axis; absent axes count as size 1."""
    return int(dict(mesh.shape).get(name, 1))


def make_abstract_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]):
    """Device-free mesh for spec-only work."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def batch_axes(mesh, global_batch: int) -> Tuple[str, ...]:
    """Greedy batch-axis assignment: take mesh axes (pod, data) in order
    while the global batch stays divisible by the joint size."""
    axes = []
    prod = 1
    for name in ("pod", "data"):
        sz = axis_size(mesh, name)
        if sz <= 1 or name not in mesh.axis_names:
            continue
        if global_batch % (prod * sz) == 0:
            axes.append(name)
            prod *= sz
    return tuple(axes)


def to_shardings(mesh, specs):
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _divisible(dim: int, mesh, axes) -> bool:
    prod = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        prod *= axis_size(mesh, a)
    return dim % prod == 0


def _spec(dim: int, axes) -> P:
    """PartitionSpec sharding ``dim`` on ``axes``, trailing dims implicit."""
    entries = [None] * (dim + 1)
    entries[dim] = axes
    return P(*entries)


# ------------------------------------------------------------- param layout
def _param_rule(key: str, shape: Tuple[int, ...], mesh) -> P:
    """One leaf -> PartitionSpec. ``key`` is the '/'-joined tree path."""
    parts = key.split("/")
    name = parts[-1]
    ndim = len(shape)
    m = "model"

    def ok(d):
        return _divisible(shape[d], mesh, m)

    if name == "scale" or ndim <= 1:
        return P()
    if "experts" in parts:
        # (stack?, E, ...): experts on model when E divides; else shard
        # expert-internal ff (last dim for wi, -2 for wo)
        e = ndim - 4 if name == "wi" else ndim - 3
        if e >= 0 and ok(e):
            return _spec(e, m)
        f = ndim - 1 if name == "wi" else ndim - 2
        if ok(f):
            return _spec(f, m)
        return P()
    if name in ("wq", "wk", "wv"):          # (stack?, d, H, hd): heads
        h = ndim - 2
        return _spec(h, m) if ok(h) else P()
    if name in ("bq", "bk", "bv"):          # (stack?, H, hd): heads
        h = ndim - 2
        return _spec(h, m) if ok(h) else P()
    if name == "wo" and "attn" in parts:    # (stack?, H, hd, d): heads
        h = ndim - 3
        return _spec(h, m) if ok(h) else P()
    if name == "wi":                        # (stack?, d, 2, ff): ff
        f = ndim - 1
        return _spec(f, m) if ok(f) else P()
    if name == "wo":                        # (stack?, ff, d): ff
        f = ndim - 2
        return _spec(f, m) if ok(f) else P()
    if name == "table" or parts[0] == "embed":      # (vocab, d): vocab
        return _spec(0, m) if ok(0) else P()
    if name == "head" or parts[-1] == "head":       # (d, vocab): vocab
        f = ndim - 1
        return _spec(f, m) if ok(f) else P()
    if name in ("w_x", "w_z", "conv_x_w", "conv_x_b", "out_norm"):
        f = ndim - 1                        # mamba: channel (d_inner)
        return _spec(f, m) if ok(f) else P()
    if name == "out_proj":                  # (stack?, d_inner, d)
        f = ndim - 2
        return _spec(f, m) if ok(f) else P()
    return P()                              # small / unknown: replicate


def _walk_specs(tree, mesh, rule):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in flat:
        key = "/".join(str(p.key) if hasattr(p, "key") else str(p.idx)
                       for p in path)
        specs.append(rule(key, tuple(leaf.shape), mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def params_pspecs(cfg, params_shape, mesh):
    """PartitionSpec tree for the model parameters."""
    return _walk_specs(params_shape, mesh, _param_rule)


def opt_state_pspecs(cfg, opt_shape, mesh, zero_pod: bool = False):
    """Optimizer state follows its parameter's layout; with ``zero_pod``
    the moments are additionally ZeRO-sharded over the pod axis on their
    leading dim when divisible."""
    def rule(key, shape, mesh_):
        parts = key.split("/")
        if parts[0] in ("m", "v") and len(parts) > 1:
            spec = _param_rule("/".join(parts[1:]), shape, mesh_)
            if zero_pod and shape and axis_size(mesh_, "pod") > 1:
                entries = list(tuple(spec)) + [None] * (len(shape)
                                                        - len(tuple(spec)))
                if entries[0] is None and _divisible(shape[0], mesh_, "pod"):
                    entries[0] = "pod"
                    return P(*entries)
            return spec
        return P()                          # step counter etc.
    return _walk_specs(opt_shape, mesh, rule)


# --------------------------------------------------------- batch/cache layout
def train_batch_pspecs(cfg, mesh, batch):
    """Input batch dict: shard the batch dim over the mesh batch axes.
    mrope-style (3, B, S) position arrays carry a leading section dim."""
    def rule(key, shape, mesh_):
        if len(shape) >= 2 and shape[0] == 3 and getattr(
                cfg, "mrope_sections", None):
            b = shape[1]
            ax = batch_axes(mesh_, b)
            return P(None, ax if ax else None)
        if not shape:
            return P()
        ax = batch_axes(mesh_, shape[0])
        return P(ax if ax else None)
    return _walk_specs(batch, mesh, rule)


def cache_pspecs(cfg, cache_shape, mesh, batch: int, mode: str = "seq"):
    """KV/state cache layout. Leaves look like (stack, B, S, H, hd) for
    attention (or (stack, B, S, dc) for MLA; (stack, B, K, d) for conv
    state). Batch shards over the batch axes; in ``seq`` mode the
    sequence dim takes "model" plus any batch axes left idle (the B=1
    long-context layout); ``heads``/``hd`` shard those dims instead."""
    bax = batch_axes(mesh, batch)

    def rule(key, shape, mesh_):
        if len(shape) < 3:
            return P()
        entries: list = [None] * len(shape)
        if _divisible(shape[1], mesh_, bax) and bax:
            entries[1] = bax if len(bax) > 1 else bax[0]
        idle = tuple(a for a in ("data",) if a not in bax
                     and axis_size(mesh_, a) > 1)
        if mode == "heads" and len(shape) >= 4:
            if _divisible(shape[3], mesh_, "model"):
                entries[3] = "model"
        elif mode == "hd" and len(shape) >= 5:
            if _divisible(shape[4], mesh_, "model"):
                entries[4] = "model"
        else:                               # "seq"
            seq_axes = idle + ("model",) if not bax else ("model",)
            if _divisible(shape[2], mesh_, seq_axes):
                entries[2] = seq_axes if len(seq_axes) > 1 else seq_axes[0]
            elif _divisible(shape[2], mesh_, "model"):
                entries[2] = "model"
        return P(*entries)
    return _walk_specs(cache_shape, mesh, rule)


# ------------------------------------------------------ activation constraints
_ctx = threading.local()


@contextlib.contextmanager
def activation_context(mesh, global_batch: int, seq_parallel: bool = False):
    """Install the logical-axis mapping used by ``constrain`` during
    lowering. Model code runs unchanged outside the context (identity)."""
    prev = getattr(_ctx, "state", None)
    _ctx.state = {"mesh": mesh, "batch_axes": batch_axes(mesh, global_batch),
                  "seq_parallel": seq_parallel}
    try:
        yield
    finally:
        _ctx.state = prev


def constrain(x, *axes):
    """with_sharding_constraint on logical axes; identity with no context."""
    state = getattr(_ctx, "state", None)
    if state is None:
        return x
    mesh = state["mesh"]
    entries = []
    for dim, ax in zip(x.shape, axes):
        if ax == "B":
            bax = state["batch_axes"]
            ok = bax and _divisible(dim, mesh, bax)
            entries.append((bax if len(bax) > 1 else bax[0]) if ok else None)
        elif ax == "S":
            ok = state["seq_parallel"] and _divisible(dim, mesh, "model")
            entries.append("model" if ok else None)
        elif ax == "M":
            entries.append("model" if _divisible(dim, mesh, "model") else None)
        else:
            entries.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))
