"""Logical-axis sharding rules (GSPMD partitioning policy).

Every parameter / activation names its dims with *logical* axes (see
``models.common.Maker``); this module maps those names onto mesh axes:

  train (RULES):
    batch            -> all pure-data axes, jointly: ('pod', 'data')
    heads/kv_heads,
    ffn/expert_ffn,
    vocab, ssm_inner -> 'model'   (tensor parallelism)
    embed            -> 'data'    (FSDP: shard weights over data, gather
                                   at use)
    kvseq/seq        -> 'model'   (sequence fallback when the preferred
                                   TP axis is taken or indivisible, e.g.
                                   kv_heads % model_size != 0)
    experts          -> unsharded (TP-inside-expert policy: each expert's
                                   ffn dim is TP-sharded instead, keeping
                                   dispatch/combine row-local)

  inference (INFERENCE_RULES): identical minus the FSDP entry -- serving
  replicates weights over 'data' (no gather-at-use on the decode path).

An axis is only assigned when the dim is divisible by the mesh axis size
and the mesh axis is not already used by another dim of the same tensor.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# name -> (priority, candidate mesh axes).  Lower priority wins contended
# mesh axes; candidates are tried in order; tuple candidates are joint
# (multi-axis) shardings.
RULES: dict[str, tuple[int, tuple]] = {
    "batch":      (0, (("pod", "data"),)),
    "kv_heads":   (1, ("model",)),
    "heads":      (1, ("model",)),
    "vocab":      (1, ("model",)),
    "ffn":        (1, ("model",)),
    "expert_ffn": (1, ("model",)),
    "ssm_inner":  (1, ("model",)),
    "embed":      (2, ("data",)),
    "kvseq":      (3, ("model",)),
    "seq":        (3, ("model",)),
}

#: Serving drops FSDP: weight-bearing 'embed' dims replicate over 'data'.
INFERENCE_RULES: dict[str, tuple[int, tuple]] = {
    k: v for k, v in RULES.items() if k != "embed"
}

_DATA_AXES = ("pod", "data")

# ---------------------------------------------------------------------------
# Program-spine axis policy (GEMM rank -> array-mesh axis)
# ---------------------------------------------------------------------------

#: Which GEMM rank to split across an array mesh, in preference order.
#: This is the model-world policy above projected onto the one contraction
#: every lowered Program is: N is the weight's free rank (ffn / heads /
#: vocab -> 'model', i.e. tensor parallelism -- each array holds a weight
#: column slice), M is the streamed token rank (batch -> data
#: parallelism), and K is the contraction (splittable only with a
#: reduction epilogue, so it is the last resort).
GEMM_AXIS_RULES: tuple[str, ...] = ("n", "m", "k")


def gemm_shard_axis(m: int, k: int, n: int, n_arrays: int,
                    tiles: dict[str, int] | None = None,
                    rules: tuple[str, ...] = GEMM_AXIS_RULES) -> str:
    """Pick the host GEMM rank ('m' | 'n' | 'k') to split over
    ``n_arrays`` arrays.

    ``tiles`` optionally gives the lowered Program's tile count along
    each host rank.  Splitting a rank the tile loop barely iterates
    (e.g. N when the whole N extent fits one tile) *replicates* the
    other ranks' instruction and load traffic on every array instead of
    partitioning it, so ranks with at least ``n_arrays`` tiles are
    preferred -- that is what keeps per-array MINISA traffic summing to
    the single-array total.  Within the surviving candidates the policy
    mirrors :func:`spec_for`'s divisibility discipline: an exactly
    divisible rank first, then any rank wide enough to occupy every
    array, then the widest rank."""
    if n_arrays < 2:
        return rules[0]
    dims = {"m": m, "k": k, "n": n}
    order = list(rules)
    if tiles is not None:
        partitioning = [ax for ax in order
                        if tiles.get(ax, 0) >= n_arrays]
        if partitioning:
            order = partitioning
        elif max(tiles.values(), default=0) > 1:
            # no rank has a tile per array: the most-tiled rank still
            # partitions the largest share of the instruction stream
            # (ties resolve in rules order)
            best = max(tiles.values())
            order = [ax for ax in order if tiles.get(ax, 0) == best]
    for ax in order:
        if dims[ax] >= n_arrays and dims[ax] % n_arrays == 0:
            return ax
    for ax in order:
        if dims[ax] >= n_arrays:
            return ax
    return max(order, key=lambda ax: dims[ax])


def _mesh_sizes(mesh) -> dict[str, int]:
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def spec_for(axes: tuple[str, ...], shape: tuple[int, ...], mesh,
             rules: dict | None = None) -> P:
    """Logical axes + concrete shape -> PartitionSpec on ``mesh``."""
    rules = RULES if rules is None else rules
    sizes = _mesh_sizes(mesh)
    assign: list = [None] * len(axes)
    used: set[str] = set()
    order = sorted(range(len(axes)),
                   key=lambda i: (rules[axes[i]][0]
                                  if axes[i] in rules else 99, i))
    for i in order:
        name = axes[i]
        if name not in rules:
            continue
        for cand in rules[name][1]:
            cand = (cand,) if isinstance(cand, str) else tuple(cand)
            present = tuple(a for a in cand if a in sizes and a not in used)
            if not present:
                continue
            total = math.prod(sizes[a] for a in present)
            if total <= 0 or shape[i] % total:
                continue
            assign[i] = present[0] if len(present) == 1 else present
            used.update(present)
            break
    while assign and assign[-1] is None:
        assign.pop()
    return P(*assign)


# ---------------------------------------------------------------------------
# Pytree helpers (params / optimizer / batch shardings)
# ---------------------------------------------------------------------------

def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(a, str) for a in x)


def tree_shardings(axes_tree, shapes_tree, mesh, inference: bool = False):
    """Matching pytrees of logical axes + abstract shapes -> NamedShardings.

    The two trees come from running the same model-definition code in
    ``axes`` and ``eval_shape`` mode, so they are leaf-for-leaf aligned.
    """
    rules = INFERENCE_RULES if inference else RULES
    axes_leaves = jax.tree_util.tree_flatten(
        axes_tree, is_leaf=_is_axes_leaf)[0]
    shape_leaves, sdef = jax.tree_util.tree_flatten(shapes_tree)
    if len(axes_leaves) != len(shape_leaves):
        raise ValueError(
            f"axes/shape trees disagree: {len(axes_leaves)} vs "
            f"{len(shape_leaves)} leaves")
    out = [NamedSharding(mesh, spec_for(a, tuple(s.shape), mesh, rules))
           for a, s in zip(axes_leaves, shape_leaves)]
    return jax.tree_util.tree_unflatten(sdef, out)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _batch_spec(shape: tuple[int, ...], sizes: dict[str, int]) -> P:
    axes = tuple(a for a in _DATA_AXES if a in sizes)
    if not shape or not axes:
        return P()
    total = math.prod(sizes[a] for a in axes)
    if shape[0] % max(total, 1):
        return P()
    return P(axes if len(axes) > 1 else axes[0])


def batch_sharding(mesh, batch_spec):
    """Batch pytree (arrays or ShapeDtypeStructs) -> NamedShardings that
    shard the leading (batch) dim over the pure-data axes."""
    sizes = _mesh_sizes(mesh)
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh,
                                   _batch_spec(tuple(leaf.shape), sizes)),
        batch_spec)


# ---------------------------------------------------------------------------
# In-graph constraints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------

def _constrain(x, spec_fn):
    """Apply with_sharding_constraint(x, spec_fn(sizes)) under the ambient
    mesh (``jax.set_mesh``); identity when no mesh is set (single-process
    smoke tests)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec_fn(_mesh_sizes(mesh)))


def constrain_batch(x, extra: tuple = ()):
    """Anchor dim 0 to the data axes; ``extra`` names trailing dims after
    the batch dim ('' / None = unsharded)."""
    def spec_fn(sizes):
        entries = [_batch_spec(tuple(x.shape), sizes)[0]
                   if _batch_spec(tuple(x.shape), sizes) else None]
        for i, name in enumerate(extra):
            dim = 1 + i
            if (name and name in sizes and dim < x.ndim
                    and x.shape[dim] % sizes[name] == 0):
                entries.append(name)
            else:
                entries.append(None)
        return P(*entries)
    return _constrain(x, spec_fn)


def constrain_seq_scores(scores):
    """Attention-score anchor: batch over data, KV-sequence (last dim)
    over 'model' (decode-path sequence parallelism)."""
    def spec_fn(sizes):
        entries: list = [None] * scores.ndim
        bspec = _batch_spec(tuple(scores.shape), sizes)
        if bspec:
            entries[0] = bspec[0]
        if ("model" in sizes and scores.ndim > 1
                and scores.shape[-1] % sizes["model"] == 0):
            entries[-1] = "model"
        return P(*entries)
    return _constrain(scores, spec_fn)


def constrain_rows_model(table):
    """Anchor a (rows, feature) table to rows-sharded / feature-replicated
    before contractions (vocab-parallel embedding gather, §Perf iter 2)."""
    def spec_fn(sizes):
        if "model" in sizes and table.shape[0] % sizes["model"] == 0:
            return P("model")
        return P()
    return _constrain(table, spec_fn)
