"""The comparison that decides a run's ``correct``.

Three numbers compare the program's first three steps with the plain
reference (``reference.py``) from the same seed and batches, and a fourth
covers the window:

``loss_gap``
    the largest relative gap of a step's loss, over the three steps;
``grad_gap``
    over the leaves, the largest gap between the norm of the program's
    first gradient as its optimizer got it (after clipping; read from its
    AdamW first moment after one step, m1 = (1 - beta1) g) and the norm of
    the reference's, over the larger of the reference's norm of that leaf
    and of the median leaf;
``change_gap``
    the same for the change of the weights over the three steps, leaving
    out the leaves whose reference gradient is under a thousandth of the
    median leaf's (they move under Adam by round-off alone, as a key bias
    does under softmax where no rotary embedding follows it);
``nonfinite_losses``
    the window's steps whose loss is not finite (limit 0).

A number is compared only when the cell's limits file names it.
"""
from __future__ import annotations

import itertools
import math

import jax
import numpy as np

EXCLUDE_BELOW = 1e-3  # of the median leaf's reference gradient norm


def leaf_paths(tree) -> list[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(k.key) for k in path) for path, _ in paths]


def by_path(tree) -> dict:
    return dict(zip(leaf_paths(tree), jax.tree.leaves(tree)))


def norm(x: np.ndarray) -> float:
    """Euclidean norm, summed in float64 a slice at a time."""
    flat = np.asarray(x).reshape(-1)
    step = 1 << 24
    return math.sqrt(sum(float(np.dot(c, c)) for c in
                         (flat[i:i + step].astype(np.float64)
                          for i in range(0, flat.size, step))))


def change_norms(before: dict, after: dict) -> dict:
    return {k: norm(np.asarray(after[k], np.float32) - np.asarray(before[k], np.float32))
            for k in before}


# -- the program's ZeRO-2 optimizer state, read back as leaves ------------------------
def themis_flat(m: np.ndarray, orders, spec_axes, axis_sizes: dict) -> np.ndarray:
    """The flat vector held as ``(chunks, per_chunk)`` in the reduce-
    scattered layout, in ``ravel_pytree`` order.

    Column block ``b`` of the global array is the shard of the device whose
    mesh indices, over ``spec_axes`` row-major, give ``b``.  What that
    device holds of chunk ``c`` is block ``k`` of the chunk, where ``k`` is
    its indices over the chunk's axis order ``orders[c]``, row-major."""
    spec_axes = (spec_axes,) if isinstance(spec_axes, str) else tuple(spec_axes or ())
    world = math.prod(axis_sizes[a] for a in spec_axes)
    n_chunks, per_chunk = m.shape
    if world == 1:
        return m.reshape(-1)
    blk = per_chunk // world
    out = np.empty_like(m)
    for idx in itertools.product(*(range(axis_sizes[a]) for a in spec_axes)):
        at = dict(zip(spec_axes, idx))
        b = np.ravel_multi_index(idx, [axis_sizes[a] for a in spec_axes])
        for c, order in enumerate(orders):
            k = np.ravel_multi_index([at[a] for a in order],
                                     [axis_sizes[a] for a in order])
            out[c, k * blk:(k + 1) * blk] = m[c, b * blk:(b + 1) * blk]
    return out.reshape(-1)


def split_flat(flat: np.ndarray, like: dict) -> dict:
    """Cut a ravelled vector into the leaves of ``like`` (path -> array),
    in the order ``jax.flatten_util.ravel_pytree`` concatenates them."""
    out, at = {}, 0
    for k, leaf in like.items():
        n = int(np.prod(np.shape(leaf)))
        out[k] = flat[at:at + n]
        at += n
    return out


# -- numbers ------------------------------------------------------------------
def _worst_leaf(prog: dict, ref: dict, keys) -> float:
    median = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median) for k in keys)


def numbers(prog: dict, ref: dict) -> dict:
    """Compare readings {"losses", "grad", "change"} of the program and of
    the reference (norms per leaf path)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss_gap = math.inf
    gmed = float(np.median(list(ref["grad"].values())))
    moving = [k for k, g in ref["grad"].items() if g >= EXCLUDE_BELOW * gmed]
    change = {k: ref["change"][k] for k in moving}
    return {
        "loss_gap": loss_gap,
        "grad_gap": _worst_leaf(prog["grad"], ref["grad"], ref["grad"]),
        "change_gap": _worst_leaf(prog["change"], change, moving),
    }


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for each number the limits name, its value and limit."""
    checks = {k: {"value": found[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
