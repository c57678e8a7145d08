"""Seeded random weights, made on the device in one jitted call, in the
dtype they are served in, one leaf for each weight of the published
block, named as in the program's parameter tree (which is checked against
``repro.models.abstract_params``).

The same arrays feed the program and the plain reference, so neither
takes anything the other made."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import catalog

F32 = jnp.float32


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Four uint32 words from any whole number ``seed``."""
    ss = np.random.SeedSequence([seed % (1 << 64), stream])
    return ss.generate_state(4, np.uint32)


def _shapes(m: Dict[str, Any], fam) -> Dict[str, Any]:
    """{path: (shape, dtype, kind)} of every leaf: the embedding, final norm
    and head here, the layer stack as family ``fam`` states it."""
    d, v = m["hidden_size"], m["vocab_size"]
    wd = jnp.dtype(m["dtype"])
    leaves = {
        "embed": ((v, d), wd, "normal"),
        "final_norm": ((d,), wd, "norm"),
    }
    if not m["tie_word_embeddings"]:
        leaves["lm_head"] = ((d, v), wd, "normal")
    leaves.update(fam.leaves(m))
    return leaves


def _leaf(key, shape, dtype, kind: str, n_layers: int, fam):
    if kind == "normal":
        return (jax.random.normal(key, shape, F32) * 0.02).astype(dtype)
    if kind == "out":
        sc = 0.02 / math.sqrt(2 * n_layers)
        return (jax.random.normal(key, shape, F32) * sc).astype(dtype)
    if kind == "norm":
        return (1.0 + 0.1 * jax.random.normal(key, shape, F32)).astype(dtype)
    if hasattr(fam, "init"):
        return fam.init(kind, key, shape, dtype)
    raise ValueError(kind)


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, val in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return out


def make(model: Dict[str, Any], family: str, seed: int):
    """The parameter tree for ``model`` from ``seed``, on the default
    device, in one jitted call."""
    fam = catalog.family(family)
    leaves = _shapes(model, fam)
    names = sorted(leaves)
    n_layers = model["num_hidden_layers"]

    def build(key):
        flat = {}
        for i, name in enumerate(names):
            shape, dtype, kind = leaves[name]
            flat[name] = _leaf(jax.random.fold_in(key, i), shape, dtype, kind,
                               n_layers, fam)
        return _nest(flat)

    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed, 0)),
                                   impl="rbg")
    return jax.block_until_ready(jax.jit(build)(key))


def abstract(model: Dict[str, Any], family: str):
    """ShapeDtypeStructs of ``make``'s tree, allocating nothing."""
    return _nest({
        name: jax.ShapeDtypeStruct(shape, dtype)
        for name, (shape, dtype, _) in _shapes(
            model, catalog.family(family)).items()
    })


def program_view(tree, program_tree) -> Tuple[Any, List[str]]:
    """``tree`` cut to the leaves of the program's tree, and the paths of
    the leaves the program has no parameter for.  Raises where the program
    has a leaf that ``tree`` lacks, or one of another shape or dtype."""
    ours = {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    view, wrong = {}, []
    for path, want in jax.tree_util.tree_leaves_with_path(program_tree):
        key = jax.tree_util.keystr(path)
        got = ours.get(key)
        if got is None or (tuple(got.shape), jnp.dtype(got.dtype)) != (
                tuple(want.shape), jnp.dtype(want.dtype)):
            wrong.append(key)
        view[key] = got
    if wrong:
        raise ValueError(f"weights differ from the program's layout at {wrong}")
    leaves = [view[jax.tree_util.keystr(p)]
              for p, _ in jax.tree_util.tree_leaves_with_path(program_tree)]
    missing = sorted(set(ours) - set(view))
    return (jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(program_tree), leaves), missing)


def nbytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


