"""Flattened, array-based index representation.

The tree builders emit this structure; everything downstream (lower bounds,
filter training, conformal calibration, search, distribution) consumes it.
It is a pytree, so it jits, shards and checkpoints like any other JAX state.

The arrays the search reads — ``series``, the leaf layout and the
summarization payload — live on the default device once ``tree.build_*``
(or ``serving.session.load_index``) hand the index over (:meth:`on_device`),
so a query batch copies only its queries.  ``order`` stays a host array: it
only maps result rows back to original ids, which happens on the host.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FlatIndex:
    kind: str                      # "dstree" | "isax"
    series: jax.Array              # (n + max_leaf, m) leaf-sorted, padded
    order: np.ndarray              # (n,) original id of sorted row i (host)
    leaf_start: jax.Array          # (L,)
    leaf_size: jax.Array           # (L,)
    max_leaf_size: int
    n_series: int
    length: int
    payload: Dict[str, jax.Array]  # summarization arrays per kind

    @property
    def n_leaves(self) -> int:
        return int(self.leaf_size.shape[0])

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.series, self.order, self.leaf_start, self.leaf_size,
                    self.payload)
        aux = (self.kind, self.max_leaf_size, self.n_series, self.length)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        series, order, leaf_start, leaf_size, payload = children
        kind, max_leaf_size, n_series, length = aux
        return cls(kind=kind, series=series, order=order,
                   leaf_start=leaf_start, leaf_size=leaf_size,
                   max_leaf_size=max_leaf_size, n_series=n_series,
                   length=length, payload=payload)

    def on_device(self) -> "FlatIndex":
        """The same index with its search-side arrays on the default device
        (one host→device copy; arrays already there are not copied)."""
        return dataclasses.replace(
            self, series=jax.device_put(self.series),
            leaf_start=jax.device_put(self.leaf_start),
            leaf_size=jax.device_put(self.leaf_size),
            payload={k: jax.device_put(v) for k, v in self.payload.items()},
            order=np.asarray(self.order))

    # -- convenience --------------------------------------------------------
    def leaf_members(self, leaf: int) -> np.ndarray:
        """Original series ids stored in ``leaf`` (host-side helper)."""
        s = int(self.leaf_start[leaf])
        e = s + int(self.leaf_size[leaf])
        return np.asarray(self.order[s:e])

    def stats(self) -> Dict[str, float]:
        sizes = np.asarray(self.leaf_size)
        return {
            "n_leaves": float(len(sizes)),
            "max_leaf": float(sizes.max()),
            "mean_leaf": float(sizes.mean()),
            "min_leaf": float(sizes.min()),
        }
