"""Host-side tree builders for the two backbone indexes.

Index *building* is a one-off, data-dependent, pointer-chasing procedure — it
runs in numpy on the host (exactly as the paper builds its C indexes on CPU).
Search, filter training, calibration and serving — the hot paths — consume
the flattened array form (`flat_index.FlatIndex`) and run in JAX.

Two builders are provided, mirroring the paper's instantiations:

* ``build_dstree``  — DSTree-like: recursive binary splits on EAPCA segment
  statistics (split the segment whose mean- or std-range is widest, at the
  median).  DSTree's adaptive re-segmentation is simplified to a fixed
  power-of-two segmentation; the node summarization (per-segment min/max of
  mean/std) and its lower bound are the real DSTree ones.
* ``build_isax``    — iSAX/MESSI-like: a prefix trie over SAX words; nodes
  split by promoting the cardinality of one dimension (round-robin over the
  widest dims), as in iSAX2/MESSI.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from . import summaries
from .flat_index import FlatIndex


@dataclasses.dataclass
class _Node:
    ids: np.ndarray                       # indices into the collection
    depth: int
    # dstree:
    # isax:
    sax_word: Optional[np.ndarray] = None       # (l,) symbols at node card
    sax_bits: Optional[np.ndarray] = None       # (l,) cardinality bits
    children: Optional[List["_Node"]] = None

    @property
    def is_leaf(self) -> bool:
        return self.children is None


# ---------------------------------------------------------------------------
# DSTree-like builder
# ---------------------------------------------------------------------------


def build_dstree(
    series: np.ndarray,
    leaf_capacity: int = 256,
    n_segments: int = 8,
    znorm: bool = True,
) -> FlatIndex:
    series = np.asarray(series, np.float32)
    if znorm:
        series = summaries.znormalize(series)
    n, m = series.shape
    stats = np.asarray(summaries.segment_stats(series, n_segments))  # (n,s,2)

    root = _Node(ids=np.arange(n), depth=0)
    stack = [root]
    while stack:
        node = stack.pop()
        if len(node.ids) <= leaf_capacity:
            continue
        st = stats[node.ids]                                  # (k, s, 2)
        # pick the (segment, statistic) with the widest range: splitting
        # there maximally tightens the children's EAPCA boxes.
        rng = st.max(axis=0) - st.min(axis=0)                 # (s, 2)
        seg, which = np.unravel_index(np.argmax(rng), rng.shape)
        vals = st[:, seg, which]
        pivot = np.median(vals)
        left = vals <= pivot
        # guard: degenerate split (all values equal) → split by halves.
        if left.all() or (~left).all():
            order = np.argsort(vals, kind="stable")
            left = np.zeros(len(vals), bool)
            left[order[: len(order) // 2]] = True
        lo = _Node(ids=node.ids[left], depth=node.depth + 1)
        hi = _Node(ids=node.ids[~left], depth=node.depth + 1)
        node.children = [lo, hi]
        node.ids = np.empty(0, np.int64)
        stack += [lo, hi]

    leaves = _collect_leaves(root)
    return _flatten(series, leaves, kind="dstree", n_segments=n_segments)


# ---------------------------------------------------------------------------
# iSAX/MESSI-like builder
# ---------------------------------------------------------------------------


def build_isax(
    series: np.ndarray,
    leaf_capacity: int = 256,
    word_len: int = 8,
    max_card_bits: int = 8,
    znorm: bool = True,
) -> FlatIndex:
    series = np.asarray(series, np.float32)
    if znorm:
        series = summaries.znormalize(series)
    n, m = series.shape
    paa = np.asarray(summaries.paa(series, word_len))            # (n, l)
    # symbols at the maximum cardinality; a node's symbol at b bits is the
    # top-b bits of the max-card symbol (iSAX cardinality promotion).
    sym_max = np.asarray(summaries.sax_from_paa(paa, max_card_bits))

    def node_word(ids: np.ndarray, bits: np.ndarray) -> np.ndarray:
        # all series in a node share the same prefix per construction
        shift = max_card_bits - bits
        return (sym_max[ids[0]] >> shift).astype(np.int32)

    # root children: cardinality 1 on every dim (2^l possible words)
    root = _Node(ids=np.arange(n), depth=0,
                 sax_word=np.zeros(word_len, np.int32),
                 sax_bits=np.zeros(word_len, np.int64))
    first_bits = np.ones(word_len, np.int64)
    buckets: dict = {}
    for i in range(n):
        w = tuple((sym_max[i] >> (max_card_bits - 1)).tolist())
        buckets.setdefault(w, []).append(i)
    root.children = []
    stack = []
    for w, ids in buckets.items():
        ch = _Node(ids=np.asarray(ids), depth=1,
                   sax_word=np.asarray(w, np.int32), sax_bits=first_bits.copy())
        root.children.append(ch)
        stack.append(ch)

    while stack:
        node = stack.pop()
        if len(node.ids) <= leaf_capacity:
            continue
        # split: promote cardinality of the dim with the fewest bits whose
        # promotion actually separates the series (iSAX2-style round robin).
        order = np.argsort(node.sax_bits, kind="stable")
        split_dim = -1
        for d in order:
            if node.sax_bits[d] >= max_card_bits:
                continue
            b = node.sax_bits[d] + 1
            bit = (sym_max[node.ids, d] >> (max_card_bits - b)) & 1
            if 0 < bit.sum() < len(bit):
                split_dim = int(d)
                break
        if split_dim < 0:      # cannot separate further → oversized leaf
            continue
        b = node.sax_bits[split_dim] + 1
        bit = (sym_max[node.ids, split_dim] >> (max_card_bits - b)) & 1
        node.children = []
        for side in (0, 1):
            ids = node.ids[bit == side]
            bits = node.sax_bits.copy()
            bits[split_dim] = b
            ch = _Node(ids=ids, depth=node.depth + 1,
                       sax_word=node_word(ids, bits), sax_bits=bits)
            node.children.append(ch)
            stack.append(ch)
        node.ids = np.empty(0, np.int64)

    leaves = _collect_leaves(root)
    return _flatten(series, leaves, kind="isax", word_len=word_len)


# ---------------------------------------------------------------------------
# Flattening
# ---------------------------------------------------------------------------


def _collect_leaves(root: _Node) -> List[_Node]:
    out: List[_Node] = []
    stack = [root]
    while stack:
        node = stack.pop()
        if node.is_leaf:
            if len(node.ids):
                out.append(node)
        else:
            stack += node.children
    # deterministic ordering (largest leaves first helps kernel tiling)
    out.sort(key=lambda nd: (-len(nd.ids), int(nd.ids[0])))
    return out


def _flatten(series: np.ndarray, leaves: List[_Node], kind: str,
             n_segments: int = 8, word_len: int = 8) -> FlatIndex:
    n, m = series.shape
    L = len(leaves)
    order = np.concatenate([lf.ids for lf in leaves]).astype(np.int32)
    sizes = np.asarray([len(lf.ids) for lf in leaves], np.int32)
    starts = np.concatenate([[0], np.cumsum(sizes[:-1])]).astype(np.int32)
    max_leaf = int(sizes.max())
    # pad the sorted array so dynamic_slice(start, max_leaf) is always in
    # bounds; padded rows are masked with +inf inside the scan kernel.
    sorted_series = np.concatenate(
        [series[order], np.zeros((max_leaf, m), np.float32)], axis=0
    )

    if kind == "dstree":
        stats = np.asarray(summaries.segment_stats(series, n_segments))
        boxes = np.stack(
            [summaries.eapca_node_box(stats[lf.ids]) for lf in leaves]
        )                                                     # (L, s, 4)
        payload = {"eapca_box": boxes}
        seg_len = np.full(n_segments, -(-m // n_segments), np.int32)
        payload["seg_len"] = seg_len
    elif kind == "isax":
        words = np.stack([lf.sax_word for lf in leaves])       # (L, l)
        bits = np.stack([lf.sax_bits for lf in leaves])        # (L, l)
        edges = summaries.sax_symbol_edges(words, bits)        # (L, l, 2)
        payload = {
            "sax_word": words.astype(np.int32),
            "sax_bits": bits.astype(np.int32),
            "sax_edges": edges,
        }
    else:  # pragma: no cover
        raise ValueError(kind)

    return FlatIndex(
        kind=kind,
        series=sorted_series,
        order=order,
        leaf_start=starts,
        leaf_size=sizes,
        max_leaf_size=max_leaf,
        n_series=n,
        length=m,
        payload={k: np.asarray(v) for k, v in payload.items()},
    ).on_device()
