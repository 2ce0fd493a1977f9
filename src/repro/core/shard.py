"""Morton-prefix sharding of the S-QuadTree store.

The object SoA of an `SQuadTree` is sorted by (S, Z, I, L) id, and the id
codec makes any subtree one contiguous id interval — so *any* contiguous
split of the sorted object array is a set of Morton-prefix ranges, and each
range rebuilds into a self-contained per-shard `SQuadTree` that keeps the
GLOBAL ids (`build(oids=...)`). Phases 1–2 then run per shard: candidate
search and node selection against the shard's own (smaller) tree, SIP
filter material clipped to the shard's id range so the per-shard driven
retrievals partition the result set exactly — the union over shards is
bit-identical to the single-host engine, and the global θ read between
shard passes prunes later shards for free (the θ bound is exact).

The fused descent stacks every shard's node planes into one
`kernels/ops.tree_descend_sharded` dispatch laid over a
`launch/mesh.make_shard_mesh` shard_map, so device count scales shard
count without touching the per-shard kernel.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import node_select, squadtree
from .squadtree import SQuadTree, build as build_tree
from .spans import span
from .store import (QuadStore, _entity_cs_csr, _sorted_lut,
                    lut_get)


@dataclasses.dataclass
class TreeShard:
    """One shard's tree plus the closed global-id range it owns.

    `filter_material` clips the I-Range intervals to [id_lo, id_hi]: a
    shard tree's upper nodes (root included) span the whole id space, so
    without the clip two shards would both emit the driven rows of ids
    they don't own and the union would double-count. E-list ids need no
    clip — shard elists are built from shard-owned objects only.

    ``clip=False`` marks the degenerate single-view over an unsharded
    store: filter material passes through untouched, so the unsharded
    engine path is literally the old code path.
    """
    tree: SQuadTree
    id_lo: int = 0
    id_hi: int = 0
    clip: bool = True

    @property
    def n_objects(self) -> int:
        return self.tree.n_objects

    def filter_material(self, v_star: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray]:
        intervals, explicit = self.tree.filter_material(v_star)
        if self.clip and len(intervals):
            lo = np.maximum(intervals[:, 0], self.id_lo)
            hi = np.minimum(intervals[:, 1], self.id_hi)
            keep = lo <= hi
            intervals = np.stack([lo[keep], hi[keep]], axis=1)
        return intervals, explicit


def shard_views(store: QuadStore) -> list[TreeShard]:
    """The store's shard list; a single no-clip view for unsharded stores."""
    shards = getattr(store, "tree_shards", None)
    if shards:
        return list(shards)
    return [TreeShard(store.tree, clip=False)]


def whole_view(store: QuadStore) -> list[TreeShard]:
    """Single global-tree view (the SIP-disabled path: with no interval
    filtering, per-shard retrieval would replicate the driven side)."""
    return [TreeShard(store.tree, clip=False)]


@dataclasses.dataclass
class ShardedQuadStore(QuadStore):
    """A QuadStore whose SQuadTree is partitioned by Morton-prefix range.

    The global `tree` is retained for id-keyed lookups that are not part
    of the per-shard Phase 1–2 sweep (`spatial_box_of`, `geom_rows`, the
    geometry pool rows); `tree_shards` carries the per-shard trees the
    executor iterates.
    """
    tree_shards: list = dataclasses.field(default_factory=list)

    @property
    def n_shards(self) -> int:
        return len(self.tree_shards)

    def shard_tree_nbytes(self) -> int:
        return sum(sh.tree.nbytes() for sh in self.tree_shards)


def shard_store(store: QuadStore, n_shards: int,
                leaf_capacity: int = 64,
                compressed: bool = True) -> ShardedQuadStore:
    """Partition `store` into `n_shards` contiguous Morton-prefix ranges.

    Each shard rebuilds a plain `SQuadTree` over its object slice with the
    precomputed GLOBAL ids and the global extent/l_max/Bloom geometry, so
    id-interval semantics (and the one shared `PreparedKeys`) carry over
    unchanged. Per-entity in/out characteristic sets are recomputed from
    the remapped quads — the remap is bijective, so the sets equal the
    build-time ones. ``compressed`` packs each shard's E-list tier
    (`SQuadTree.pack_elists`).

    Shards are equal-object-count splits; empty ranges (more shards than
    objects) are dropped.
    """
    tree = store.tree
    if tree is None:
        raise ValueError("cannot shard a store with no spatial index")
    m = tree.n_objects
    n_shards = max(1, int(n_shards))
    bounds = [round(i * m / n_shards) for i in range(n_shards + 1)]
    cs_keys, cs_vals = _sorted_lut(store.cs_of_entity)
    bank = tree.bloom_self
    shards: list[TreeShard] = []
    for a, b in zip(bounds[:-1], bounds[1:]):
        if b <= a:
            continue
        oids = tree.obj_ids[a:b]
        cs_self = lut_get(cs_keys, cs_vals, oids)
        cs_in, cs_out = _entity_cs_csr(store.quads, oids, cs_keys, cs_vals)
        sub = build_tree(
            tree.obj_entity[a:b], tree.obj_mbr[a:b], cs_self,
            cs_in=cs_in, cs_out=cs_out,
            extent=tree.extent, l_max=tree.l_max,
            leaf_capacity=leaf_capacity,
            bloom_words=bank.nbits // 32, bloom_k=bank.k,
            oids=oids, boxes_normalized=True, compressed=compressed)
        shards.append(TreeShard(sub, id_lo=int(oids[0]), id_hi=int(oids[-1])))
    fields = {f.name: getattr(store, f.name)
              for f in dataclasses.fields(QuadStore)}
    return ShardedQuadStore(**fields, tree_shards=shards)


# ---------------------------------------------------------------------------
# Sharded Phases 1–2
# ---------------------------------------------------------------------------

def candidate_nodes_sharded(shards: list[TreeShard], box_sets, dist_norm,
                            driven_cs: np.ndarray,
                            prepared=None, probe_backend=None,
                            descend_backend=None,
                            cs_paths: list | None = None) -> list[np.ndarray]:
    """Per-shard Phase-1 candidate masks for one shared CS set.

    Returns a list aligned with `shards` of (B, N_s) bool masks. The host
    frontier route loops shards (each already batched over blocks); the
    fused routes stack every shard's node planes into ONE
    `ops.tree_descend_sharded` dispatch (shard_map over the shard mesh,
    sequential per-shard failover) — both bit-identical to calling each
    shard's `candidate_nodes` alone.
    """
    driven_cs = np.asarray(driven_cs, dtype=np.int64)
    dback = squadtree.resolve_descend_backend(descend_backend)
    if cs_paths is None:
        cs_paths = [None] * len(shards)
    if dback == "numpy" or len(shards) == 1:
        return [sh.tree.candidate_nodes(
                    box_sets, dist_norm, driven_cs, prepared=prepared,
                    probe_backend=probe_backend, descend_backend=dback,
                    cs_path=cs_paths[si])
                for si, sh in enumerate(shards)]
    from ..kernels import ops
    from . import geometry
    boxes = squadtree._pad_box_sets(box_sets)
    n_b = len(boxes)
    sizes = [sh.tree.n_nodes for sh in shards]
    if not (n_b and len(driven_cs) and boxes.shape[1]):
        return [np.zeros((n_b, n), dtype=bool) for n in sizes]
    paths = [cs_paths[si] if cs_paths[si] is not None
             else sh.tree.cs_path_mask(driven_cs, prepared=prepared,
                                       probe_backend=probe_backend)
             for si, sh in enumerate(shards)]
    n_max = max(sizes)
    stacked = np.empty((len(shards), 4, n_max), dtype=np.int64)
    stacked[:] = ops.DESCEND_PAD_BOX[None, :, None]
    cs_stack = np.zeros((len(shards), n_max), dtype=bool)
    for si, sh in enumerate(shards):
        stacked[si, :, :sizes[si]] = sh.tree._node_key_planes()
        cs_stack[si, :sizes[si]] = paths[si]
    d = (dist_norm if np.ndim(dist_norm) == 0
         else np.asarray(dist_norm, dtype=np.float64)[:, None])
    expanded = geometry.expand_boxes(boxes, d)
    keys = ops.f64_sort_keys(expanded)
    pad = ~np.isfinite(boxes[..., 0])
    if pad.any():
        keys[pad] = ops.DESCEND_PAD_BOX
    masks = ops.tree_descend_sharded(stacked, cs_stack, keys, backend=dback)
    return [masks[si, :, :sizes[si]] for si in range(len(shards))]


def sip_select(shards: list[TreeShard], box_sets, dist_norm,
               driven_cs: np.ndarray, prepared, probe_backend,
               descend_backend, cs_paths, params, card_all: list
               ) -> list[list[np.ndarray]]:
    """Phases 1+2 across shards: candidate masks then the per-shard V*
    selection DP. Returns per-BLOCK lists of per-shard V* arrays (the
    shape `QueryCursor._vstars` stores)."""
    with span("streak.phase1", rows=len(box_sets)):
        masks = candidate_nodes_sharded(
            shards, box_sets, dist_norm, driven_cs, prepared=prepared,
            probe_backend=probe_backend, descend_backend=descend_backend,
            cs_paths=cs_paths)
    with span("streak.phase2", rows=len(box_sets)):
        per_shard = [node_select.select_batch(sh.tree, masks[si], driven_cs,
                                              params, card_all[si])
                     for si, sh in enumerate(shards)]
    n_blocks = len(masks[0]) if len(shards) else 0
    return [[per_shard[si][b] for si in range(len(shards))]
            for b in range(n_blocks)]
