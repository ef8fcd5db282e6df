"""shard_map plumbing for model-axis-sharded flat buffers.

A sharded :class:`~repro.core.flatbuf.FlatLayout` (``layout.shards >
1``) assigns each model (TP) shard one contiguous, tile-aligned bucket
of the flat coordinate space.  This module moves trees in and out of
that buffer **without any model-axis communication**: every operation
is a ``jax.shard_map`` program in which rank m runs the
ordinary ``flatbuf`` flatten/unflatten on its *local* leaf blocks with
``layout.bucket()`` -- no concatenate ever crosses a shard boundary, so
neither XLA's concat partitioner (which PR 2 had to dodge with
whole-leaf gathers, see the old ``gather_leafdims``) nor any implicit
all-gather is involved.

Spec conventions (derived from the layout, so in/out specs always agree
with the bucket geometry):

  * buffer  ``[P(, D), n_pad]``      -> ``P(pod(, data), model)``
  * sharded leaf                     -> model axis on ``slot.shard_dim``
  * per-bucket-copy leaf             -> replicated over model (each rank
    holds the identical copy; ``check_vma=False`` because shard_map
    cannot prove the replication invariant the layout guarantees)

Uneven sharded leaves (``slot.shard_pad > 0``) cross the shard_map
boundary in their PADDED shape -- shard_map requires every sharded dim
to divide the mesh axis, so trees are zero-extended via
``flatbuf.pad_tree`` on the way in and sliced back to the logical
extent via ``flatbuf.unpad_tree`` on the way out.  Both are
shard-boundary-aligned pad/slice ops (GSPMD's physical layout for an
unevenly sharded dim IS the ceil-padded form), so they lower without
model-axis communication; the zero tail is don't-care exactly like
tile padding.

``check_vma=False`` is safe here by construction: copies are only ever
written from model-replicated inputs through deterministic elementwise
programs, so they remain bit-identical on every rank.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.core import flatbuf, stages
from repro.core.topology import Topology

PyTree = Any


def model_sharding(topo: Topology, specs: PyTree) -> flatbuf.ModelSharding:
    """The :class:`flatbuf.ModelSharding` of this mesh's model axis."""
    return flatbuf.ModelSharding(shards=topo.model_shards,
                                 axis=topo.model_axis, specs=specs)


def buf_spec(topo: Topology, layout: flatbuf.FlatLayout,
             batch_dims: int = 1) -> P:
    """PartitionSpec of a ``[*batch, n_pad]`` buffer of this layout."""
    ax = topo.model_axis if layout.shards > 1 else None
    lead = (topo.pod_axis, topo.data_axis)[:batch_dims]
    return P(*lead, ax)


def leaf_specs(topo: Topology, layout: flatbuf.FlatLayout,
               batch_dims: int = 1) -> PyTree:
    """Per-leaf PartitionSpecs implied by the layout's bucket placement.

    Sharded slots put the model axis on their ``shard_dim``; per-bucket
    copies are replicated over model.  Leading dims follow the usual
    ``[P(, D), *leaf]`` convention.
    """
    lead = (topo.pod_axis, topo.data_axis)[:batch_dims]
    out = []
    for slot in layout.slots:
        dims = [None] * len(slot.shape)
        if slot.shard_dim is not None:
            dims[slot.shard_dim] = topo.model_axis
        out.append(P(*lead, *dims))
    return layout.treedef.unflatten(out)


def _smap(topo: Topology, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=topo.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@stages.in_stage("relayout")
def flatten(topo: Topology, layout: flatbuf.FlatLayout, tree: PyTree,
            batch_dims: int = 1, dtype: Any = None) -> jax.Array:
    """Sharded ``flatten_tree``: each rank writes only its own bucket.

    Bit-identical to the reference ``flatbuf.flatten_tree`` on the same
    sharded layout (same per-leaf casts, same placement), but lowers to
    purely local reshapes/concats -- zero collectives.
    """
    if layout.shards == 1:
        return flatbuf.flatten_tree(layout, tree, batch_dims=batch_dims,
                                    dtype=dtype)
    bucket = layout.bucket()

    def prog(local_tree):
        return flatbuf.flatten_tree(bucket, local_tree,
                                    batch_dims=batch_dims, dtype=dtype)

    tree = flatbuf.pad_tree(layout, tree, batch_dims)
    return _smap(topo, prog, (leaf_specs(topo, layout, batch_dims),),
                 buf_spec(topo, layout, batch_dims))(tree)


@stages.in_stage("relayout")
def tree_views(topo: Topology, fs: flatbuf.FlatState,
               cast: bool = True) -> PyTree:
    """Sharded ``FlatState.tree()``: leaf views without model gathers.

    Each rank slices its local bucket; sharded leaves come back with
    the model axis on ``shard_dim`` (== the master/compute placement
    the layout was built from), copies come back replicated.
    """
    layout, batch_dims = fs.layout, fs.batch_dims
    if layout.shards == 1:
        return fs.tree(cast=cast)
    bucket = layout.bucket()

    def prog(local_buf):
        return flatbuf.unflatten_tree(bucket, local_buf,
                                      batch_dims=batch_dims, cast=cast)

    out = _smap(topo, prog, (buf_spec(topo, layout, batch_dims),),
                leaf_specs(topo, layout, batch_dims))(fs.buf)
    return flatbuf.unpad_tree(layout, out, batch_dims)
