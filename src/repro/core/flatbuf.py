"""Flat-buffer gradient bucketization: one contiguous view of a pytree.

The sign->pack->vote->update sweep is elementwise and coordinate-order
agnostic, so running it per-leaf under ``jax.tree.map`` only buys N small
dispatches, N ragged pads, and N tiny collectives.  This module precomputes
a **static leaf layout** for any float pytree so the hot path can operate on
ONE contiguous ``[..., n_pad]`` buffer (or its 1-bit packed twin) instead:

  * every leaf is assigned a coordinate range ``[offset, offset + size)``
    with ``offset % 128 == 0`` (leaf tails padded to a whole 128-lane
    row, so buffers assemble as (8, 128)-tiled lane rows and every slot
    is also whole 32-bit pack words): the float and packed-word domains
    share the same layout -- leaf i's words are exactly
    ``[offset/32, (offset + padded)/32)``;
  * the total is padded to the 32*128 TPU tile (one packed word per lane),
    so 2D views handed to the Pallas kernels need no further padding;
  * dtype promotion rule: the buffer dtype is ``jnp.promote_types`` over
    all leaf dtypes (float leaves only) -- promotion is widening, so
    ``unflatten_tree(flatten_tree(t))`` restores every leaf bit-exactly.

``flatten_tree``/``unflatten_tree`` are cheap reshape/slice views around a
single concatenate (unflatten is pure views); ``pack_tree`` fuses the DC
correction ``u + rho*delta`` and the sign into the per-leaf pack and
concatenates at the *word* level, so the full-precision buffer is never
materialized on the fallback path (the wire payload is 1/32 the tally).

Padding convention: float padding is 0 and ``sgn(0) = +1``, bit-identical
to ``signs.pack_signs``'s all-ones tail bits -- so
``pack_tree(layout, t) == pack_signs(sgn(flatten_tree(layout, t)))``
holds bitwise (tested in tests/test_flatbuf.py).

State layouts
-------------
PR 1 used the flat buffer only as a *transient* inside the fused
transport; with ``AlgoConfig(state_layout="flat")`` (``core.hier``) the
buffer becomes the *persistent* master state.  :class:`FlatState` wraps
one ``[*batch, n_pad]`` buffer together with its static
:class:`FlatLayout` as a single pytree node (the layout rides in the
treedef aux data, so jit/eval_shape/checkpoint traversals see exactly
one array leaf).  Under ``state_layout="flat"``:

  * ``TrainState.params`` / ``delta`` / ``delta_next`` are
    ``FlatState([P, n_pad])`` buffers (master / delta dtype), and the
    replicated-regime EF / momentum buffers are ``FlatState([P, D,
    n_pad])`` -- the whole-model update and the pre-sign correction
    ``u + rho*delta`` are single elementwise sweeps instead of per-leaf
    tree maps;
  * leaf views are materialized only at the loss-function boundary and
    at checkpoint/eval edges via :meth:`FlatState.tree`
    (``unflatten_tree`` is pure slice/reshape views);
  * coordinates beyond each leaf's ``size`` (tail + tile padding, and
    in sharded layouts the ``shard_pad`` zero tail of an uneven leaf's
    last block) are *don't-care*: the fused vote/update kernel sweeps
    them along with the real coordinates (their gradient is 0 -> vote
    +1, so they drift), but no view ever reads them and
    ``checkpoint.store`` round-trips only the real coordinates.

The layout of a given tree is deterministic (flatten order x the rules
above), so two runs -- or a tree-state checkpoint and a flat-state run
-- always agree on where every leaf lives.

Model-axis sharded layouts (per-shard buckets)
----------------------------------------------
``make_layout(..., sharding=ModelSharding(...))`` lays the tree out as
``shards`` identical **buckets**, one per model (TP) shard, so the flat
buffer can live sharded along the mesh's model axis end to end -- no
leaf is ever gathered to build or read the buffer:

  * a leaf whose PartitionSpec names the model axis on a nonzero dim
    contributes its *local block* to each bucket (bucket m holds block m
    of the leaf along ``LeafSlot.shard_dim``).  Extents that do NOT
    divide by ``shards`` are padded *inside the layout*: the dim is
    zero-extended up to ``shards * ceil(extent / shards)``
    (``LeafSlot.shard_pad`` records the tail), so every bucket still
    holds one equal block and the leaf stays sharded end to end -- the
    zero tail is don't-care exactly like tile padding (``sgn(0) = +1``,
    never read back, never checkpointed);
  * every other leaf (replicated specs, zero-size dims) is **copied
    whole into every bucket** -- each shard votes/updates its own copy
    from identical inputs, so the copies stay bit-identical by
    construction and any one of them is the leaf;
  * slots store *local* (per-bucket) geometry; the buckets share one
    slot table, each bucket is independently 32*128-tile aligned, and
    ``n_pad = shards * bucket_pad`` with bucket m owning the contiguous
    word range ``[m * bucket_pad/32, (m+1) * bucket_pad/32)``.

``layout.bucket()`` is the shards=1 layout of ONE bucket: inside a
``shard_map`` program (see ``core.shardflat``) every rank runs the
ordinary ``flatten_tree``/``unflatten_tree``/``pack_tree`` on its local
block with the bucket layout, which is how the sharded layout stays a
pure re-indexing of the same per-coordinate arithmetic.  The global
(reference) ``flatten_tree``/``unflatten_tree``/``pack_tree`` here
implement identical semantics with static slices/concats and work on
any runtime -- they are the oracle the shard_map path is tested
against.  Coordinate ORDER differs from the unsharded layout (buckets
interleave leaf blocks), but the sign->vote->update sweep is
coordinate-order agnostic, so trajectories stay bit-identical
leaf-for-leaf.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import signs, stages

PyTree = Any

PACK = signs.PACK_WIDTH          # 32 sign bits per uint32 word
LANES = 128                      # TPU lane count
TILE = PACK * LANES              # 4096 coords = 128 packed words


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Static placement of one leaf inside the flat buffer.

    For sharded layouts (``FlatLayout.shards > 1``) the geometry is
    LOCAL: ``shape``/``size``/``padded`` describe the per-bucket block
    and ``offset`` is the offset *within* a bucket.  ``shard_dim`` is
    the leaf dim the model axis shards, or None for a leaf copied whole
    into every bucket.  ``shard_pad`` is the number of zero-filled rows
    the layout appends to the GLOBAL extent along ``shard_dim`` so it
    divides evenly (uneven TP leaves): logical global extent =
    ``shape[shard_dim] * shards - shard_pad``.
    """
    shape: tuple[int, ...]       # leaf dims (batch dims excluded)
    dtype: Any                   # original leaf dtype (restored on unflatten)
    size: int                    # prod(shape)
    padded: int                  # size padded to a LANES multiple
    offset: int                  # coordinate offset; offset % LANES == 0
    shard_dim: int | None = None  # model-sharded leaf dim (sharded layouts)
    shard_pad: int = 0           # zero tail padding the global shard_dim
                                 # extent up to a multiple of shards

    @property
    def word_offset(self) -> int:
        return self.offset // PACK

    @property
    def words(self) -> int:
        return self.padded // PACK

    def global_shape(self, shards: int) -> tuple[int, ...]:
        """The LOGICAL (unpadded) leaf shape this slot stores."""
        if self.shard_dim is None:
            return self.shape
        d = self.shard_dim
        return (self.shape[:d] + (self.shape[d] * shards - self.shard_pad,)
                + self.shape[d + 1:])

    def global_size(self, shards: int) -> int:
        """Number of REAL (logical) coordinates this slot stores."""
        return int(functools.reduce(
            lambda a, b: a * b, self.global_shape(shards), 1))


@dataclasses.dataclass(frozen=True)
class ModelSharding:
    """How the model (TP) axis divides a tree into per-shard buckets.

    ``specs`` is a pytree of ``jax.sharding.PartitionSpec`` over the
    LEAF dims (batch dims excluded) -- the same trees ``ModelBundle``
    carries as master/compute specs.  A leaf shards on the first dim
    whose spec entry names ``axis`` and has a nonzero extent (uneven
    extents are zero-padded up to a multiple of ``shards`` inside the
    layout, see ``LeafSlot.shard_pad``); everything else is copied
    whole into every bucket.
    """
    shards: int
    axis: str
    specs: Any


def _path_key(path) -> str:
    """'/'-joined leaf path key (same convention as checkpoint.store)."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


@functools.lru_cache(maxsize=None)
def _warn_zero_copy(leaf_key: str, shape: tuple[int, ...], dim: int,
                    shards: int):
    # keyed on the leaf PATH, not just the shape: two different leaves
    # of equal shape must each warn, while re-laying the same tree out
    # (master / delta / EF layouts share geometry) stays deduped.  This
    # is the ONE remaining per-bucket-copy fallback for a spec'd model
    # dim -- a zero-size extent carries no data, so nothing is lost,
    # but the spec is almost certainly a mistake worth surfacing.
    warnings.warn(
        f"flatbuf sharded layout: leaf {leaf_key!r} (shape {shape}) is "
        f"model-sharded on zero-size dim {dim}; it carries no data, so "
        f"it is stored as a per-bucket COPY rather than {shards} padded "
        f"blocks.", stacklevel=3)


def _spec_shard_dim(spec, axis: str, shape: tuple[int, ...],
                    shards: int, leaf_key: str = "") -> int | None:
    if spec is None:
        return None
    for i, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if axis in names:
            if i < len(shape) and shape[i] > 0:
                return i         # uneven extents shard too: padded blocks
            if i < len(shape):
                _warn_zero_copy(leaf_key, shape, i, shards)
            return None          # zero-size dim -> per-bucket copy
    return None


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static layout of a pytree as one tile-aligned flat buffer."""
    treedef: Any
    slots: tuple[LeafSlot, ...]
    n: int                       # distinct real coordinates
    n_pad: int                   # buffer length; n_pad % (shards*TILE) == 0
    dtype: Any                   # promoted float dtype of the flat buffer
    shards: int = 1              # model-axis buckets (1 = unsharded)

    @property
    def n_words(self) -> int:
        return self.n_pad // PACK

    @property
    def bucket_pad(self) -> int:
        """Coordinates per model-shard bucket (== n_pad when shards=1)."""
        return self.n_pad // self.shards

    @property
    def bucket_words(self) -> int:
        return self.bucket_pad // PACK

    def bucket(self) -> "FlatLayout":
        """The shards=1 layout of ONE bucket (identity when unsharded).

        This is what a shard_map program uses on its local block: the
        slots already store local geometry, so the bucket layout is the
        same slot table over a ``bucket_pad``-long buffer.
        """
        if self.shards == 1:
            return self
        return dataclasses.replace(
            self, shards=1, n_pad=self.bucket_pad,
            n=sum(s.size for s in self.slots))


@jax.tree_util.register_pytree_node_class
class FlatState:
    """One flat buffer + its static :class:`FlatLayout`, as a pytree node.

    The buffer is the single array leaf; ``(layout, batch_dims)`` ride in
    the treedef aux data, so the layout is available statically wherever
    the state travels (train step, eval_shape, checkpoint store) and two
    ``FlatState``s with the same layout are structure-compatible under
    ``jax.tree`` transforms, ``lax.cond`` and donation.
    """

    __slots__ = ("buf", "layout", "batch_dims")

    def __init__(self, buf, layout: FlatLayout, batch_dims: int = 1):
        self.buf = buf
        self.layout = layout
        self.batch_dims = batch_dims

    def tree(self, cast: bool = True) -> PyTree:
        """Materialize the leaf views (slice/reshape, no copy)."""
        return unflatten_tree(self.layout, self.buf,
                              batch_dims=self.batch_dims, cast=cast)

    def replace(self, buf) -> "FlatState":
        return FlatState(buf, self.layout, self.batch_dims)

    def tree_flatten(self):
        return (self.buf,), (self.layout, self.batch_dims)

    @classmethod
    def tree_unflatten(cls, aux, children):
        layout, batch_dims = aux
        return cls(children[0], layout, batch_dims)

    def __repr__(self):
        return (f"FlatState(buf={getattr(self.buf, 'shape', self.buf)!r}, "
                f"n={self.layout.n}, n_pad={self.layout.n_pad}, "
                f"batch_dims={self.batch_dims})")


def from_tree(tree: PyTree, batch_dims: int = 0, dtype: Any = None,
              sharding: ModelSharding | None = None) -> FlatState:
    """Lay out and flatten ``tree`` into a :class:`FlatState` in one call."""
    layout = make_layout(tree, batch_dims=batch_dims, sharding=sharding)
    buf = flatten_tree(layout, tree, batch_dims=batch_dims, dtype=dtype)
    return FlatState(buf, layout, batch_dims)


def with_dtype(layout: FlatLayout, dtype: Any) -> FlatLayout:
    """The same coordinate layout, re-labeled for a buffer of ``dtype``.

    Auxiliary flat-state buffers (DC delta, EF residual, momentum) share
    the master's slot geometry but store a different dtype; their slots
    must say so, or ``FlatState.tree()`` / checkpoint metadata would
    report the master dtype for them.
    """
    dtype = jnp.dtype(dtype)
    slots = tuple(dataclasses.replace(s, dtype=dtype) for s in layout.slots)
    return dataclasses.replace(layout, slots=slots, dtype=dtype)


def make_layout(tree: PyTree, batch_dims: int = 0, tile: int = TILE,
                sharding: ModelSharding | None = None) -> FlatLayout:
    """Compute the static layout of ``tree`` (shapes/dtypes only).

    batch_dims: number of leading dims shared by every leaf (e.g. 2 for
    ``[P, D, *leaf]`` per-device gradients) that stay un-flattened.

    sharding: lay the tree out as per-model-shard buckets (see the
    module docstring).  Uneven extents shard as padded blocks, so a
    sharding normalizes back to the unsharded (shards=1) layout only
    when NO leaf spec names the model axis on a nonzero dim -- callers
    can pass the mesh sharding unconditionally.
    """
    keyed, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [leaf for _, leaf in keyed]
    leaf_keys = [_path_key(p) for p, _ in keyed]
    if not leaves:
        raise ValueError("cannot lay out an empty pytree")
    shards = sharding.shards if sharding is not None else 1
    if shards > 1:
        spec_leaves = treedef.flatten_up_to(sharding.specs)
    else:
        spec_leaves = [None] * len(leaves)
    kinds = set()
    for leaf in leaves:
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            kinds.add("float")
        elif jnp.issubdtype(leaf.dtype, jnp.signedinteger):
            kinds.add("int")
        else:
            raise ValueError(
                "flatbuf only buckets float / signed-int leaves, got "
                f"{leaf.dtype}")
    if len(kinds) > 1:
        # jnp.promote_types(int32, bfloat16) == bfloat16 -- NOT widening,
        # so a mixed buffer could corrupt int values; keep trees
        # dtype-kind homogeneous (sign trees are all-int, grads all-float)
        raise ValueError("flatbuf trees must not mix int and float leaves")
    slots = []
    offset = 0
    dtype = None
    for leaf, spec, key in zip(leaves, spec_leaves, leaf_keys):
        shape = tuple(leaf.shape[batch_dims:])
        sd = (_spec_shard_dim(spec, sharding.axis, shape, shards, key)
              if shards > 1 else None)
        sp = 0
        if sd is not None:
            # pad the sharded extent up to the next multiple of shards
            # so every bucket holds one equal local block (zero tail =
            # don't-care coordinates, same convention as tile padding)
            blk = -(-shape[sd] // shards)
            sp = blk * shards - shape[sd]
            shape = shape[:sd] + (blk,) + shape[sd + 1:]
        size = int(functools.reduce(lambda a, b: a * b, shape, 1))
        padded = _ceil_to(max(size, 1), LANES)
        slots.append(LeafSlot(shape=shape, dtype=leaf.dtype, size=size,
                              padded=padded, offset=offset, shard_dim=sd,
                              shard_pad=sp))
        offset += padded
        dtype = (leaf.dtype if dtype is None
                 else jnp.promote_types(dtype, leaf.dtype))
    if shards > 1 and all(s.shard_dim is None for s in slots):
        shards = 1               # nothing shards: don't pay M-way copies
    n = sum(s.global_size(shards) if s.shard_dim is not None else s.size
            for s in slots)
    return FlatLayout(treedef=treedef, slots=tuple(slots), n=n,
                      n_pad=shards * _ceil_to(offset, tile),
                      dtype=jnp.dtype(dtype), shards=shards)


def _pad_shard_tail(slot: LeafSlot, leaf: jax.Array, batch_dims: int):
    """Zero-extend an uneven sharded leaf's shard_dim to blk * shards.

    Zero fill keeps the tail don't-care under the padding convention
    (``sgn(0) = +1``); no view ever reads it back.
    """
    if slot.shard_dim is None or not slot.shard_pad:
        return leaf
    pads = [(0, 0)] * leaf.ndim
    pads[batch_dims + slot.shard_dim] = (0, slot.shard_pad)
    return jnp.pad(leaf, pads)


@stages.in_stage("relayout")
def pad_tree(layout: FlatLayout, tree: PyTree,
             batch_dims: int = 0) -> PyTree:
    """Logical tree -> the layout's padded-shard shapes (zero tails).

    Every uneven sharded leaf gains ``shard_pad`` zero rows along its
    ``shard_dim`` so each leaf dim divides evenly by ``layout.shards``
    -- the shapes a ``shard_map`` program (``core.shardflat``) needs at
    its boundary.  Identity for even/copy slots and unsharded layouts.
    """
    leaves = layout.treedef.flatten_up_to(tree)
    return layout.treedef.unflatten(
        [_pad_shard_tail(s, leaf, batch_dims)
         for s, leaf in zip(layout.slots, leaves)])


@stages.in_stage("relayout")
def unpad_tree(layout: FlatLayout, tree: PyTree,
               batch_dims: int = 0) -> PyTree:
    """Inverse of :func:`pad_tree`: slice each leaf back to its logical
    extent (drops the don't-care zero tail; pure static slices)."""
    leaves = layout.treedef.flatten_up_to(tree)
    out = []
    for slot, leaf in zip(layout.slots, leaves):
        if slot.shard_dim is not None and slot.shard_pad:
            ax = batch_dims + slot.shard_dim
            leaf = jax.lax.slice_in_dim(
                leaf, 0, leaf.shape[ax] - slot.shard_pad, axis=ax)
        out.append(leaf)
    return layout.treedef.unflatten(out)


@stages.in_stage("relayout")
def bucket_trees(layout: FlatLayout, tree: PyTree,
                 batch_dims: int = 0) -> list[PyTree]:
    """Per-bucket local trees of a sharded layout (static slices).

    Bucket m's tree holds block m of every sharded leaf (along its
    ``shard_dim``, zero-padded tail for uneven extents) and the full
    leaf for per-bucket copies -- exactly what rank m of a shard_map
    program sees locally.
    """
    leaves = [_pad_shard_tail(s, leaf, batch_dims)
              for s, leaf in zip(layout.slots,
                                 layout.treedef.flatten_up_to(tree))]
    out = []
    for m in range(layout.shards):
        parts = []
        for slot, leaf in zip(layout.slots, leaves):
            if slot.shard_dim is None:
                parts.append(leaf)
            else:
                ax = batch_dims + slot.shard_dim
                w = slot.shape[slot.shard_dim]
                parts.append(jax.lax.slice_in_dim(leaf, m * w, (m + 1) * w,
                                                  axis=ax))
        out.append(layout.treedef.unflatten(parts))
    return out


def _flat_leaf(slot: LeafSlot, leaf: jax.Array, batch_dims: int):
    batch = leaf.shape[:batch_dims]
    flat = leaf.reshape(batch + (slot.size,))
    if slot.padded != slot.size:
        flat = jnp.pad(flat, [(0, 0)] * batch_dims
                       + [(0, slot.padded - slot.size)])
    return flat


@stages.in_stage("relayout")
def flatten_tree(layout: FlatLayout, tree: PyTree, batch_dims: int = 0,
                 dtype: Any = None) -> jax.Array:
    """tree -> ``[*batch, n_pad]`` buffer in the (promoted) buffer dtype.

    Sharded layouts build each bucket from the leaf blocks it owns
    (static slices -- the reference semantics of the shard_map path in
    ``core.shardflat``, which never moves a block off its shard).
    """
    if layout.shards > 1:
        bucket = layout.bucket()
        return jnp.concatenate(
            [flatten_tree(bucket, t, batch_dims=batch_dims, dtype=dtype)
             for t in bucket_trees(layout, tree, batch_dims)], axis=-1)
    dtype = layout.dtype if dtype is None else dtype
    leaves = layout.treedef.flatten_up_to(tree)
    batch = leaves[0].shape[:batch_dims]
    nb = int(functools.reduce(lambda a, b: a * b, batch, 1))
    # assemble as [nb, n_pad/128, 128] lane rows (slots are whole rows)
    # over ONE merged batch dim, the tail padding a piece of the same
    # concatenate: the TPU compiler turns a rank-3 [P, D, n] concat of
    # size-1 batch dims into code that grows with n (minutes and GiBs
    # of host memory at 1B parameters), and a [1, n] array of a 16- or
    # 8-bit dtype occupies 2x / 4x its bytes in HBM, while the lane-row
    # form is (8, 128)-tiled -- for f32 byte-for-byte the flat buffer
    parts = [_flat_leaf(s, leaf.astype(dtype).reshape(
                 (nb,) + leaf.shape[batch_dims:]), 1).reshape(nb, -1, LANES)
             for s, leaf in zip(layout.slots, leaves)]
    tail = layout.n_pad // LANES - sum(p.shape[1] for p in parts)
    if tail:
        parts.append(jnp.zeros((nb, tail, LANES), dtype))
    buf = jnp.concatenate(parts, axis=1)
    return buf.reshape(batch + (layout.n_pad,))


@stages.in_stage("relayout")
def unflatten_tree(layout: FlatLayout, buf: jax.Array, batch_dims: int = 0,
                   cast: bool = True) -> PyTree:
    """``[*batch, n_pad]`` buffer -> pytree of slice views.

    cast=True restores each leaf's original dtype (exact for widening
    promotions); cast=False keeps ``buf.dtype`` (e.g. int8 vote bits).

    Sharded layouts reassemble each sharded leaf by concatenating its
    per-bucket blocks along ``shard_dim`` (then dropping the uneven
    ``shard_pad`` zero tail); per-bucket copies read bucket 0 (all
    copies are bit-identical by construction).
    """
    if layout.shards > 1:
        bucket = layout.bucket()
        bp = layout.bucket_pad
        parts = [
            bucket.treedef.flatten_up_to(
                unflatten_tree(bucket, buf[..., m * bp:(m + 1) * bp],
                               batch_dims=batch_dims, cast=cast))
            for m in range(layout.shards)]
        leaves = []
        for i, slot in enumerate(layout.slots):
            if slot.shard_dim is None:
                leaves.append(parts[0][i])
            else:
                ax = batch_dims + slot.shard_dim
                full = jnp.concatenate([p[i] for p in parts], axis=ax)
                if slot.shard_pad:
                    full = jax.lax.slice_in_dim(
                        full, 0, full.shape[ax] - slot.shard_pad, axis=ax)
                leaves.append(full)
        return layout.treedef.unflatten(leaves)
    batch = buf.shape[:batch_dims]
    if batch_dims > 1:      # slice ONE merged batch dim (see flatten_tree)
        buf = buf.reshape(int(functools.reduce(lambda a, b: a * b, batch, 1)),
                          buf.shape[-1])
    leaves = []
    for s in layout.slots:
        leaf = buf[..., s.offset:s.offset + s.size].reshape(batch + s.shape)
        leaves.append(leaf.astype(s.dtype) if cast else leaf)
    return layout.treedef.unflatten(leaves)


def _with_mid_axes(x: jax.Array, batch_dims: int, target_batch: int):
    """[*b, n] -> [*b, 1...1, n] broadcastable against target_batch dims."""
    for _ in range(target_batch - batch_dims):
        x = x[..., None, :]
    return x


def pack_tree(layout: FlatLayout, tree: PyTree, batch_dims: int = 0,
              delta: PyTree | None = None, rho: float = 0.0,
              delta_batch_dims: int = 0) -> jax.Array:
    """Fused (u + rho*delta) -> sign -> 1-bit pack, concatenated per word.

    Returns ``[*batch, n_pad/32]`` uint32.  The correction is added in each
    leaf's own dtype -- exactly ``u + rho * delta.astype(u.dtype)``, the
    same arithmetic the per-leaf tree path uses -- so votes stay
    bit-identical to the ``ag_packed`` transport.  Word concatenation means
    the full-precision flat buffer never exists: only the 1-bit payload is
    contiguous.  Tail words are all-ones (+1 signs), matching
    ``pack_signs`` padding.
    """
    if layout.shards > 1:
        bucket = layout.bucket()
        uts = bucket_trees(layout, tree, batch_dims)
        dts = (bucket_trees(layout, delta, delta_batch_dims)
               if delta is not None else [None] * layout.shards)
        words = [pack_tree(bucket, ut, batch_dims=batch_dims, delta=dt,
                           rho=rho, delta_batch_dims=delta_batch_dims)
                 for ut, dt in zip(uts, dts)]
        with stages.scope("sign_pack"):
            return jnp.concatenate(words, axis=-1)
    leaves = layout.treedef.flatten_up_to(tree)
    dl_leaves = (layout.treedef.flatten_up_to(delta)
                 if delta is not None else [None] * len(leaves))
    parts = []
    for slot, leaf, dl in zip(layout.slots, leaves, dl_leaves):
        u = leaf.reshape(leaf.shape[:batch_dims] + (slot.size,))
        if slot.size == 0:
            # pack_signs pads to ceil(size/32) words == 0 for empty
            # leaves, but the slot still occupies `words` all-padding
            # words (+1 signs) so later offsets stay aligned.
            parts.append(jnp.full(leaf.shape[:batch_dims] + (slot.words,),
                                  0xFFFFFFFF, jnp.uint32))
            continue
        if dl is not None and rho:
            with stages.scope("correction"):
                dlf = dl.reshape(dl.shape[:delta_batch_dims] + (slot.size,))
                dlf = _with_mid_axes(dlf, delta_batch_dims, batch_dims)
                u = u + rho * dlf.astype(u.dtype)
        with stages.scope("sign_pack"):
            w = signs.pack_signs(signs.sgn(u))            # pads to +1 bits
            if w.shape[-1] != slot.words:
                w = jnp.pad(w, [(0, 0)] * batch_dims
                            + [(0, slot.words - w.shape[-1])],
                            constant_values=jnp.uint32(0xFFFFFFFF))
        parts.append(w)
    with stages.scope("sign_pack"):
        words = jnp.concatenate(parts, axis=-1)
        tail = layout.n_words - words.shape[-1]
        if tail:
            words = jnp.pad(words, [(0, 0)] * batch_dims + [(0, tail)],
                            constant_values=jnp.uint32(0xFFFFFFFF))
    return words
