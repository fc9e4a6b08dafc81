"""A train state that crosses a jit boundary as one buffer per shape, not one per leaf.

A jitted call pays host time for every buffer that comes out of it: on a TPU v5e
about 48 us each, the allocation of the output buffer, and about 1 us for each that
goes in (``PERF.md``, PR 29).  DreamerV3-XL's carry of parameters and Adam moments
is 552 leaves, 421 of them vectors and small kernels of a few shapes.  ``pack`` stacks
the small leaves of one ``(shape, dtype)`` on a new leading axis and leaves a large
leaf a buffer of its own (``ALONE_BYTES``); ``Packed.unpack`` takes row ``i`` of its
class's buffer, a slice on the major axis.  A jitted function unpacks at entry, works
on the tree as before, and packs at exit by the same spec (``utils/blocks.py::_open``):
only the boundary changes.

``Packed`` is a pytree whose leaves are the class buffers and whose aux data is the
``PackSpec``, so it passes through ``jax.jit``, ``jax.device_get`` and
``jax.tree.map`` as any tree does; two trees of one structure share one spec, so
nothing recompiles.  Outside jit it iterates and indexes as the tree it packs (one
jitted call that splits the stacked buffers; a buffer that is one leaf is handed on as
it is: for a recorder or the end of a run, never for a steady loop), and
``jax.device_get(packed).unpack()`` is the tree on the host (what a checkpoint writes).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp


#: A leaf this large keeps a buffer of its own.  What a stacked buffer saves is the host's
#: ~50 us for one more output buffer; what it costs is on the device: XLA writes a
#: parameter and its two Adam moments (and the norms ``Health/*`` takes of them) in one
#: fusion with three outputs, and rows of one stacked output it writes by three fusions
#: that each read their inputs again.  With DreamerV3-XL's 2.4 GiB all stacked the block
#: took 8 ms more on the chip, with the leaves under a MiB stacked (421 of 552, 9 MiB)
#: it took what it took before (``PERF.md``, PR 29).
ALONE_BYTES = 1 << 20


def _class_key(leaf: Any) -> Tuple[Any, ...]:
    """``(shape, dtype)``, and the sharding of a leaf laid out over several devices:
    leaves sharded differently are never stacked."""
    key = (tuple(jnp.shape(leaf)), jnp.dtype(jnp.result_type(leaf)))
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or len(sharding.device_set) == 1:
        return key
    return (*key, sharding)


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """How a tree's leaves lie in class buffers: its treedef, each class's key and
    size in the order the classes first appear, and each leaf's ``(class, row)``."""

    treedef: Any
    classes: Tuple[Tuple[Any, ...], ...]
    sizes: Tuple[int, ...]
    slots: Tuple[Tuple[int, int], ...]

    @classmethod
    def of(cls, tree: Any, alone: int = ALONE_BYTES) -> "PackSpec":
        """A leaf of ``alone`` bytes or more is a class of its own."""
        leaves, treedef = jax.tree.flatten(tree)
        index, sizes, slots = {}, [], []
        for i, leaf in enumerate(leaves):
            key = _class_key(leaf)
            if math.prod(key[0]) * key[1].itemsize >= alone:
                key = (*key, i)
            c = index.setdefault(key, len(index))
            if c == len(sizes):
                sizes.append(0)
            slots.append((c, sizes[c]))
            sizes[c] += 1
        return cls(treedef, tuple(index), tuple(sizes), tuple(slots))

    def rows(self, tree: Any) -> List[List[Any]]:
        """The leaves of ``tree`` (of this spec's structure and shapes), class by class."""
        rows: List[List[Any]] = [[] for _ in self.sizes]
        for leaf, (c, _) in zip(self.treedef.flatten_up_to(tree), self.slots):
            rows[c].append(leaf)
        return rows

    def pack(self, tree: Any) -> "Packed":
        """``tree`` as class buffers, for use inside a jit.  A class of one leaf keeps its
        buffer as it is: no stack, no extra axis."""
        return Packed(tuple(r[0] if len(r) == 1 else jnp.stack(r) for r in self.rows(tree)), self)


@jax.tree_util.register_pytree_node_class
class Packed:
    """Class buffers and the spec that says which leaf is which row."""

    __slots__ = ("buffers", "spec")

    def __init__(self, buffers: Tuple[Any, ...], spec: PackSpec):
        self.buffers = tuple(buffers)
        self.spec = spec

    def tree_flatten(self):
        return self.buffers, self.spec

    @classmethod
    def tree_unflatten(cls, spec, buffers):
        return cls(buffers, spec)

    def _assemble(self, rows: List[Any]) -> Any:
        return self.spec.treedef.unflatten([rows[c][row] for c, row in self.spec.slots])

    def unpack(self) -> Any:
        """The packed tree.  Call it inside a jit (the slices are offsets into the
        entry buffers) or on host copies; on device arrays outside a jit every row
        would be a dispatch of its own: iterate or index instead."""
        return self._assemble([[b] if n == 1 else b for b, n in zip(self.buffers, self.spec.sizes)])

    def __iter__(self):
        return iter(_unpack(self))

    def __getitem__(self, key):
        return _unpack(self)[key]

    def __repr__(self) -> str:
        return f"Packed({len(self.spec.slots)} leaves in {len(self.buffers)} buffers)"


# Outside a jit only the stacked classes go through a jitted call: a buffer that is one
# leaf is that leaf, so a large leaf is neither copied nor held twice.
_split = jax.jit(lambda stacked: [list(b) for b in stacked])
_stack = jax.jit(lambda rows: [jnp.stack(r) for r in rows])


def _unpack(packed: Packed) -> Any:
    sizes = packed.spec.sizes
    split = iter(_split([b for b, n in zip(packed.buffers, sizes) if n > 1]))
    return packed._assemble([[b] if n == 1 else next(split) for b, n in zip(packed.buffers, sizes)])


def pack(tree: Any, alone: int = ALONE_BYTES) -> Packed:
    """``tree`` packed by its own spec (``PackSpec.of(tree, alone)``), in one jitted call."""
    spec = PackSpec.of(tree, alone)
    rows = spec.rows(tree)
    stacked = iter(_stack([r for r in rows if len(r) > 1]))
    return Packed(tuple(jnp.asarray(r[0]) if len(r) == 1 else next(stacked) for r in rows), spec)
