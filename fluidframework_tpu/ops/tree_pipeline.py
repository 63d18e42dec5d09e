"""SharedTree as the SECOND kernel family of the generic catch-up
pipeline (ISSUE 14 tentpole; PAPER §0 names exactly two vmap-able
kernels — the merge-tree op-apply loop and the SharedTree rebaser — and
through round 13 only the first rode the cache/pipeline stack).

This module binds ``ops/tree_kernel.py`` into the four-tier pipeline of
``ops/pipeline.py``:

- **tier 2** (:class:`~fluidframework_tpu.ops.pipeline.PackCache` over
  :data:`TREE_FAMILY`): packed forest windows reuse across catch-ups —
  an exact edit-window hit costs a dict lookup, a grown tail packs ONLY
  its suffix messages onto copies of the cached planes through the SAME
  per-message fill the fresh pack uses
  (``tree_kernel.fill_tree_doc_messages`` — byte drift is impossible by
  construction).  Tree suffixes, unlike merge-tree ones, also
  materialize NEW node/container state rows; those land strictly in the
  per-doc row suffixes of the state planes (interning is append-only and
  edits never rewrite a base row at pack time), which is what makes the
  tier-2.5 splice below sound;
- **tier 2.5** (:class:`~fluidframework_tpu.ops.device_cache.
  DevicePackCache` with :class:`TreeDeviceOps`): forest + edit planes
  stay device-resident; an exact window dispatches with ZERO h2d pack
  bytes, a lineage-proven grown tail uploads only its new edit rows AND
  its newly-materialized node/container rows, spliced in place over
  three donated row axes;
- **tier 0** (the family-agnostic ``DeltaExportCache``): the fold
  exports a per-doc ``[D, 2]`` digest of the FINAL forest arrays
  (:func:`tree_doc_digests`, masked to each doc's used node/container
  rows so bucket padding and neighbours' growth never perturb it);
  unchanged documents serve their cached summaries with no download,
  changed documents gather only their rows;
- tier 1 (the folded-result cache) needs nothing: it was always
  family-agnostic.

``pipelined_tree_replay`` is the drop-in bulk entry point with the full
``pack/upload/dispatch/device_wait/download/extract`` +
``h2d_bytes``/``d2h_bytes`` stage schema; the mesh twin rides
``parallel/shard.replay_tree_sharded`` through the same family hooks.
Fallback routing (revive / multi-id move / MAX_DEPTH / purged-parent
inserts / limbo bases) is byte-exact as ever — and now counted PER
REASON through ``ops/batching.count_fallback``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .device_cache import (
    DevicePackCache,
    gather_suffix_rows,
    splice_row_planes,
    tuple_sig,
)
from .family import KernelFamily
from .interning import Interner
from .mergetree_kernel import (
    _mix_u32,
    export_to_numpy,
    gather_export_rows,
    program_name,
)
from .pipeline import (
    PackCache,
    _copy_interner,
    _mt_pad_token,
    pipelined_family_replay,
)
from .tree_kernel import (
    TreeDocInput,
    TreeEdits,
    TreeState,
    fill_tree_doc_messages,
    known_tree_fallback,
    oracle_fallback_summary,
    pack_tree_batch,
    replay_vmapped,
    scatter_tree_doc_rows,
    summaries_from_tree_export,
    tree_buckets,
)

__all__ = [
    "TREE_FAMILY",
    "TreeDeviceOps",
    "pipelined_tree_replay",
    "summaries_from_tree_export",
    "tree_device_cache",
    "tree_doc_digests",
    "tree_pack_cache",
]


# ---------------------------------------------------------------------------
# Device digest over the final forest arrays (the tier-0 gate)
# ---------------------------------------------------------------------------

#: node-axis planes entering the digest (order = salt index); container
#: planes follow at _CONT_SALT_BASE.
_DIGEST_NODE_PLANES = ("next", "prev", "node_container", "value",
                       "value_seq", "insert_seq", "removed_seq")
_DIGEST_CONT_PLANES = ("head", "container_parent")
_CONT_SALT_BASE = 8
#: active-row value mask: XORed into live values so a stored 0 at an
#: active position never aliases a masked (padding) position's zero
#: contribution.
_ACTIVE_XOR = 0xA5A5A5A5


def tree_doc_digests(final: TreeState, n_nodes: jnp.ndarray,
                     n_cont: jnp.ndarray) -> jnp.ndarray:
    """``[D, 2]`` int32 digest of each document's final forest — the
    device-computed identity the tier-0 delta path compares before
    deciding which documents' state rows must cross the d2h link.

    Properties the delta path relies on (pinned by tests):

    - **masked**: only rows the document actually interned
      (``idx < n_nodes[d]`` / ``idx < n_cont[d]``) contribute — bucket
      padding (which legitimately grows when a NEIGHBOUR document in
      the chunk grows) never reaches the hash, and the fold provably
      never writes past the interned rows (every edit targets an
      interned index);
    - **position-salted**: weights are per (plane, row-index), so two
      different forests cannot cancel by swapping rows; live values XOR
      a constant so value 0 at a live row stays distinct from absence;
    - 64 bits across two independently-salted lanes, ``overflow`` mixed
      in (an overflowed doc routes to the oracle — its digest must not
      alias the non-overflowed fold of other inputs); every structural
      failure (missing entry, anchor drift, digest mismatch) falls back
      to the full download, so a collision is the only wrong-serve path
      and the host anchor already pins the op-list identity.
    """
    D, N = final.next.shape
    C = final.head.shape[1]
    node_idx = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
    cont_idx = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    act_n = node_idx < n_nodes[:, None]
    act_c = cont_idx < n_cont[:, None]
    node_u = node_idx.astype(jnp.uint32)
    cont_u = cont_idx.astype(jnp.uint32)
    accs = []
    for lane_salt in (jnp.uint32(0x9E3779B9), jnp.uint32(0x85EBCA6B)):
        acc = jnp.zeros((D,), jnp.uint32)
        for i, f in enumerate(_DIGEST_NODE_PLANES):
            v = jnp.where(
                act_n,
                getattr(final, f).astype(jnp.uint32)
                ^ jnp.uint32(_ACTIVE_XOR),
                jnp.uint32(0))
            w = _mix_u32(node_u * jnp.uint32(0x01000193)
                         + jnp.uint32(i) + lane_salt)
            acc = acc + (v * w).sum(axis=1, dtype=jnp.uint32)
        for i, f in enumerate(_DIGEST_CONT_PLANES):
            v = jnp.where(
                act_c,
                getattr(final, f).astype(jnp.uint32)
                ^ jnp.uint32(_ACTIVE_XOR),
                jnp.uint32(0))
            w = _mix_u32(cont_u * jnp.uint32(0x01000193)
                         + jnp.uint32(_CONT_SALT_BASE + i) + lane_salt)
            acc = acc + (v * w).sum(axis=1, dtype=jnp.uint32)
        acc = acc ^ _mix_u32(n_nodes.astype(jnp.uint32) + lane_salt)
        acc = acc ^ _mix_u32(n_cont.astype(jnp.uint32) * jnp.uint32(3)
                             + lane_salt)
        acc = acc ^ jnp.where(final.overflow, jnp.uint32(0x5BD1E995),
                              jnp.uint32(0))
        accs.append(_mix_u32(acc))
    return jax.lax.bitcast_convert_type(
        jnp.stack(accs, axis=-1), jnp.int32)


# ---------------------------------------------------------------------------
# Dispatch / extraction (the family's export legs)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=4)
def _tree_export_fn(digest: bool):
    """Jitted fold+export: the vmapped edit-fold with the final forest
    planes as a flat tuple (``TreeState`` field order; ``overflow``
    rides as a plane — the host routes MAX_DEPTH fallbacks off it) and,
    under ``digest``, the ``[D, 2]`` digest plane appended LAST — the
    same split contract as the merge-tree export."""

    def run(state: TreeState, edits: TreeEdits, n_nodes, n_cont):
        return tree_fold_export(state, edits, n_nodes, n_cont, digest)

    run.__name__ = run.__qualname__ = program_name("tree", digest)
    return jax.jit(run)


def tree_fold_export(state: TreeState, edits: TreeEdits, n_nodes, n_cont,
                     digest: bool) -> tuple:
    """The traced body of the tree fold+export programs (single-device
    and mesh): the final forest planes in ``TreeState`` field order (the
    export is the planes themselves), then the digest plane under
    ``digest``."""
    with jax.named_scope("fold"):
        final = replay_vmapped(state, edits)
    out = tuple(final)
    if digest:
        with jax.named_scope("digest"):
            out = out + (tree_doc_digests(final, n_nodes, n_cont),)
    return out


def _tree_aux(meta: dict, digest: bool):
    """Per-doc used-row counts — the digest's mask inputs (tiny [D]
    planes; uploaded, or served device-resident by tier 2.5)."""
    return (np.asarray(meta["n_nodes"], np.int32),
            np.asarray(meta["n_cont"], np.int32))


def _tree_dispatch(state: TreeState, edits: TreeEdits, meta: dict,
                   digest: bool, aux_dev):
    if aux_dev is None:
        aux_dev = _tree_aux(meta, digest)
    n_nodes, n_cont = aux_dev
    return _tree_export_fn(digest)(state, edits, n_nodes, n_cont)


def _tree_dispatch_sharded(mesh, state: TreeState, edits: TreeEdits,
                           meta: dict, digest: bool, aux_dev):
    from ..parallel.shard import tree_sharded_export_step

    if aux_dev is None:
        aux_dev = _tree_aux(meta, digest)
    n_nodes, n_cont = aux_dev
    return tree_sharded_export_step(mesh, digest)(state, edits,
                                                  n_nodes, n_cont)


def _split_tree_digest(export, digested: bool):
    """``(core, digest_or_None)``: the digest plane rides LAST."""
    if not digested:
        return export, None
    return export[:-1], export[-1]


def _tree_narrow(chunk, state, edits, meta):
    """No transfer-narrowing for the forest planes (all int32; the
    linked-list indices and seqs genuinely span the int32 range at
    bucket scale) — state uploads cold AND warm (a cold doc's base
    rows are the materialized insert blocks, not derivable in-graph)."""
    return state, edits


# ---------------------------------------------------------------------------
# Tier 2: the suffix extension (family ``extend`` hook)
# ---------------------------------------------------------------------------


def _copy_tree_pack(pack):
    from .tree_kernel import _DocPack

    out = _DocPack.__new__(_DocPack)
    out.node_ids = _copy_interner(pack.node_ids)
    out.node_types = list(pack.node_types)
    out.containers = _copy_interner(pack.containers)
    out.fallback_reason = pack.fallback_reason
    out.header_seq = pack.header_seq
    out.base_min_seq = pack.base_min_seq
    out.removal_time = dict(pack.removal_time)
    out.boundary = pack.boundary
    return out


def _extend_tree(entry, chunk: Sequence[TreeDocInput]):
    """Pack only each doc's suffix messages on top of the cached forest
    planes; None = shape buckets do not hold (caller full-packs).

    Soundness: interning is append-only (old node/container indices are
    stable), per-message fills only MATERIALIZE new rows — a suffix edit
    referencing an existing node adds an edit row, never rewrites a
    packed state row — so the combined arrays are the cached arrays plus
    per-doc row suffixes, written through the SAME fill/scatter helpers
    as a fresh pack."""
    meta = entry.meta
    T = entry.ops.kind.shape[1]
    N = entry.state.next.shape[1]
    C = entry.state.head.shape[1]
    # The shared sizing predicate over the COMBINED windows must land in
    # the SAME buckets (estimates are upper bounds of used rows, so an
    # equal bucket proves the cached arrays are large enough for N/T) —
    # tree_buckets is the ONE derivation point, shared with the fresh
    # pack.
    if tree_buckets(chunk) != (N, T):
        return None

    values: Interner = meta["values"]  # shared, append-only
    doc_packs = [_copy_tree_pack(p) for p in meta["doc_packs"]]
    filled = []
    try:
        for d, doc in enumerate(chunk):
            pack = doc_packs[d]
            suffix = doc.ops[entry.n_ops[d]:]
            node_rows, chains, edit_rows = {}, {}, []
            fill_tree_doc_messages(pack, values, node_rows, chains,
                                   edit_rows, suffix)
            filled.append((node_rows, chains, edit_rows))
    except ValueError:
        # An edit shape this fill doesn't know must degrade to a full
        # pack — which raises the same error if genuinely malformed —
        # never crash only-when-warm.  Interner appends already made are
        # unreferenced and harmless.
        return None
    old_t = entry.t_rows
    if any(len(p.containers) > C for p in doc_packs) \
            or any(len(p.node_ids) > N for p in doc_packs) \
            or any(old_t[d] + len(rows) > T
                   for d, (_n, _c, rows) in enumerate(filled)):
        return None  # container bucket (unsized by the estimate) grew
    old_n = np.asarray(meta["n_nodes"])
    for d, (node_rows, _chains, _rows) in enumerate(filled):
        if node_rows and min(node_rows) < int(old_n[d]):
            # A suffix spec re-interned an EXISTING node id (a
            # duplicate-id stream — nothing validates client-minted
            # ids): the rewrite lands BELOW the cached row watermark,
            # which the device-resident splice (strictly rows >=
            # watermark) could never mirror.  Full repack keeps every
            # tier byte-exact — lose the win, never corrupt.
            return None

    # Commit: copy the cached planes (the entry must stay intact) and
    # scatter ONLY the new rows through the shared scatter.
    st = {f: np.copy(getattr(entry.state, f)) for f in TreeState._fields}
    ed = {f: np.copy(getattr(entry.ops, f)) for f in TreeEdits._fields}
    old_cont = np.asarray(meta["n_cont"])
    for d, (node_rows, chains, edit_rows) in enumerate(filled):
        scatter_tree_doc_rows(st, ed, d, node_rows, chains, edit_rows,
                              doc_packs[d].containers.values,
                              t_base=int(old_t[d]),
                              cont_start=int(old_cont[d]))
    new_meta = dict(
        meta,
        docs=list(chunk),
        doc_packs=doc_packs,
        n_nodes=np.asarray([len(p.node_ids) for p in doc_packs],
                           np.int32),
        n_cont=np.asarray([len(p.containers) for p in doc_packs],
                          np.int32),
        t_rows=np.asarray(
            [int(old_t[d]) + len(rows)
             for d, (_n, _c, rows) in enumerate(filled)], np.int32),
    )
    return TreeState(**st), TreeEdits(**ed), new_meta


def _tree_entry_rows(chunk, meta):
    return [int(x) for x in np.asarray(meta["t_rows"])]


def _tree_entry_nbytes(state, edits, meta) -> int:
    # The retained HOST meta rides the entry too: the shared value
    # interner plus each doc's id/container interners and purge
    # bookkeeping (flat deterministic per-item estimates — the LRU
    # budget must track real memory, not just the int32 planes; the
    # merge-tree twin counts its arena the same way).
    host = len(meta["values"]) * 8
    for p in meta["doc_packs"]:
        host += (len(p.node_ids) + len(p.containers)) * 64
        host += (len(p.removal_time) + len(p.node_types)) * 32
    return int(sum(np.asarray(x).nbytes for x in edits)
               + sum(np.asarray(x).nbytes for x in state) + host)


# ---------------------------------------------------------------------------
# Tier 2.5: the tree device-ops (three donated splice axes)
# ---------------------------------------------------------------------------


class _TreeNodePlanes(NamedTuple):
    """The ``[D, N]`` node-axis planes of :class:`TreeState` — the
    second splice group (suffix inserts materialize new node rows)."""

    next: jnp.ndarray
    prev: jnp.ndarray
    node_container: jnp.ndarray
    value: jnp.ndarray
    value_seq: jnp.ndarray
    insert_seq: jnp.ndarray
    removed_seq: jnp.ndarray


class _TreeContPlanes(NamedTuple):
    """The ``[D, C]`` container-axis planes — the third splice group."""

    head: jnp.ndarray
    container_parent: jnp.ndarray


def _group(tuple_type, tree):
    return tuple_type(*(getattr(tree, f) for f in tuple_type._fields))


class TreeDeviceOps:
    """The tree family's tier-2.5 hooks.  All planes are int32 (no
    narrow encodings → ``migrate`` is a no-op and any signature move is
    a genuine bucket change), the aux planes are the per-doc used-row
    counts the digest masks by, and a suffix splice writes THREE donated
    row axes: edit rows (like the merge-tree op splice) plus the node
    and container state rows the suffix's inserts materialized."""

    @staticmethod
    def sig(state, edits) -> tuple:
        return tuple_sig(state, edits)

    @staticmethod
    def aux(meta):
        return _tree_aux(meta, True)

    @staticmethod
    def t_rows(host_edits) -> np.ndarray:
        return np.count_nonzero(
            np.asarray(host_edits.kind), axis=1).astype(np.int32)

    @staticmethod
    def entry_aux(meta):
        """Host row-count snapshot the NEXT splice diffs against."""
        return (np.asarray(meta["n_nodes"], np.int32),
                np.asarray(meta["n_cont"], np.int32))

    def migrate(self, cache, tokens, entry, sig, docs) -> None:
        return  # int32-only planes: no encoding flip exists

    def splice(self, cache: DevicePackCache, entry, docs,
               state: TreeState, edits: TreeEdits, meta: dict,
               sharding) -> Optional[int]:
        t_new = self.t_rows(edits)
        t_old = np.asarray(entry.t_rows, np.int32)
        n_new, c_new = self.aux(meta)
        n_old, c_old = entry.aux
        if np.any(t_new < t_old) or np.any(n_new < n_old) \
                or np.any(c_new < c_old):
            return None
        # Pre-flight EVERY host gather before the first donation: a
        # bail after donating would leave the entry half-spliced.
        ed_rows, _ = gather_suffix_rows(TreeEdits, edits, t_old, t_new)
        if ed_rows is None:
            return None  # suffix ~ whole buffer: full upload is cheaper
        node_rows = cont_rows = None
        if np.any(n_new > n_old):
            node_rows, _ = gather_suffix_rows(
                _TreeNodePlanes, _group(_TreeNodePlanes, state),
                n_old, n_new)
            if node_rows is None:
                return None
        if np.any(c_new > c_old):
            cont_rows, _ = gather_suffix_rows(
                _TreeContPlanes, _group(_TreeContPlanes, state),
                c_old, c_new)
            if cont_rows is None:
                return None
        uploaded = sum(v.nbytes for v in ed_rows.values()) \
            + 2 * t_new.nbytes
        new_edits = splice_row_planes(
            TreeEdits, entry.ops,
            TreeEdits(**{f: cache.put(v, sharding)
                         for f, v in ed_rows.items()}),
            cache.put(t_old, sharding),
            cache.put(t_new - t_old, sharding))
        entry.ops = new_edits
        node_group = _group(_TreeNodePlanes, entry.state)
        if node_rows is not None:
            uploaded += sum(v.nbytes for v in node_rows.values()) \
                + 2 * n_new.nbytes
            node_group = splice_row_planes(
                _TreeNodePlanes, node_group,
                _TreeNodePlanes(**{f: cache.put(v, sharding)
                                   for f, v in node_rows.items()}),
                cache.put(n_old, sharding),
                cache.put(n_new - n_old, sharding))
        cont_group = _group(_TreeContPlanes, entry.state)
        if cont_rows is not None:
            uploaded += sum(v.nbytes for v in cont_rows.values()) \
                + 2 * c_new.nbytes
            cont_group = splice_row_planes(
                _TreeContPlanes, cont_group,
                _TreeContPlanes(**{f: cache.put(v, sharding)
                                   for f, v in cont_rows.items()}),
                cache.put(c_old, sharding),
                cache.put(c_new - c_old, sharding))
        # Reassemble the resident state from the (possibly spliced)
        # groups; ``overflow`` is an input plane that suffix packs never
        # touch (always the initial zeros), so it carries over.
        entry.state = TreeState(
            head=cont_group.head,
            container_parent=cont_group.container_parent,
            overflow=entry.state.overflow,
            **{f: getattr(node_group, f)
               for f in _TreeNodePlanes._fields})
        # The digest masks by the NEW counts: refresh the resident aux
        # planes (tiny upload, counted).
        entry.base = (cache.put(n_new, sharding),
                      cache.put(c_new, sharding))
        uploaded += 2 * n_new.nbytes
        # Advance the splice watermark (the merge-tree twin does the
        # same): the NEXT splice must gather only rows past THIS one,
        # not re-upload everything since the last full store.
        entry.t_rows = t_new
        return int(uploaded)


# ---------------------------------------------------------------------------
# The family instance + public entry points
# ---------------------------------------------------------------------------


TREE_FAMILY = KernelFamily(
    name="tree",
    known_fallback=known_tree_fallback,
    fallback_summary=oracle_fallback_summary,
    pack=pack_tree_batch,
    entry_rows=_tree_entry_rows,
    entry_nbytes=_tree_entry_nbytes,
    extend=_extend_tree,
    order=lambda batch, schedule: list(range(len(batch))),
    narrow=_tree_narrow,
    aux=_tree_aux,
    dispatch=_tree_dispatch,
    split_digest=_split_tree_digest,
    chunk_tag=lambda meta: None,
    fetch=export_to_numpy,
    gather_rows=gather_export_rows,
    extract=lambda meta, arr, st, stage: summaries_from_tree_export(
        meta, arr, stats=st, stage=stage),
    per_doc_meta=("n_nodes", "n_cont", "t_rows"),
    make_pad=lambda: TreeDocInput(doc_id="\x00pad", ops=[]),
    pad_token=_mt_pad_token,
    dispatch_sharded=_tree_dispatch_sharded,
)


def tree_pack_cache(max_bytes: int = 192 << 20) -> PackCache:
    """A tier-2 pack cache bound to the tree family."""
    return PackCache(max_bytes, family=TREE_FAMILY)


def tree_device_cache(max_bytes: int = 192 << 20,
                      sharding=None) -> DevicePackCache:
    """A tier-2.5 device-resident cache bound to the tree family."""
    return DevicePackCache(max_bytes, sharding=sharding,
                           device_ops=TreeDeviceOps())


def pipelined_tree_replay(docs: Sequence[TreeDocInput], **kwargs):
    """Bulk SharedTree catch-up through the generic four-tier pipeline —
    the second instance of ``pipelined_family_replay`` (the merge-tree
    entry point is ``pipelined_mergetree_replay``).  Byte-identical to
    ``replay_tree_batch`` and the ``dds/tree.py`` oracle with every
    cache on, off, or freshly invalidated (pinned by
    tests/test_tree_pipeline.py)."""
    return pipelined_family_replay(TREE_FAMILY, docs, **kwargs)
