"""Document-sharded replay: pjit over a ``docs`` mesh axis.

The batch state/op tensors are laid out ``[D, ...]`` with D the document
axis; sharding them ``P("docs")`` makes XLA partition the vmapped op-fold
with no communication (each chip folds its shard of documents).  The
merge-tree path exports per-doc transfer buffers doc-sharded (fully
collective-free — each chip encodes its shard); where a step needs
cross-chip assembly (matrix resolved cells, tree/map replicated
outputs) it is a single all-gather over ICI, expressed as a replication
sharding constraint.

Multi-slice (DCN) scale-out: :func:`dcn_mesh` builds a 2-D
``("slice", "docs")`` mesh — the slice axis spans TPU slices connected
over DCN, the docs axis spans chips within a slice over ICI.  Every step
builder shards the document dimension over *all* mesh axes (pure data
parallelism across the whole fleet), so the fold itself never
communicates; only the small replicated assembly outputs (per-doc
lengths / overflow flags) cross DCN, and XLA gathers them
hierarchically — ICI within a slice first, then one small DCN exchange —
which is exactly how the reference's capability maps to TPU fabric
(SURVEY.md §5 distributed-comm: Kafka/Redis fan-out → ICI collectives
within a slice, DCN only for cross-slice assembly).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.mergetree_kernel import (
    MTOps,
    MTState,
    MergeTreeDocInput,
    _export_cold_fn,
    _export_warm_fn,
    program_name,
)
from ..protocol.summary import SummaryTree
from ..utils.telemetry import span

DOC_AXIS = "docs"
SLICE_AXIS = "slice"


def doc_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over all (or the given) devices, document-sharded."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (DOC_AXIS,))


def dcn_mesh(n_slices: int, devices: Optional[Sequence] = None) -> Mesh:
    """A 2-D ``(slice, docs)`` mesh for multi-slice deployments: outer axis
    across slices (DCN), inner axis across a slice's chips (ICI).

    Devices are grouped by their hardware slice when the platform exposes
    ``slice_index`` (real multi-slice TPU), so the inner mesh axis never
    straddles a DCN boundary; flat device lists (tests, single slice)
    reshape in order."""
    if devices is None:
        devices = jax.devices()
    devices = sorted(
        devices, key=lambda d: (getattr(d, "slice_index", 0) or 0, d.id)
    )
    if n_slices <= 0 or len(devices) % n_slices:
        raise ValueError(
            f"{len(devices)} devices do not split into {n_slices} slices"
        )
    per_slice = len(devices) // n_slices
    hw_slices = {getattr(d, "slice_index", 0) or 0 for d in devices}
    if len(hw_slices) > 1:
        # Real multi-slice hardware: every mesh row must stay within one
        # hardware slice, or "ICI" docs-axis collectives silently cross
        # DCN and the performance contract of this mesh is violated.
        for row_start in range(0, len(devices), per_slice):
            row = devices[row_start:row_start + per_slice]
            if len({getattr(d, "slice_index", 0) or 0 for d in row}) > 1:
                raise ValueError(
                    f"n_slices={n_slices} does not match the hardware "
                    f"slice grouping ({len(hw_slices)} slices of "
                    f"{len(devices) // len(hw_slices)} devices); a mesh "
                    "row would straddle a DCN boundary"
                )
    grid = np.asarray(devices).reshape(n_slices, per_slice)
    return Mesh(grid, (SLICE_AXIS, DOC_AXIS))


def _doc_spec(mesh: Mesh) -> P:
    """Shard the leading (document/op) dimension over ALL mesh axes — on a
    1-D mesh this is P("docs"); on a dcn_mesh it is P(("slice", "docs")),
    i.e. data parallelism across the whole fleet."""
    return P(tuple(mesh.axis_names))


def _pad_docs(docs: Sequence, multiple: int, make_pad):
    """Pad the doc list to a multiple of the mesh size with empty documents
    (noop streams) so the doc axis shards evenly."""
    docs = list(docs)
    while len(docs) % multiple:
        docs.append(make_pad())
    return docs


def _shard_put(mesh: Mesh, tree):
    shard = NamedSharding(mesh, _doc_spec(mesh))
    return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), shard), tree)


def _docs_per_device(export, n_padded: int, n_real: int) -> dict:
    """``{"docs_on_device_<id>": n}``: how many REAL (non-pad) documents
    each device folded, read off the doc-sharded export's own shards —
    a shard's leading-axis row range is its device's documents."""
    out: dict = {}
    if export.shape[0] != n_padded:
        return out  # not doc-major: nothing to attribute
    for shard in export.addressable_shards:
        rows = shard.index[0]
        lo = rows.start or 0
        hi = n_padded if rows.stop is None else rows.stop
        key = f"docs_on_device_{shard.device.id}"
        out[key] = out.get(key, 0) + max(0, min(hi, n_real) - lo)
    return out


def sharded_export_step(mesh: Mesh, S: int, i16: bool, ob_rows: bool,
                        ov_slots: int, i8: bool, sequential: bool,
                        has_props: bool, warm: bool,
                        digest: bool = False):
    """Mesh-sharded fold+EXPORT: the SAME cached builders as the
    single-chip path (``_export_cold_fn`` / ``_export_warm_fn``) with
    the doc-sharded placement threaded through as ``out_sharding`` — one
    derivation point, so the mesh path can never drift from the
    single-chip export pipeline.  The step widens narrow uploads
    in-graph, folds with the chunk-fact specialization, and emits the
    fused transfer buffer doc-sharded (~10× less d2h than the 13 full
    int32 state planes it replaced), with the forced row-major fetch
    layout where the backend supports layouts.  ``digest`` appends the
    per-doc state digest plane (the tier-0 delta-download gate), sharded
    like the buffer.  The fold and export are per-doc elementwise along
    the doc axis: no collective is inserted; each chip folds and encodes
    its shard."""
    shard = NamedSharding(mesh, _doc_spec(mesh))
    if warm:
        return _export_warm_fn(i16, ob_rows, ov_slots, i8, sequential,
                               has_props, out_sharding=shard,
                               digest=digest)
    return _export_cold_fn(S, i16, ob_rows, ov_slots, i8, sequential,
                           has_props, out_sharding=shard, digest=digest)


def replay_family_sharded(
    family,
    docs: Sequence,
    mesh: Optional[Mesh] = None,
    stats: Optional[dict] = None,
    stage: Optional[dict] = None,
    pack_cache=None,
    delta_cache=None,
    device_cache=None,
) -> List[SummaryTree]:
    """THE generic mesh-sharded catch-up fold (round 14): pack → narrow
    → shard over the mesh → family fold+export in-graph → shared host
    extraction, serving the IDENTICAL four-tier cache stack and
    stage-counter schema as the single-device pipeline — ``pack_cache``
    (tier 2 suffix reuse), ``delta_cache`` (tier 0 digest-gated delta
    download; only the digest plane and changed documents' rows cross
    d2h), ``device_cache`` (tier 2.5 resident upload buffers, placed
    doc-sharded; exact hits upload nothing, suffix hits splice in place)
    — with ``stage`` accumulating the
    ``pack``/``upload``/``dispatch``/``device_wait``/``download``/
    ``extract`` busy split plus ``h2d_bytes``/``d2h_bytes``.

    Every family-shaped decision rides the
    :class:`~fluidframework_tpu.ops.family.KernelFamily` hooks (the same
    descriptor the single-device pipeline consumes, plus
    ``dispatch_sharded``/``make_pad``/``pad_token``), so the merge-tree
    and tree mesh paths cannot drift from each other or from their
    single-device twins.  ``stats`` accumulates ``device_docs`` /
    ``fallback_docs`` (+ the per-reason split) exactly like the batch
    entry points, plus ``delta_docs`` for tier-0 serves."""
    from ..ops.batching import partition_replay
    from ..ops.pipeline import (
        _block_until_ready,
        _count_d2h,
        _count_h2d,
        _nbytes,
        _np_nbytes,
        delta_merge_changed,
        delta_route,
        delta_store_all,
        delta_sub_meta,
        seed_stage,
    )

    seed_stage(stage)
    if mesh is None:
        mesh = doc_mesh()
    shard = NamedSharding(mesh, _doc_spec(mesh))
    if device_cache is not None:
        device_cache.set_sharding(shard)

    def _bump_stats(st: dict) -> None:
        if stats is not None:
            for k, v in st.items():
                stats[k] = stats.get(k, 0) + v

    def fold_batch_export(batch):
        n_real = len(batch)
        pad_base = len(batch)
        padded = _pad_docs(batch, mesh.size, family.make_pad)
        # Pad docs carry a deterministic token so the padded chunk's
        # token tuple keys tiers 2/2.5 (any None would bypass both) —
        # but only when every REAL doc is tokened; a mixed chunk
        # bypasses anyway and must keep doing so.
        if family.pad_token is not None \
                and all(d.cache_token is not None for d in batch):
            for k in range(pad_base, len(padded)):
                padded[k].cache_token = family.pad_token(k)
        with span("pipeline.pack", stage, "pack", chunk=0):
            if pack_cache is not None:
                state, ops, meta = pack_cache.pack(padded)
            else:
                state, ops, meta = family.pack(padded)
            state_n, ops_n = family.narrow(padded, state, ops, meta)
        want_digest = delta_cache is not None

        # --- upload leg: resident tier or explicit sharded device_put;
        # h2d_bytes counts what really crossed either way.
        with span("pipeline.upload", stage, "upload", chunk=0):
            aux_dev = None
            if device_cache is not None:
                state_u, ops_u, aux_dev, up_bytes = device_cache.acquire(
                    state_n, ops_n, meta)
                if isinstance(jax.tree.leaves(ops_u)[0], np.ndarray):
                    # Bypass route (token-less chunk): shard-place like
                    # the plain path so the step still runs
                    # mesh-partitioned.
                    ops_u = _shard_put(mesh, ops_u)
                    state_u = _shard_put(mesh, state_u) \
                        if state_u is not None else None
            else:
                up_bytes = _np_nbytes(state_n) + _np_nbytes(ops_n)
                ops_u = _shard_put(mesh, ops_n)
                state_u = _shard_put(mesh, state_n) \
                    if state_n is not None else None
            if aux_dev is None:
                aux_host = family.aux(meta, want_digest)
                up_bytes += _np_nbytes(tuple(jax.tree.leaves(aux_host)))
                aux_dev = _shard_put(mesh, aux_host)
        _count_h2d(stage, up_bytes)

        # --- dispatch + honest device wait.
        with span("pipeline.dispatch", stage, "dispatch", chunk=0):
            export = family.dispatch_sharded(mesh, state_u, ops_u, meta,
                                             want_digest, aux_dev)
            core, dig = family.split_digest(export, want_digest)
        with span("pipeline.device_wait", stage, "device_wait", chunk=0):
            _block_until_ready(core, dig)
        _bump_stats(_docs_per_device(jax.tree.leaves(core)[0],
                                     len(padded), n_real))

        # Pad trimming: served/changed/extraction all operate on the
        # REAL prefix (pads sit at the tail), so stats and the tier-0
        # entries never see a pad; the sliced view extracts identically
        # (chunk-global meta untouched, per-doc offsets absolute).
        meta_real = dict(
            meta,
            docs=meta["docs"][:n_real],
            doc_packs=meta["doc_packs"][:n_real],
        )
        for key in family.per_doc_meta:
            if key in meta:
                meta_real[key] = np.asarray(meta[key])[:n_real]
        real_docs = meta_real["docs"]

        def trim(ex_np):
            return tuple(a[:n_real] for a in ex_np) \
                if isinstance(ex_np, tuple) else ex_np[:n_real]

        def extract(meta_x, arr, extra=()):
            with span("pipeline.extract", stage, "extract", chunk=0):
                st: dict = {}
                res = family.extract(meta_x, arr, st, stage)
                for fn in extra:
                    fn(res)
            _bump_stats(st)
            return res

        def fetch_full():
            # d2h_bytes counts the PADDED buffer — that is what crosses
            # the link; pads trim host-side after the transfer.
            with span("pipeline.download", stage, "download", chunk=0):
                raw = family.fetch(core)
            _count_d2h(stage, _nbytes(raw))
            return trim(raw)

        if dig is None:
            return extract(meta_real, fetch_full())
        with span("pipeline.download", stage, "download", chunk=0):
            dig_full = np.asarray(dig)  # the full padded plane crosses
        _count_d2h(stage, dig_full.nbytes)
        dig_np = dig_full[:n_real]
        # The shared tier-0 decision + entry publication
        # (ops/pipeline.py delta_* helpers — one derivation point with
        # the single-device pipeline); pads never enter the handshake.
        route, served, changed = delta_route(real_docs, dig_np,
                                             delta_cache)
        if route == "full":
            # Cold / all-changed / fallback route — and the golden
            # oracle the delta path is tested against.
            def store(res):
                delta_store_all(delta_cache, real_docs, dig_np, res)

            return extract(meta_real, fetch_full(), extra=(store,))
        if route == "served":
            delta_cache.note_bytes_saved(_nbytes(core))
            _bump_stats({"delta_docs": len(real_docs)})
            return [served[d] for d in range(len(real_docs))]
        with span("pipeline.download", stage, "download", chunk=0):
            sub, fetched = family.gather_rows(
                core, np.asarray(changed, np.int32))
        _count_d2h(stage, fetched)
        delta_cache.note_bytes_saved(max(0, _nbytes(core) - fetched))
        with span("pipeline.extract", stage, "extract", chunk=0):
            st: dict = {}
            got = family.extract(
                delta_sub_meta(meta_real, changed, family.per_doc_meta),
                sub, st, stage)
            res = delta_merge_changed(delta_cache, meta_real, dig_np,
                                      served, changed, got)
        st["delta_docs"] = st.get("delta_docs", 0) + len(served)
        _bump_stats(st)
        return res

    return partition_replay(
        docs, family.known_fallback, family.fallback_summary,
        fold_batch_export, stats=stats, stage=stage,
    )


def replay_mergetree_sharded(
    docs: Sequence[MergeTreeDocInput],
    mesh: Optional[Mesh] = None,
    stats: Optional[dict] = None,
    stage: Optional[dict] = None,
    pack_cache=None,
    delta_cache=None,
    device_cache=None,
) -> List[SummaryTree]:
    """Multi-chip merge-tree catch-up replay — the merge-tree instance
    of :func:`replay_family_sharded` (round 13 paid the mesh-parity
    debt; round 14 made the body family-generic).  Byte-compatible with
    the single-chip path and the CPU oracle; fetches the same fused
    (elided/int16/int8) export buffer as single-chip and uploads the
    narrow encodings."""
    from ..ops.pipeline import MERGETREE_FAMILY

    return replay_family_sharded(
        MERGETREE_FAMILY, docs, mesh=mesh, stats=stats, stage=stage,
        pack_cache=pack_cache, delta_cache=delta_cache,
        device_cache=device_cache,
    )


@functools.lru_cache(maxsize=64)
def map_sharded_replay_step(mesh: Mesh, num_keys: int, num_docs: int):
    """Jitted, mesh-sharded LWW map reduction (cached per shape — a fresh
    jit closure every call would recompile identical shapes).

    The map kernel's inputs are FLAT op arrays (one row per set/delete op,
    grouped by global key id), so the shard axis is the op axis: each chip
    reduces its op shard and XLA assembles the per-key winners with
    cross-chip collectives (the segment reductions' combiner ops ride ICI),
    returning replicated per-key results for the host summarizer."""
    from ..ops.map_kernel import _map_lww_kernel

    shard = NamedSharding(mesh, _doc_spec(mesh))
    replicated = NamedSharding(mesh, P())

    def _step(key_gid, op_seq, is_set, val_idx, key_doc,
              clear_doc, clear_seq):
        return _map_lww_kernel(
            key_gid, op_seq, is_set, val_idx, key_doc, clear_doc, clear_seq,
            num_keys=num_keys, num_docs=num_docs,
        )

    return jax.jit(
        _step,
        in_shardings=(shard, shard, shard, shard, replicated,
                      shard, shard),
        out_shardings=(replicated, replicated),
    )


def replay_map_sharded(docs, mesh: Optional[Mesh] = None,
                       stats: Optional[dict] = None) -> List[SummaryTree]:
    """Multi-chip SharedMap catch-up replay; byte-compatible with
    ``replay_map_batch`` and the CPU oracle.  ``stats`` accumulates
    ``device_docs`` exactly like the batch entry point (the LWW
    reduction has no fallback cases), so the mesh service path reports
    the same split as single-chip."""
    from ..ops.map_kernel import pack_map_batch, summaries_from_lww

    if not docs:
        return []
    if stats is not None:
        stats["device_docs"] = stats.get("device_docs", 0) + len(docs)
    if mesh is None:
        mesh = doc_mesh()
    # Bucket floor = mesh size so the flat op axis splits evenly over
    # power-of-two meshes of ANY size (buckets otherwise floor at 64).
    batch = pack_map_batch(docs, bucket_floor=mesh.size)
    shard = NamedSharding(mesh, _doc_spec(mesh))
    replicated = NamedSharding(mesh, P())

    def put(arr, sh):
        return jax.device_put(jnp.asarray(arr), sh)

    step = map_sharded_replay_step(mesh, batch.num_keys, batch.num_docs)
    present, win_val = step(
        put(batch.key_gid, shard), put(batch.op_seq, shard),
        put(batch.is_set, shard), put(batch.val_idx, shard),
        put(batch.key_doc, replicated),
        put(batch.clear_doc, shard), put(batch.clear_seq, shard),
    )
    return summaries_from_lww(batch, present, win_val)


@functools.lru_cache(maxsize=8)
def matrix_sharded_replay_step(mesh: Mesh):
    """Jitted, mesh-sharded matrix fold (cached per mesh — a fresh jit
    closure every call would recompile identical shapes): the dual-axis
    permutation streams
    (packed ``[2D, ...]``, two axis rows per matrix) partitioned along the
    doc axis; per-op resolved cell handles are assembled cross-chip for the
    host cell fold — the ICI all-gather."""
    from ..ops.matrix_kernel import replay_resolving_vmapped

    shard = NamedSharding(mesh, _doc_spec(mesh))
    replicated = NamedSharding(mesh, P())

    def _step(state: MTState, ops: MTOps):
        final, resolved = replay_resolving_vmapped(state, ops)
        resolved = jax.lax.with_sharding_constraint(resolved, replicated)
        return final, resolved

    state_shardings = MTState(
        tstart=shard, tlen=shard, ins_seq=shard, ins_client=shard,
        rem_seq=shard, rem_client=shard, rem2_seq=shard, rem2_client=shard,
        ob1_seq=shard, ob1_client=shard, ob2_seq=shard, ob2_client=shard,
        props=shard, n=shard, overflow=shard,
    )
    ops_shardings = MTOps(
        kind=shard, seq=shard, client=shard, ref_seq=shard, min_seq=shard,
        a=shard, b=shard, tstart=shard, tlen=shard, pvals=shard,
    )
    return jax.jit(
        _step,
        in_shardings=(state_shardings, ops_shardings),
        out_shardings=(state_shardings, replicated),
    )


def replay_matrix_sharded(
    docs, mesh: Optional[Mesh] = None, step=None,
    stats: Optional[dict] = None,
) -> List[SummaryTree]:
    """Multi-chip SharedMatrix catch-up replay (see replay_mergetree_sharded).

    Matrices pack as TWO axis rows each, so the doc list pads to half the
    mesh size to keep the [2D] axis evenly sharded.  ``stats``
    accumulates ``device_docs``/``fallback_docs`` like the batch entry
    point (pre-pack routing + per-axis overflow fallbacks)."""
    from ..ops.batching import partition_replay
    from ..ops.matrix_kernel import (
        MatrixDocInput,
        known_matrix_fallback,
        oracle_matrix_fallback,
        pack_matrix_batch,
        summary_from_matrix_state,
    )

    if mesh is None:
        mesh = doc_mesh()
    the_step = step if step is not None else (
        matrix_sharded_replay_step(mesh) if docs else None
    )

    def fold_batch(batch):
        import math

        n_real = len(batch)
        # Matrices pack TWO axis rows each: pad the doc count so 2·D is
        # divisible by the mesh size for ANY size (odd meshes need D to be
        # a multiple of the size itself).
        doc_mult = mesh.size // math.gcd(mesh.size, 2)
        padded = _pad_docs(
            batch, max(1, doc_mult),
            lambda: MatrixDocInput(doc_id="\x00pad", ops=[]),
        )
        state, ops, meta = pack_matrix_batch(padded)
        final, resolved = the_step(_shard_put(mesh, state),
                                   _shard_put(mesh, ops))
        state_np = {k: np.asarray(v) for k, v in final._asdict().items()}
        resolved_np = np.asarray(resolved)
        return [
            summary_from_matrix_state(meta, state_np, resolved_np, d,
                                      stats=stats)
            for d in range(n_real)
        ]

    return partition_replay(
        docs, known_matrix_fallback, oracle_matrix_fallback, fold_batch,
        stats=stats,
    )


@functools.lru_cache(maxsize=8)
def tree_sharded_replay_step(mesh: Mesh):
    """Jitted, mesh-sharded tree replay step (cached per mesh): the
    edit-fold partitioned
    along the doc axis; per-doc overflow flags (the host needs every one to
    route fallbacks) assembled cross-chip — the ICI all-gather."""
    from ..ops.tree_kernel import TreeEdits, TreeState
    from ..ops.tree_kernel import replay_vmapped as tree_replay_vmapped

    shard = NamedSharding(mesh, _doc_spec(mesh))
    replicated = NamedSharding(mesh, P())

    def _step(state: TreeState, edits: TreeEdits):
        final = tree_replay_vmapped(state, edits)
        overflow = jax.lax.with_sharding_constraint(
            final.overflow, replicated
        )
        return final, overflow

    state_shardings = TreeState(
        head=shard, next=shard, prev=shard, node_container=shard,
        container_parent=shard, value=shard, value_seq=shard,
        insert_seq=shard, removed_seq=shard, overflow=shard,
    )
    edit_shardings = TreeEdits(
        kind=shard, seq=shard, container=shard, anchor=shard,
        first=shard, tail=shard, value=shard, purge_msn=shard,
    )
    return jax.jit(
        _step,
        in_shardings=(state_shardings, edit_shardings),
        out_shardings=(state_shardings, replicated),
    )


@functools.lru_cache(maxsize=16)
def tree_sharded_export_step(mesh: Mesh, digest: bool):
    """Jitted, mesh-sharded tree fold+EXPORT (cached per mesh/digest):
    the vmapped edit-fold partitioned along the doc axis, the final
    forest planes emitted doc-sharded (each chip encodes its shard;
    the host trims pads after the transfer), and — under ``digest`` —
    the per-doc ``[D, 2]`` digest plane appended LAST, sharded like the
    planes.  The tree family's ``dispatch_sharded`` hook; the fold is
    per-doc elementwise, so no collective is inserted."""
    from ..ops.tree_kernel import TreeEdits, TreeState
    from ..ops.tree_pipeline import tree_fold_export

    shard = NamedSharding(mesh, _doc_spec(mesh))

    def _step(state: TreeState, edits: TreeEdits, n_nodes, n_cont):
        return tree_fold_export(state, edits, n_nodes, n_cont, digest)

    _step.__name__ = _step.__qualname__ = program_name("tree", digest)
    n_out = len(TreeState._fields) + (1 if digest else 0)
    return jax.jit(
        _step,
        in_shardings=(
            TreeState(*([shard] * len(TreeState._fields))),
            TreeEdits(*([shard] * len(TreeEdits._fields))),
            shard, shard,
        ),
        out_shardings=(shard,) * n_out,
    )


def replay_tree_sharded(
    docs, mesh: Optional[Mesh] = None,
    stats: Optional[dict] = None,
    stage: Optional[dict] = None,
    pack_cache=None,
    delta_cache=None,
    device_cache=None,
) -> List[SummaryTree]:
    """Multi-chip SharedTree catch-up replay — the SECOND instance of
    :func:`replay_family_sharded` (ISSUE 14): the tree route serves the
    identical four-tier stack and stage schema as the merge-tree mesh
    fold.  ``stats`` accumulates ``device_docs``/``fallback_docs`` (with
    the per-reason split: revive / multi-id move / MAX_DEPTH overflow /
    purged-parent inserts / limbo bases) like the batch entry point."""
    from ..ops.tree_pipeline import TREE_FAMILY

    return replay_family_sharded(
        TREE_FAMILY, docs, mesh=mesh, stats=stats, stage=stage,
        pack_cache=pack_cache, delta_cache=delta_cache,
        device_cache=device_cache,
    )
