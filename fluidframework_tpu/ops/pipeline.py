"""Chunked, pipelined bulk catch-up replay — the PRODUCT's version of
the bench harness's e2e loop (SURVEY §3.2: catch-up is the north-star
path, and the service must not be slower than the benchmark of itself).

Round 14: the pipeline is KERNEL-FAMILY-GENERIC.  Everything below —
chunking, the thread-pool pack/extract legs, the single-device-thread
dispatch/fetch contract, the tier-2 :class:`PackCache`, the tier-2.5
device-resident handshake, the tier-0 digest-gated delta download, and
the ``pack/upload/dispatch/device_wait/download/extract`` +
``h2d_bytes``/``d2h_bytes`` stage schema — runs through a
:class:`~fluidframework_tpu.ops.family.KernelFamily` descriptor.
``pipelined_mergetree_replay`` is the merge-tree instance;
``ops/tree_pipeline.py`` registers the SharedTree rebaser as the second
(the PAPER §0 pair), and a third family (matrix) can ride for free.

Shape (round-5 pipeline, BASELINE.md):

- documents are chunked (``chunk_docs``) so jitted shapes stay bucketed
  and per-transfer sizes bounded;
- chunks are fact-scheduled (annotate-free docs grouped) so the majority
  volume folds with the props plane traced away — results return in the
  CALLER's order regardless;
- packing (C++, GIL-released) runs in a thread pool; extraction
  likewise; ALL device interaction — dispatch, ``copy_to_host_async``,
  the blocking fetch — stays on the calling thread.  Round 5's chip run
  saw the device client slow persistently (~70–90 ms/call) when a second
  thread fetched while another dispatched, and a single device thread
  also serializes correctly on every backend;
- the blocking fetch trails the dispatch front by ``fetch_depth`` chunks
  so upload/fold/download overlap without a second device thread;
- oracle-fallback docs route around the device exactly like
  ``replay_mergetree_batch`` (shared ``partition_replay`` + post-fold
  overflow handling inside ``summaries_from_export``).
"""

from __future__ import annotations

import collections
import itertools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

import jax
import numpy as np

from ..utils.telemetry import span
from .batching import partition_replay
from .family import KernelFamily
from .interning import Interner, next_bucket_fine
from .mergetree_kernel import (
    I8_LIMIT,
    I16_LIMIT,
    K_INSERT,
    K_NOOP,
    K_OBLITERATE,
    MTOps,
    MergeTreeDocInput,
    NOT_REMOVED,
    _export_flags,
    export_to_numpy,
    fill_sequence_op_rows,
    gather_export_rows,
    known_oracle_fallback,
    narrow_ops_for_upload,
    narrow_state_for_upload,
    oracle_fallback_summary,
    ov_slot_count,
    pack_mergetree_batch,
    replay_export,
    split_export_digest,
    stream_counts,
    summaries_from_export,
    tail_remover_counts,
)
from .native_pack import decode_string_ops, stream_offset, stream_seq_at


# ---------------------------------------------------------------------------
# Pack cache (tier 2 of the catch-up cache): packed-chunk reuse
# ---------------------------------------------------------------------------


def _copy_interner(src: Interner) -> Interner:
    out = Interner()
    out._by_key = dict(src._by_key)
    out.values = list(src.values)
    return out


def _copy_doc_pack(pack):
    from .mergetree_kernel import _DocPack

    out = _DocPack()
    out.clients = _copy_interner(pack.clients)
    out.interval_ops = list(pack.interval_ops)
    out.needs_fallback = pack.needs_fallback
    return out


#: monotone pack-generation ids — the lineage tokens tier 2 stamps into
#: ``meta["_pack_lineage"]`` so the device-resident tier (tier 2.5,
#: ops/device_cache.py) can PROVE a set of host arrays is the literal
#: suffix-extension of what it holds resident.  itertools.count.__next__
#: is atomic under CPython, so the stamp needs no extra locking.  ONE
#: counter across every family: a generation id never collides between
#: the merge-tree and tree caches.
_PACK_GEN = itertools.count(1)


class _PackEntry:
    """One cached packed window: the wide (pre-narrow) chunk arrays plus
    the per-doc window bookkeeping needed to match and extend it."""

    __slots__ = ("tokens", "n_ops", "first_seq", "last_seq", "t_rows",
                 "state", "ops", "meta", "nbytes", "gen")

    def __init__(self, tokens, n_ops, first_seq, last_seq, t_rows,
                 state, ops, meta, nbytes, gen=0):
        self.gen = gen
        self.tokens = tokens
        self.n_ops = n_ops
        self.first_seq = first_seq
        self.last_seq = last_seq
        self.t_rows = t_rows
        self.state = state
        self.ops = ops
        self.meta = meta
        self.nbytes = nbytes


def doc_window(doc):
    """``(op count, first seq, last seq)`` of a document's tail, the window
    tiers 0, 2 and 2.5 match on; a stream document's is read off its
    record headers."""
    if getattr(doc, "binary_ops", None) is not None:
        n, _bytes, _chars, first, last = stream_counts(doc)
        return n, first, last
    n = len(doc.ops)
    if n == 0:
        return 0, 0, 0
    return n, doc.ops[0].seq, doc.ops[-1].seq


def _seq_at(doc, i: int) -> int:
    blob = getattr(doc, "binary_ops", None)
    return doc.ops[i].seq if blob is None else stream_seq_at(blob, i)


def match_windows(n_ops, first_seq, last_seq, chunk) -> Optional[str]:
    """THE window-matching rule shared by tier 2 (:class:`PackCache`)
    and tier 2.5 (``ops/device_cache.DevicePackCache``), for EVERY
    family (ascending-seq message lists or record streams): "exact" when every
    doc's op window is unchanged vs the cached per-doc
    ``(n_ops, first_seq, last_seq)``, "suffix" when every window
    extends its cached one (same first seq, the old tail still in
    place, any new rows strictly past it — same-seq rows only ever
    arrive inside one sequenced message, which the cached window
    already held in full), else None.  One derivation point: the two
    tiers deciding differently would let resident device buffers
    disagree with the packed host arrays they mirror."""
    exact = True
    for d, doc in enumerate(chunk):
        n, first, _last = doc_window(doc)
        cached_n = n_ops[d]
        if n < cached_n:
            return None
        if cached_n:
            if first != first_seq[d] \
                    or _seq_at(doc, cached_n - 1) != last_seq[d]:
                return None
            if n > cached_n and _seq_at(doc, cached_n) <= last_seq[d]:
                return None
        if n != cached_n:
            exact = False
    return "exact" if exact else "suffix"


class PackCache:
    """Suffix-aware cache of packed chunk outputs — tier 2 of the
    catch-up cache, attacking the pack leg of the host floor
    (BENCH_cpu_fullscale_r05c: pack is the largest busy stage).
    Family-generic since round 14: the default instance serves
    ``pack_mergetree_batch`` windows; construct with the tree family
    (``ops/tree_pipeline.tree_pack_cache``) to cache SharedTree packs —
    window matching, lineage stamping, LRU, and locking are THIS class,
    while packing/extension go through the family hooks.

    Chunks are keyed by the ordered tuple of per-doc ``cache_token``s
    (doc + base summary + storage generation identity, supplied by the
    catch-up service); a chunk with any doc without a token bypasses the
    cache.

    Three outcomes per chunk:

    - **exact**: every doc's op window is unchanged → the cached arrays
      are returned as-is (zero pack work; only the meta's ``docs`` are
      re-pointed so extraction reads fresh ``final_seq``/``final_msn``).
    - **suffix**: every doc's window extends the cached one (same first
      seq, tail grew — the append-only op log guarantees the shared
      prefix is byte-identical under an equal token) → the family's
      ``extend`` packs ONLY the new suffix rows onto copies of the
      cached arrays, provided the chunk's shape buckets hold; any
      violation just falls back to a full repack — never corrupts.
    - **miss**: a full family pack whose result is cached.

    Extraction-side summaries are byte-identical in all three cases
    (pinned by tests): intern ids may differ from a fresh pack's, but
    ids never reach the summary bytes — everything resolves through the
    chunk's own tables.

    Thread-safe: lookups/stores lock, and suffix extensions serialize on
    their own mutex (they append to an entry's shared arena/interner);
    full packs and exact hits run lock-free.
    """

    def __init__(self, max_bytes: int = 192 << 20,
                 family: Optional[KernelFamily] = None) -> None:
        from ..utils.telemetry import CounterSet

        self.family = family if family is not None else MERGETREE_FAMILY
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        # Serializes suffix extension: extend appends to the cached
        # entry's SHARED arena and value interner (append-only, so
        # readers are safe, but two concurrent extends of the same entry
        # would interleave writes).  Extends are the rare path — one
        # mutex for all of them costs nothing and makes the thread-safety
        # claim unconditional instead of relying on callers never
        # sharing a token tuple across concurrent pack() calls.
        self._extend_lock = threading.Lock()
        # tokens -> _PackEntry (insertion order = LRU order)
        self._entries: dict = {}  # guarded-by: _lock
        self._bytes = 0  # guarded-by: _lock
        self.counters = CounterSet(
            "exact_hits", "suffix_hits", "misses", "bypass", "inserts",
            "evictions",
        )  # guarded-by: _lock (CounterSet is not internally synchronized)

    def stats(self) -> dict:
        with self._lock:
            out = self.counters.snapshot()
            out["entries"] = len(self._entries)
            out["bytes"] = self._bytes
        return out

    # -- public entry point ----------------------------------------------------

    def pack(self, chunk):
        """(state, ops, meta) for ``chunk`` — cached, suffix-extended, or
        freshly packed."""
        family = self.family
        tokens = tuple(d.cache_token for d in chunk)
        if any(t is None for t in tokens):
            with self._lock:
                self.counters.bump("bypass")
            return family.pack(chunk)
        with self._lock:
            entry = self._entries.get(tokens)
        if entry is not None:
            kind = self._match(entry, chunk)
            if kind == "exact":
                with self._lock:
                    self._touch(tokens)
                    self.counters.bump("exact_hits")
                return entry.state, entry.ops, dict(
                    entry.meta, docs=list(chunk),
                    _pack_lineage=("exact", entry.gen))
            if kind == "suffix" and family.extend is not None:
                parent_gen = entry.gen
                with self._extend_lock:
                    extended = family.extend(entry, chunk)
                if extended is not None:
                    state, ops, meta = extended
                    gen = self._store(tokens, chunk, state, ops, meta)
                    # The lineage stamp: these arrays are the literal
                    # extension of generation ``parent_gen`` — the
                    # device-resident tier's suffix-splice license.
                    meta["_pack_lineage"] = ("suffix", parent_gen, gen)
                    with self._lock:
                        self.counters.bump("suffix_hits")
                    return state, ops, meta
        with self._lock:
            self.counters.bump("misses")
        state, ops, meta = family.pack(chunk)
        gen = self._store(tokens, chunk, state, ops, meta)
        meta["_pack_lineage"] = ("full", gen)
        return state, ops, meta

    # -- bookkeeping -----------------------------------------------------------

    def _touch(self, tokens) -> None:  # holds-lock: _lock
        entry = self._entries.pop(tokens, None)
        if entry is not None:
            self._entries[tokens] = entry

    def _store(self, tokens, chunk, state, ops, meta) -> int:
        """Insert/replace the entry; returns its pack generation (fresh
        even when the byte budget refuses the entry — the lineage stamp
        must still be unique per produced array set)."""
        n_ops, first_seq, last_seq = [], [], []
        for doc in chunk:
            n, first, last = doc_window(doc)
            n_ops.append(n)
            first_seq.append(first)
            last_seq.append(last)
        t_rows = list(self.family.entry_rows(chunk, meta))
        # The stored meta never serves extraction directly — both the
        # exact-hit and suffix paths re-point ``docs`` at the fresh chunk
        # — so drop the doc inputs (and with them the per-op Python
        # message lists, the dominant retained memory the byte budget
        # would otherwise silently under-count).
        gen = next(_PACK_GEN)
        entry = _PackEntry(tokens, n_ops, first_seq, last_seq, t_rows,
                           state, ops, dict(meta, docs=None),
                           self.family.entry_nbytes(state, ops, meta),
                           gen=gen)
        with self._lock:
            old = self._entries.pop(tokens, None)
            if old is not None:
                self._bytes -= old.nbytes
            if entry.nbytes > self.max_bytes:
                self.counters.bump("evictions")
                return gen
            self._entries[tokens] = entry
            self._bytes += entry.nbytes
            self.counters.bump("inserts")
            while self._bytes > self.max_bytes and self._entries:
                oldest = next(iter(self._entries))
                dropped = self._entries.pop(oldest)
                self._bytes -= dropped.nbytes
                self.counters.bump("evictions")
        return gen

    @staticmethod
    def _match(entry: _PackEntry, chunk) -> Optional[str]:
        return match_windows(entry.n_ops, entry.first_seq,
                             entry.last_seq, chunk)


# ---------------------------------------------------------------------------
# Merge-tree tier-2 suffix extension (the family's ``extend`` hook)
# ---------------------------------------------------------------------------


def _extend_mergetree(entry: _PackEntry, chunk):
    """Pack only each doc's suffix ops on top of the cached arrays;
    None = shape/bucket constraints do not hold (caller full-packs)."""
    meta = entry.meta
    T = entry.ops.kind.shape[1]
    S = int(meta["_S"])
    K = int(meta["props_K"])
    key_ids = {k: i for i, k in enumerate(meta["prop_keys"])}
    # Pre-scan (no shared state touched): per-doc text-op counts and
    # the suffix's new property keys, so every bucket check happens
    # before any mutation.
    new_t_counts, suffixes = [], []
    new_keys = []
    for d, doc in enumerate(chunk):
        suffix = _suffix_ops(doc, entry.n_ops[d])
        suffixes.append(suffix)
        t_count = entry.t_rows[d]
        for msg in suffix:
            contents = msg.contents
            if contents["kind"].startswith("interval"):
                continue
            t_count += 1
            for key in (contents.get("props") or {}):
                if key not in key_ids and key not in new_keys:
                    new_keys.append(key)
        new_t_counts.append(t_count)
    if len(key_ids) + len(new_keys) > K:
        return None  # props bucket would grow: repack
    if next_bucket_fine(max(max(new_t_counts), 1), floor=16) != T:
        return None  # op-row bucket would grow
    base_counts = [int(n) for n in np.asarray(entry.state.n)]
    s_need = max(bc + 2 * tc
                 for bc, tc in zip(base_counts, new_t_counts))
    if next_bucket_fine(max(s_need, 1), floor=32) != S:
        return None  # slot bucket would grow
    for key in new_keys:
        key_ids[key] = len(key_ids)

    # Commit: copy the op arrays (the cached entry must stay intact),
    # share the append-only arena/value interner and the untouched
    # base state, and fill only the suffix rows.
    op = {f: np.copy(getattr(entry.ops, f)) for f in MTOps._fields}
    arena = meta["arena"]
    values: Interner = meta["values"]
    doc_packs = [_copy_doc_pack(p) for p in meta["doc_packs"]]
    try:
        _fill_mergetree_suffixes(chunk, suffixes, entry, op, arena,
                                 values, doc_packs, key_ids)
    except ValueError:
        # An op shape this fill doesn't know (drift vs
        # pack_mergetree_batch's row fill) must degrade to a full
        # pack — which raises the same error if the op is genuinely
        # malformed — never crash only-when-warm.  The arena/interner
        # appends already made are unreferenced and harmless.
        return None
    new_meta = dict(
        meta,
        docs=list(chunk),
        doc_packs=doc_packs,
        prop_keys=sorted(key_ids, key=key_ids.__getitem__),
    )
    state = _refresh_mergetree_facts(entry.state, op, new_meta, chunk)
    return state, MTOps(**op), new_meta


def _suffix_ops(doc, n: int):
    """A document's ops past its first ``n``, as messages: a stream
    document decodes only its records past the cached window, so the one
    row fill below serves both kinds."""
    if doc.binary_ops is None:
        return doc.ops[n:]
    blob = doc.binary_ops
    return decode_string_ops(blob[stream_offset(blob, n):],
                             list(doc.binary_clients or []),
                             prop_keys=doc.binary_prop_keys,
                             values=doc.binary_values)


def _fill_mergetree_suffixes(chunk, suffixes, entry, op, arena, values,
                             doc_packs, key_ids) -> None:
    # THE shared row fill (mergetree_kernel.fill_sequence_op_rows) —
    # byte-drift between fresh and suffix-cached packs is impossible
    # by construction.
    for d, doc in enumerate(chunk):
        pack = doc_packs[d]
        if known_oracle_fallback(doc):
            pack.needs_fallback = True
        fill_sequence_op_rows(op, d, entry.t_rows[d] - 1, suffixes[d],
                              pack, arena, key_ids.__getitem__, values)


def _refresh_mergetree_facts(state, op, meta, chunk):
    """Re-derive the chunk facts over the COMBINED arrays — same
    predicates as ``pack_mergetree_batch``, except the i16 text bound
    checks the actual per-doc rebased span ends (suffix text is not
    contiguous with the doc's original arena span).  Returns the base
    state, given as many overlap planes as the combined chunk's
    ``ov_slot_count`` (a new removing client can raise it, a suffix never
    lowers it; the cached state itself is never written)."""
    doc_base = np.asarray(meta["doc_base"], np.int32)
    S = int(meta["_S"])
    is_ins = op["kind"] == K_INSERT
    op_end = np.where(
        is_ins, op["tstart"] + op["tlen"] - doc_base[:, None], 0
    )
    live = np.arange(state.tstart.shape[1],
                     dtype=np.int32)[None, :] < np.asarray(
                         state.n)[:, None]
    st_end = np.where(
        live,
        np.asarray(state.tstart) + np.asarray(state.tlen)
        - doc_base[:, None],
        0,
    )
    max_off = max(int(op_end.max(initial=0)),
                  int(st_end.max(initial=0)))
    max_seq = max(
        int(op["seq"].max(initial=0)),
        max((d.final_seq for d in chunk), default=0),
        max((d.base_seq for d in chunk), default=0),
    )
    max_clients = max(
        (len(p.clients) for p in meta["doc_packs"]), default=0
    )
    n_values = len(meta["values"])
    meta["i16_ok"] = (
        max_seq < I16_LIMIT and max_off < I16_LIMIT and S < I16_LIMIT
        and n_values < I16_LIMIT and max_clients < I16_LIMIT
    )
    real_ops = op["kind"] != K_NOOP
    max_tlen = max(int(op["tlen"].max(initial=0)),
                   int(np.asarray(state.tlen).max(initial=0)))
    meta["i8_ok"] = (
        meta["i16_ok"] and max_seq < I8_LIMIT and max_tlen < I8_LIMIT
        and n_values < I8_LIMIT and max_clients < I8_LIMIT
    )
    sequential = not bool(
        (real_ops & (op["ref_seq"] != op["seq"] - 1)).any()
    )
    meta["sequential"] = sequential
    meta["ob_rows"] = bool(
        (np.asarray(state.ob1_seq) != NOT_REMOVED).any()
        or (op["kind"] == K_OBLITERATE).any()
    )
    # The base removers per record, read back off the base planes: the
    # winner plus every occupied overlap slot.
    overlap = (np.asarray(state.rem2_client) >= 0).astype(np.int64) + sum(
        np.asarray(p) != NOT_REMOVED for p in state.remx_seq)
    base = (np.asarray(state.rem_seq) != NOT_REMOVED) + overlap
    ov_slots = ov_slot_count(
        tail_remover_counts(op["kind"], op["client"]),
        base.max(axis=1, initial=0), int(overlap.max(initial=0)),
        sequential)
    meta["ov_slots"] = ov_slots
    meta["has_props"] = len(meta["prop_keys"]) > 0
    extra = ov_slots - 1 - len(state.remx_seq)
    if extra <= 0:
        return state
    D, S = np.shape(state.rem2_seq)
    return state._replace(
        remx_seq=tuple(state.remx_seq) + tuple(
            np.full((D, S), NOT_REMOVED, np.int32) for _ in range(extra)),
        remx_client=tuple(state.remx_client) + tuple(
            np.full((D, S), -1, np.int32) for _ in range(extra)))


# -- tier-0 delta-download routing: ONE derivation point --------------------
# The single-device pipeline below and the mesh fold
# (parallel/shard.py replay_family_sharded) both consume these — the
# byte-identity-critical cache logic (serve gate, entry publication, the
# changed-rows sub-meta) must never fork into hand-synced copies, for
# ANY family.


def delta_route(docs, dig_np, delta_cache):
    """The per-chunk tier-0 decision after the digest plane arrived:
    ``("full", {}, None)`` — nothing servable, the cold/fallback/oracle
    route; ``("served", served, None)`` — every document serves without
    a download; ``("partial", served, changed)`` — only ``changed``
    positions' rows must cross."""
    served = (delta_cache.serve_many(docs, dig_np)
              if delta_cache.any_candidate(docs) else {})
    if not served:
        return "full", served, None
    if len(served) == len(docs):
        return "served", served, None
    return "partial", served, [d for d in range(len(docs))
                               if d not in served]


def delta_store_all(delta_cache, docs, dig_np, trees) -> None:
    """(Re)publish every document's tier-0 entry — the cold-fill leg."""
    delta_cache.put_many(
        (doc, (int(dig_np[d, 0]), int(dig_np[d, 1])), trees[d])
        for d, doc in enumerate(docs))


def delta_sub_meta(meta, changed,
                   per_doc: Sequence[str] = ("doc_base",)) -> dict:
    """The per-doc meta rows of only the CHANGED positions (the gathered
    rows' extraction view); chunk-global meta passes through.
    ``per_doc`` names the family's per-doc ndarray meta entries that
    must slice alongside ``docs``/``doc_packs``."""
    docs = meta["docs"]
    rows = np.asarray(changed, np.intp)
    out = dict(
        meta,
        docs=[docs[d] for d in changed],
        doc_packs=[meta["doc_packs"][d] for d in changed],
    )
    for key in per_doc:
        if key in meta:
            out[key] = np.asarray(meta[key])[rows]
    return out


def delta_merge_changed(delta_cache, meta, dig_np, served, changed, got):
    """Served trees + freshly extracted changed trees → the chunk's
    result list, publishing the changed documents' new tier-0 entries."""
    docs = meta["docs"]
    res: List = [None] * len(docs)
    for d, tree in served.items():
        res[d] = tree
    for d, tree in zip(changed, got):
        res[d] = tree
    delta_cache.put_many(
        (docs[d], (int(dig_np[d, 0]), int(dig_np[d, 1])), tree)
        for d, tree in zip(changed, got))
    return res


# ---------------------------------------------------------------------------
# The family-generic pipelined fold
# ---------------------------------------------------------------------------


def pipelined_family_replay(
    family: KernelFamily,
    docs,
    *,
    chunk_docs: int = 1024,
    pack_threads: int = 4,
    extract_threads: int = 3,
    fetch_depth: int = 2,
    schedule: bool = True,
    stats: Optional[dict] = None,
    stage: Optional[dict] = None,
    packed_out: Optional[list] = None,
    pack_cache: Optional[PackCache] = None,
    delta_cache=None,
    device_cache=None,
    pin_resident: bool = False,
):
    """Canonical summaries for ``docs`` in the given order, through the
    generic four-tier pipeline for any registered kernel family.

    ``stats`` accumulates ``device_docs``/``fallback_docs`` (plus the
    per-reason ``fallback_<reason>`` split and ``delta_docs`` for
    documents served from the tier-0 delta cache without a download);
    ``stage`` (if given) accumulates busy seconds under
    ``pack``/``dispatch``/``upload``/``device_wait``/``download``/
    ``extract``/``fallback`` (the ``pipeline.<key>`` spans, one per
    chunk or call) and the integer byte counters
    ``h2d_bytes``/``d2h_bytes``; ``packed_out`` (if
    given) collects ``(state, ops, meta, tag)`` per chunk in schedule
    order so a caller can reuse the pack work; ``pack_cache`` (if given,
    built over THIS family) reuses packed windows across calls for docs
    carrying a ``cache_token`` (see :class:`PackCache`);
    ``delta_cache`` (a ``service.catchup_cache.DeltaExportCache``, tier 0
    of the catch-up cache) turns on digest-gated delta download: the fold
    emits a per-doc state digest, only the tiny digest plane round-trips
    eagerly, and only CHANGED documents' export rows are gathered and
    downloaded — unchanged documents serve their cached summaries
    byte-identically.  Any miss/mismatch falls back to the full fetch.
    ``device_cache`` (an ``ops.device_cache.DevicePackCache`` built over
    this family's device ops, tier 2.5) keeps packed chunk arrays
    device-resident across calls: an exact tier-2 window hit dispatches
    with ZERO h2d pack bytes, a suffix hit uploads only the new rows
    through a donated in-place splice, and any mismatch falls back to
    the full upload — which without the tier is also the only route (and
    is what ``h2d_bytes`` then counts).  ``pin_resident=True`` (the
    streaming fold) pins every chunk this call serves into the device
    cache's resident-state tier — exempt from LRU, spill-to-host over
    its own byte budget (see ``DevicePackCache.pin``)."""

    # Seed HERE, not in the fold: a batch that routes entirely to
    # fallback never reaches _pipelined_fold, and the schema contract
    # (same keys single-device and mesh, every configuration) must hold
    # for it too.
    seed_stage(stage)

    def fold(batch):
        return _pipelined_fold(
            family, batch, chunk_docs, pack_threads, extract_threads,
            fetch_depth, schedule, stats, stage, packed_out, pack_cache,
            delta_cache, device_cache, pin_resident,
        )

    return partition_replay(
        docs, family.known_fallback, family.fallback_summary, fold,
        stats=stats, stage=stage,
    )


def pipelined_mergetree_replay(
    docs: Sequence[MergeTreeDocInput],
    *,
    chunk_docs: int = 1024,
    pack_threads: int = 4,
    extract_threads: int = 3,
    fetch_depth: int = 2,
    schedule: bool = True,
    stats: Optional[dict] = None,
    stage: Optional[dict] = None,
    packed_out: Optional[list] = None,
    pack_cache: Optional[PackCache] = None,
    delta_cache=None,
    device_cache=None,
    pin_resident: bool = False,
):
    """The merge-tree instance of :func:`pipelined_family_replay` — the
    original round-5 entry point, signature unchanged."""
    return pipelined_family_replay(
        MERGETREE_FAMILY, docs,
        chunk_docs=chunk_docs, pack_threads=pack_threads,
        extract_threads=extract_threads, fetch_depth=fetch_depth,
        schedule=schedule, stats=stats, stage=stage,
        packed_out=packed_out, pack_cache=pack_cache,
        delta_cache=delta_cache, device_cache=device_cache,
        pin_resident=pin_resident,
    )


def _count_d2h(stage: Optional[dict], nbytes: int) -> None:
    """Accumulate ACTUAL bytes fetched over the d2h link this call (an
    integer counter riding the stage dict next to the busy seconds)."""
    if stage is not None:
        stage["d2h_bytes"] = stage.get("d2h_bytes", 0) + int(nbytes)


def _count_h2d(stage: Optional[dict], nbytes: int) -> None:
    """The upload-side twin of :func:`_count_d2h`: bytes of pack data
    this call pushed over the h2d link — the observable the
    device-resident tier (ISSUE 13) exists to shrink."""
    if stage is not None:
        stage["h2d_bytes"] = stage.get("h2d_bytes", 0) + int(nbytes)


def _nbytes(handle) -> int:
    """Byte size of a device/host buffer handle (or tuple of them) from
    shape metadata alone — never forces a transfer."""
    leaves = handle if isinstance(handle, tuple) else (handle,)
    return int(sum(leaf.nbytes for leaf in leaves))


def _np_nbytes(tree) -> int:
    """Bytes of the NUMPY leaves of a state/ops tree — exactly what the
    dispatch jit will push over the h2d link (device-resident leaves
    pass through and cost nothing)."""
    if tree is None:
        return 0
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(tree)
                   if isinstance(leaf, np.ndarray)))


def _block_until_ready(*handles) -> None:
    """Wait for device computation to finish WITHOUT transferring — the
    honest boundary between fold wait and the d2h copy (numpy leaves on
    the CPU backend pass through)."""
    for handle in handles:
        if handle is None:
            continue
        leaves = handle if isinstance(handle, tuple) else (handle,)
        for leaf in leaves:
            wait = getattr(leaf, "block_until_ready", None)
            if wait is not None:
                wait()


#: THE stage schema, identical for every family, single-device and mesh
#: (the byte counters ride as ints next to the busy seconds).  Each key
#: is the aggregate of the ``pipeline.<key>`` spans; ``fallback`` (the
#: oracle folds of routed-out documents) is also counted inside
#: ``extract`` where the extractor takes them.
STAGE_KEYS = ("pack", "upload", "dispatch", "device_wait", "download",
              "extract", "fallback")


def seed_stage(stage: Optional[dict]) -> None:
    """Pre-seed the full stage schema so every fold — with or without
    cache tiers, single-device or mesh — reports the SAME keys (a leg
    that never ran reads 0, instead of being absent)."""
    if stage is None:
        return
    for key in STAGE_KEYS:
        stage.setdefault(key, 0.0)
    stage.setdefault("h2d_bytes", 0)
    stage.setdefault("d2h_bytes", 0)


def _pipelined_fold(family, batch, chunk_docs, pack_threads,
                    extract_threads, fetch_depth, schedule, stats, stage,
                    packed_out, pack_cache=None, delta_cache=None,
                    device_cache=None, pin_resident=False):
    order = family.order(batch, schedule)
    sched = [batch[i] for i in order]
    starts = list(range(0, len(sched), chunk_docs))

    def pack_one(lo):
        with span("pipeline.pack", stage, "pack", chunk=lo // chunk_docs):
            chunk = sched[lo:lo + chunk_docs]
            if pack_cache is not None:
                state, ops, meta = pack_cache.pack(chunk)
            else:
                state, ops, meta = family.pack(chunk)
            state, ops = family.narrow(chunk, state, ops, meta)
        return state, ops, meta, lo // chunk_docs

    def extract_one(i, meta, arr, dig_np=None):
        """Full-download extraction; with ``dig_np`` it also
        (re)publishes every doc's tier-0 entry — the cold-fill leg of the
        delta path."""
        with span("pipeline.extract", stage, "extract", chunk=i):
            st: dict = {}
            res = family.extract(meta, arr, st, stage)
            if dig_np is not None:
                delta_store_all(delta_cache, meta["docs"], dig_np, res)
        return res, st

    def extract_served(docs, served):
        """Whole chunk served from tier 0: zero download, zero extract."""
        return [served[d] for d in range(len(docs))], \
            {"delta_docs": len(docs)}

    def extract_delta(i, meta, arr, changed, served, dig_np):
        """Extract ONLY the changed documents from their gathered rows;
        unchanged documents serve their cached summaries byte-identically
        (the cached tree came out of this same extraction under an equal
        digest + host anchor)."""
        with span("pipeline.extract", stage, "extract", chunk=i):
            st: dict = {}
            got = family.extract(
                delta_sub_meta(meta, changed, family.per_doc_meta), arr, st,
                stage)
            res = delta_merge_changed(delta_cache, meta, dig_np, served,
                                      changed, got)
        st["delta_docs"] = st.get("delta_docs", 0) + len(served)
        return res, st

    out: List = []

    def collect(fut) -> None:
        res, st = fut.result()
        out.extend(res)
        if stats is not None:
            for k, v in st.items():
                stats[k] = stats.get(k, 0) + v

    pack_futs: collections.deque = collections.deque()
    ex_futs: collections.deque = collections.deque()
    inflight: collections.deque = collections.deque()
    with ThreadPoolExecutor(max_workers=pack_threads) as pack_pool, \
            ThreadPoolExecutor(max_workers=extract_threads) as ex_pool:
        try:
            next_i = 0
            while next_i < len(starts) and len(pack_futs) < pack_threads + 1:
                pack_futs.append(pack_pool.submit(pack_one, starts[next_i]))
                next_i += 1

            def fetch_one(i, meta, core, dig, cand) -> None:
                # Honest stage split: wait for the DEVICE to finish first
                # (fold + export compute), so "download" times the copy
                # alone and d2h_bytes attributes what actually crossed.
                with span("pipeline.device_wait", stage, "device_wait",
                          chunk=i):
                    _block_until_ready(core, dig)
                docs = meta["docs"]
                if dig is None:
                    with span("pipeline.download", stage, "download",
                              chunk=i):
                        arr = family.fetch(core)  # the d2h link RPC(s)
                    _count_d2h(stage, _nbytes(arr))
                    ex_futs.append(ex_pool.submit(extract_one, i, meta, arr))
                else:
                    with span("pipeline.download", stage, "download",
                              chunk=i):
                        dig_np = np.asarray(dig)  # the tiny eager round-trip
                    _count_d2h(stage, dig_np.nbytes)
                    # Host cache work stays OUTSIDE the download window
                    # (the stage times link traffic alone); one lock
                    # acquisition serves the whole chunk
                    # (delta_route, the shared tier-0 decision).
                    route, served, changed = (
                        delta_route(docs, dig_np, delta_cache)
                        if cand else ("full", {}, None))
                    if route == "full":
                        # Cold / all-changed / fallback route — and the
                        # golden oracle the delta path is tested against.
                        with span("pipeline.download", stage, "download",
                                  chunk=i):
                            arr = family.fetch(core)
                        _count_d2h(stage, _nbytes(arr))
                        ex_futs.append(ex_pool.submit(
                            extract_one, i, meta, arr, dig_np))
                    elif route == "served":
                        delta_cache.note_bytes_saved(_nbytes(core))
                        ex_futs.append(ex_pool.submit(
                            extract_served, docs, served))
                    else:
                        # Exact rows on host-viewable buffers; fine-
                        # bucketed device gather (or whole-buffer fetch
                        # when padding would move it all) elsewhere —
                        # the family's gather owns that choice and
                        # reports the bytes that really crossed.
                        with span("pipeline.download", stage, "download",
                                  chunk=i):
                            sub, fetched = family.gather_rows(
                                core, np.asarray(changed, np.int32))
                        _count_d2h(stage, fetched)
                        delta_cache.note_bytes_saved(
                            max(0, _nbytes(core) - fetched))
                        ex_futs.append(ex_pool.submit(
                            extract_delta, i, meta, sub, changed, served,
                            dig_np))
                if len(ex_futs) >= extract_threads + 1:
                    collect(ex_futs.popleft())

            want_digest = delta_cache is not None
            while pack_futs:
                fut = pack_futs.popleft()
                state, ops, meta, i = fut.result()
                if next_i < len(starts):
                    pack_futs.append(
                        pack_pool.submit(pack_one, starts[next_i]))
                    next_i += 1
                # --- upload leg (tier 2.5): resident buffers on a warm
                # window, donated suffix splice on a grown one, full
                # device_put otherwise.  All device interaction stays on
                # THIS thread (the pipeline's single-device-thread
                # contract); `upload` times the explicit transfers and
                # h2d_bytes counts what really crossed — without the
                # tier, the full host arrays upload inside the jit call
                # below, so they are counted here either way.
                base_dev = None
                host_state, host_ops = state, ops
                if device_cache is not None:
                    with span("pipeline.upload", stage, "upload", chunk=i):
                        state, ops, base_dev, up_bytes = \
                            device_cache.acquire(state, ops, meta,
                                                 pin=pin_resident)
                    _count_h2d(stage, up_bytes)
                else:
                    _count_h2d(stage,
                               _np_nbytes(state) + _np_nbytes(ops))
                with span("pipeline.dispatch", stage, "dispatch", chunk=i):
                    ex = family.dispatch(state, ops, meta, want_digest,
                                         base_dev)
                    core, dig = family.split_digest(ex, want_digest)
                    cand = want_digest and delta_cache.any_candidate(
                        meta["docs"])
                    if dig is not None:
                        _start_host_copy(dig)
                    if dig is None or not cand:
                        # No tier-0 candidate can skip the download:
                        # start the full async copy at dispatch like the
                        # plain path.  With candidates present, starting
                        # it would transfer the very bytes delta download
                        # exists to avoid.
                        _start_host_copy(core)
                if packed_out is not None:
                    # state included so a caller re-timing the fold can
                    # replay WARM chunks with the same executable the e2e
                    # used (None for cold chunks).  Always the HOST
                    # arrays: a resident-tier buffer may later be
                    # donated away by a suffix splice — a collected
                    # reference must never die under the caller.
                    packed_out.append((host_state, host_ops, meta,
                                       family.chunk_tag(meta)))
                inflight.append((i, meta, core, dig, cand))
                if len(inflight) > fetch_depth:
                    fetch_one(*inflight.popleft())
            while inflight:
                fetch_one(*inflight.popleft())
            while ex_futs:
                collect(ex_futs.popleft())
        finally:
            for f in pack_futs:
                f.cancel()
            for f in ex_futs:
                f.cancel()
    # Restore the caller's order.
    restored: List = [None] * len(batch)
    for pos, i in enumerate(order):
        restored[i] = out[pos]
    return restored


def _has_props(doc: MergeTreeDocInput) -> bool:
    for msg in doc.ops:
        op = msg.contents
        if not op["kind"].startswith("interval") and op.get("props"):
            return True
    return bool(any(r.get("p") for r in (doc.base_records or [])))


def _chunk_S(meta: dict) -> int:
    """The chunk's padded slot capacity (pack_mergetree_batch's S bucket),
    recovered from the packed meta for the cold-start export builder."""
    return int(meta["_S"])


def _start_host_copy(ex) -> None:
    leaves = ex if isinstance(ex, tuple) else (ex,)
    for leaf in leaves:
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            copy()


# ---------------------------------------------------------------------------
# The merge-tree family instance
# ---------------------------------------------------------------------------


def _mt_order(batch, schedule: bool):
    order = list(range(len(batch)))
    if schedule and any(d.binary_ops is not None for d in batch):
        # Fact-homogeneous scheduling: annotate-free docs first, so their
        # chunks compile with the props plane traced away (~20% fold win
        # on the pure-text majority).  Stable sort; order restored by the
        # caller.  Binary docs carry the fact in their header (O(1));
        # message-list docs would need an O(ops) serial pre-scan on this
        # thread, so a batch with no binary docs keeps its order (the
        # pack pre-scan derives the facts in the parallel pool
        # regardless).
        order.sort(key=lambda i: batch[i].binary_prop_keys is not None
                   if batch[i].binary_ops is not None
                   else _has_props(batch[i]))
    return order


def _mt_narrow(chunk, state, ops, meta):
    warm = any(d.base_records for d in chunk)
    return (narrow_state_for_upload(state, meta) if warm else None,
            narrow_ops_for_upload(ops, meta))


def _mt_aux(meta, digest: bool):
    """The per-doc arena base the dispatch consumes next to state/ops —
    real bases when the narrow layout or the digest reads them, zeros
    otherwise (inert, but the jitted signature always takes the arg)."""
    if bool(meta.get("i16_ok")) or digest:
        return np.asarray(meta["doc_base"], np.int32)
    return np.zeros((len(meta["docs"]),), np.int32)


def _mt_dispatch(state, ops, meta, digest: bool, aux_dev):
    return replay_export(state, ops, meta, S=int(meta["_S"]),
                         digest=digest, doc_base=aux_dev)


def _mt_dispatch_sharded(mesh, state, ops, meta, digest: bool, aux_dev):
    from ..parallel.shard import sharded_export_step

    i16, ob_rows, ov_slots, i8, has_props = _export_flags(meta)
    sequential = bool(meta.get("sequential"))
    warm = state is not None
    step = sharded_export_step(mesh, int(meta["_S"]), i16, ob_rows,
                               ov_slots, i8, sequential, has_props, warm,
                               digest=digest)
    return step(state, ops, aux_dev) if warm else step(ops, aux_dev)


def _mt_entry_rows(chunk, meta):
    return [
        stream_counts(doc)[0] if doc.binary_ops is not None else
        sum(1 for m in doc.ops
            if not m.contents["kind"].startswith("interval"))
        for doc in chunk
    ]


def _mt_entry_nbytes(state, ops, meta) -> int:
    return (
        sum(np.asarray(x).nbytes for x in ops)
        + sum(np.asarray(x).nbytes for x in state)
        + len(meta["arena"]) * 4
    )


def _mt_pad_token(k: int) -> tuple:
    """A deterministic cache token for mesh pad documents: the padded
    chunk's token tuple must stay all-non-None for tier-2/2.5 keying,
    and an empty pad doc's "stream" is trivially append-only under a
    fixed token.  Component 0 is a sentinel epoch, so the tier-0/2.5
    epoch sweeps treat pad entries as stale on any real epoch change."""
    return ("\x00pad", f"\x00pad{k}", 0, "")


MERGETREE_FAMILY = KernelFamily(
    name="mergetree",
    known_fallback=known_oracle_fallback,
    fallback_summary=oracle_fallback_summary,
    pack=pack_mergetree_batch,
    entry_rows=_mt_entry_rows,
    entry_nbytes=_mt_entry_nbytes,
    extend=_extend_mergetree,
    order=_mt_order,
    narrow=_mt_narrow,
    aux=_mt_aux,
    dispatch=_mt_dispatch,
    split_digest=split_export_digest,
    chunk_tag=_chunk_S,
    fetch=export_to_numpy,
    gather_rows=gather_export_rows,
    extract=lambda meta, arr, st, stage: summaries_from_export(
        meta, arr, stats=st, stage=stage),
    per_doc_meta=("doc_base",),
    make_pad=lambda: MergeTreeDocInput(doc_id="\x00pad", ops=[]),
    pad_token=_mt_pad_token,
    dispatch_sharded=_mt_dispatch_sharded,
)
