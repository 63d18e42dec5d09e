"""Kernel-family descriptor: the contract a vmap-able replay kernel
implements to ride the SHARED catch-up pipeline (ops/pipeline.py) and its
cache tiers instead of a bare ``replay_*_batch`` loop.

PAPER.md §0 names TWO kernels that trace and ``vmap`` — the merge-tree
op-apply loop and the SharedTree rebaser — but through round 13 every
cache tier, stage counter, and bench measured only the merge-tree
instance, and the pipeline was hard-wired to its types.  This descriptor
is the round-14 refactor: everything the pipeline does per chunk — pack,
tier-2 window reuse, upload (tier 2.5), dispatch, the tier-0 digest
handshake, download, extraction, fallback routing — goes through these
hooks, and ``pipelined_mergetree_replay`` becomes one instance of the
generic fold next to the SharedTree instance (ops/tree_pipeline.py).
The tier-1 result cache (service/catchup_cache.py) is already
family-agnostic (it keys folded summary trees, not kernel arrays).

A family's ``(state, ops)`` are namedtuples of ``[D, ...]`` planes with
the document axis leading — the invariant every generic helper
(``match_windows``, ``gather_export_rows``, the mesh doc-sharding)
relies on.  Hooks that a family does not support are None and the
corresponding tier degrades gracefully (e.g. ``extend=None`` turns every
grown-tail window into a full repack — a lost win, never corruption).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class KernelFamily:
    """One replay kernel's bindings into the family-generic pipeline.

    Grouped by pipeline leg (see ops/pipeline.py ``_pipelined_fold`` for
    the call sites; parallel/shard.py ``replay_family_sharded`` consumes
    the same hooks plus ``dispatch_sharded``/``make_pad``/``pad_token``):

    routing
      - ``known_fallback(doc)`` → falsy | True | reason str: pre-pack
        oracle routing (reasons feed the per-reason fallback counters);
      - ``fallback_summary(doc)`` → SummaryTree: the exactness escape
        hatch (also the post-fold fallback the extractor takes).

    pack / tier 2
      - ``pack(chunk)`` → ``(state, ops, meta)``;
      - ``entry_rows(chunk, meta)`` → per-doc used op-row counts (the
        suffix fill offsets the cache entry tracks);
      - ``entry_nbytes(state, ops, meta)`` → retained bytes for the LRU
        budget;
      - ``extend(entry, chunk)`` → ``(state, ops, meta)`` | None: pack
        only the suffix on top of a cached window (None = repack).

    upload / dispatch
      - ``order(batch, schedule)`` → schedule-order index list;
      - ``narrow(chunk, state, ops, meta)`` → ``(state_u | None,
        ops_u)``: the h2d transfer encodings;
      - ``aux(meta, digest)`` → host array tree the dispatch needs next
        to state/ops (merge-tree: the per-doc arena base; tree: used
        node/container counts for the digest mask);
      - ``dispatch(state_u, ops_u, meta, digest, aux_dev)`` → export
        handle(s); ``aux_dev`` is the device-resident aux from tier 2.5
        or None (derive from ``aux``);
      - ``split_digest(export, want)`` → ``(core, digest | None)``;
      - ``chunk_tag(meta)`` → value stored in ``packed_out`` tuples.

    download / extract / tier 0
      - ``fetch(core)`` → host arrays (the full d2h transfer);
      - ``gather_rows(core, idx)`` → ``(rows, moved_bytes)``: only the
        changed documents' rows;
      - ``extract(meta, arr, stats, stage)`` → summaries (counting
        post-fold fallbacks per reason into ``stats``, and the seconds of
        their oracle folds into ``stage["fallback"]``);
      - ``per_doc_meta``: names of per-doc ndarray meta entries the
        changed-rows sub-meta must slice alongside docs/doc_packs.

    mesh (parallel/shard.py)
      - ``make_pad()`` → an empty pad document;
      - ``pad_token(k)`` → deterministic cache token for pad docs;
      - ``dispatch_sharded(mesh, state_u, ops_u, meta, digest,
        aux_dev)`` → export placed doc-sharded over the mesh.
    """

    name: str
    # routing
    known_fallback: Callable[[Any], Any]
    fallback_summary: Callable[[Any], Any]
    # pack / tier 2
    pack: Callable[[Any], Tuple[Any, Any, dict]]
    entry_rows: Callable[[Any, dict], Any]
    entry_nbytes: Callable[[Any, Any, dict], int]
    extend: Optional[Callable[[Any, Any], Any]]
    # upload / dispatch
    order: Callable[[Any, bool], Any]
    narrow: Callable[[Any, Any, Any, dict], Tuple[Any, Any]]
    aux: Callable[[dict, bool], Any]
    dispatch: Callable[[Any, Any, dict, bool, Any], Any]
    split_digest: Callable[[Any, bool], Tuple[Any, Any]]
    chunk_tag: Callable[[dict], Any]
    # download / extract / tier 0
    fetch: Callable[[Any], Any]
    gather_rows: Callable[[Any, Any], Tuple[Any, int]]
    extract: Callable[[dict, Any, dict, Optional[dict]], Any]
    per_doc_meta: Tuple[str, ...] = ()
    # mesh
    make_pad: Optional[Callable[[], Any]] = None
    pad_token: Optional[Callable[[int], tuple]] = None
    dispatch_sharded: Optional[Callable[..., Any]] = None
