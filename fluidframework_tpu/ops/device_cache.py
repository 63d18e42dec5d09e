"""Device-resident pack buffers — tier 2.5 of the catch-up cache.

Tier 2 (:class:`~fluidframework_tpu.ops.pipeline.PackCache`) killed the
host *pack* work on warm catch-ups, and tier 0 made downloads delta-only
— but the **upload** leg stayed untouched: even on an exact tier-2 hit,
the pipeline re-uploads the full packed planes to the device on every
fold call.  On round 5's recorded link
(``BENCH_tpu_measured_r05.json``: h2d 15 MB/s) that re-upload IS the
warm hot path.  This module keeps the packed chunk arrays resident in
device memory across fold calls, keyed by the chunk's ordered
``cache_token`` tuple — the same identity tier 2 already proves sound:

- **exact** hit (every doc's op window unchanged): the dispatch leg
  consumes the resident buffers directly — ZERO h2d bytes for ops,
  state and the per-doc aux planes;
- **suffix** hit (windows grew under the same pack-cache lineage): only
  the new suffix rows cross the link as fine-bucketed ``[D, L]`` row
  planes, and a jitted splice with ``donate_argnums`` writes them into
  the resident buffers IN PLACE — no 2× HBM spike, and the jit cache
  stays bounded because ``L`` rides the fine bucket ladder;
- anything else — bucket overflow (shape signature moved), a
  narrow↔wide transfer-encoding flip (dtype signature moved), unknown
  pack lineage, window mismatch — falls back to the full upload and
  re-stores.  The resident tier can lose a win, never corrupt.

The class is FAMILY-GENERIC since round 14: window matching, the LRU,
epoch invalidation, and the store/serve handshake are shared, while the
family-shaped pieces — the transfer-encoding signature, the donated
splice (merge-tree splices one op-row axis; the tree family splices edit
rows AND the node/container state rows its suffix inserts materialized
— see ops/tree_pipeline.py), and encoding migration — live on a small
*device-ops* object (:class:`MergeTreeDeviceOps` is the default).

Soundness of the suffix splice is *structural*, belt and braces:

- the token contract (append-only op stream over a pinned base within
  one storage generation) pins the shared prefix bytes;
- the **pack lineage** (``meta["_pack_lineage"]``, stamped by tier 2)
  additionally proves the host arrays in hand are the literal
  suffix-extension of the arrays the resident buffers were built from —
  a fresh repack (whose arena layout may legitimately differ) can never
  masquerade as an extension;
- the **encoding signature** (per-field dtype + shape of the narrowed
  upload arrays) pins the transfer encoding: an ``i16``→wide flip or a
  bucket change is a signature mismatch, not a corrupted splice.

Donation discipline: after the splice the PREVIOUS resident buffers are
dead (XLA reused their memory) — the entry swaps in the splice outputs
and the old references are never read again (the FL-TRACE-DONATE lint
rule pins this discipline package-wide).  All device interaction
(``device_put``, the splice dispatch) must happen on the caller's single
device thread — the same contract the pipeline already holds for
dispatch/fetch; the lock here guards only the entry map and counters.

Byte-bounded LRU over insertion order (no wall-clock — replay-safe),
epoch invalidation riding the existing fence/epoch sweeps (tokens carry
the storage epoch as component 0).  Counters: ``served`` (exact hits —
zero-upload dispatches), ``spliced`` (suffix splices), ``misses``,
``bypass``, ``inserts``, ``evictions``, ``invalidations``, and
``bytes_saved`` (h2d bytes the resident tier kept off the link).
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.telemetry import CounterSet
from .interning import next_bucket_fine
from .mergetree_kernel import MTOps, MTState, _widen_ops, _widen_state
# The shared tier-2/2.5 contracts live in the pipeline module (no
# cycle: pipeline never imports this module): _np_nbytes is THE "what
# the dispatch jit pushes over h2d" byte rule the reductions compare
# against, doc_window/match_windows THE window-identity rules.
from .pipeline import _np_nbytes, doc_window, match_windows


def _dev_nbytes(*trees) -> int:
    total = 0
    for tree in trees:
        if tree is None:
            continue
        total += int(sum(leaf.nbytes for leaf in jax.tree.leaves(tree)))
    return total


def tuple_sig(state, ops) -> tuple:
    """The transfer-encoding signature over namedtuple plane trees:
    per-field dtype + shape of the (already narrowed) upload arrays.
    Any bucket growth, narrow↔wide encoding flip, or cold↔warm change
    moves it — and a moved signature means the resident buffers cannot
    be extended, only replaced (unless the family's ``migrate`` can
    convert them in-graph)."""
    sig = tuple((f, str(getattr(ops, f).dtype), getattr(ops, f).shape)
                for f in type(ops)._fields)
    if state is not None:
        for f in type(state)._fields:
            v = getattr(state, f)
            # A tuple field (the merge-tree overlap slots past the first)
            # gives an entry per plane: a state with more slots never
            # matches resident buffers with fewer.
            planes = enumerate(v) if isinstance(v, tuple) else [(None, v)]
            sig += tuple((f if i is None else f"{f}[{i}]", str(x.dtype),
                          x.shape) for i, x in planes)
    return sig


def splice_row_planes(tuple_type, resident, rows, start, count):
    """Donated in-place row splice over a namedtuple of ``[D, L, ...]``
    planes: ``out[d, start[d] + j] = rows[d, j]`` for ``j < count[d]``
    — THE shared splice primitive (merge-tree op rows, tree edit rows,
    tree node/container state rows all ride it).  ``resident`` is
    DONATED; expressed as a clipped take-along-axis + masked select (no
    scatter), elementwise along the doc axis, so the same executable
    serves the sharded mesh placement with zero collectives."""
    return _splice_jit(tuple_type)(resident, rows, start, count)


def _splice_ops(ops: MTOps, rows: MTOps, start, count) -> MTOps:
    """The merge-tree instance of :func:`splice_row_planes` (the name
    the splice-parity tests pin).  ``ops`` is DONATED."""
    return splice_row_planes(MTOps, ops, rows, start, count)


@functools.lru_cache(maxsize=16)
def _splice_jit(tuple_type):
    def _splice(resident, rows, start, count):
        lead = getattr(resident, tuple_type._fields[0])
        T = lead.shape[1]
        t_idx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)  # [1, T]
        rel = t_idx - start[:, None]                            # [D, T]
        L = getattr(rows, tuple_type._fields[0]).shape[1]
        take = jnp.clip(rel, 0, L - 1)
        mask = (rel >= 0) & (rel < count[:, None])

        def one(field, r):
            if field.ndim == 2:
                return jnp.where(
                    mask, jnp.take_along_axis(r, take, axis=1), field)
            return jnp.where(
                mask[:, :, None],
                jnp.take_along_axis(r, take[:, :, None], axis=1),
                field)

        return tuple_type(*(one(getattr(resident, f), getattr(rows, f))
                            for f in tuple_type._fields))

    # A stable program name per spliced plane group on the device trace
    # (splice_mtops, splice_treeedits, splice_treenodeplanes, ...).
    _splice.__name__ = _splice.__qualname__ = \
        f"splice_{tuple_type.__name__.strip('_').lower()}"
    return jax.jit(_splice, donate_argnums=(0,))


def gather_suffix_rows(tuple_type, host_tree, t_old: np.ndarray,
                       t_new: np.ndarray, floor: int = 8):
    """Host-side suffix-row gather for the splice upload: each doc's
    rows ``[t_old[d], t_new[d])`` taken from the combined host planes
    into fine-bucketed ``[D, L, ...]`` arrays (pad rows clone the last
    valid index — masked out by the splice).  Returns ``(rows_np, L)``
    or ``(None, L)`` when ``L`` reaches the full plane width (the full
    upload is then cheaper than a splice)."""
    lead = np.asarray(getattr(host_tree, tuple_type._fields[0]))
    T = lead.shape[1]
    grow = int((t_new - t_old).max(initial=0))
    L = min(next_bucket_fine(max(grow, 1), floor=floor), T)
    if L >= T:
        return None, L
    idx = np.minimum(
        t_old[:, None] + np.arange(L, dtype=np.int32)[None, :], T - 1)
    rows_np = {}
    for f in tuple_type._fields:
        v = np.asarray(getattr(host_tree, f))
        take = idx if v.ndim == 2 else idx[:, :, None]
        rows_np[f] = np.take_along_axis(v, take, axis=1)
    return rows_np, L


class _ResidentEntry:
    """One chunk's device-resident upload state + the host bookkeeping
    needed to match and extend it."""

    __slots__ = ("tokens", "n_ops", "first_seq", "last_seq", "t_rows",
                 "sig", "gen", "state", "ops", "base", "aux", "nbytes",
                 "pinned", "spilled")

    def __init__(self, tokens, n_ops, first_seq, last_seq, t_rows, sig,
                 gen, state, ops, base, aux=None):
        self.tokens = tokens
        self.n_ops = n_ops
        self.first_seq = first_seq
        self.last_seq = last_seq
        self.t_rows = t_rows            # np [D]: used op rows per doc
        self.sig = sig
        self.gen = gen                  # tier-2 pack generation (or None)
        self.state = state              # device state tree or None (cold)
        self.ops = ops                  # device ops tree
        self.base = base                # device per-doc aux tree
        self.aux = aux                  # family host bookkeeping (counts)
        self.nbytes = _dev_nbytes(state, ops, base)
        #: resident-state tier (round 16): a pinned entry is exempt from
        #: the LRU sweep — the streaming fold keeps its working set live
        #: across fold calls.  Over the pin budget the oldest pinned
        #: entry SPILLS to host numpy copies (spilled=True): device HBM
        #: freed, the next acquire re-uploads from the host copy instead
        #: of repacking — a lost win, never corruption.
        self.pinned = False
        self.spilled = False


def _lineage_gen(meta: dict) -> Optional[int]:
    """The tier-2 pack generation of the host arrays in hand (None when
    tier 2 did not produce them — exact reuse only)."""
    lin = meta.get("_pack_lineage")
    return lin[-1] if lin else None


def _lineage_parent(meta: dict) -> Optional[int]:
    """For a suffix-extended pack, the generation it extended."""
    lin = meta.get("_pack_lineage")
    if lin and lin[0] == "suffix":
        return lin[1]
    return None


# ---------------------------------------------------------------------------
# The merge-tree device-ops instance
# ---------------------------------------------------------------------------


@jax.jit
def _widen_resident_ops(ops: MTOps, doc_base: jnp.ndarray) -> MTOps:
    """In-graph narrow→wide migration of resident op buffers (the
    kernel's own ``_widen_ops`` inverse — exact by construction).  Zero
    bytes cross the link: the whole point is that a chunk whose suffix
    text landed at the shared arena tail (blowing the int16 offset
    bound and flipping the upload encoding wide) can keep splicing
    instead of re-uploading the full planes.  No donation here — an
    int16 buffer cannot alias an int32 output; the narrow originals
    free by refcount the moment the entry swaps."""
    return _widen_ops(ops, doc_base)


@jax.jit
def _widen_resident_state(state: MTState,
                          doc_base: jnp.ndarray) -> MTState:
    """The warm-state twin of :func:`_widen_resident_ops`."""
    return _widen_state(state, doc_base)


class MergeTreeDeviceOps:
    """The merge-tree family's tier-2.5 hooks: int16/int8 narrow
    encodings (with the in-graph narrow→wide migration), a single
    op-row splice axis, and the per-doc arena base as the aux plane."""

    @staticmethod
    def sig(state, ops) -> tuple:
        return tuple_sig(state, ops)

    @staticmethod
    def aux(meta):
        return np.asarray(meta["doc_base"], np.int32)

    @staticmethod
    def t_rows(host_ops) -> np.ndarray:
        return np.count_nonzero(
            np.asarray(host_ops.kind), axis=1).astype(np.int32)

    @staticmethod
    def entry_aux(meta):
        return None

    @staticmethod
    def _widened_sig(sig: tuple) -> tuple:
        """The signature the same arrays would carry in the WIDE (int32)
        transfer encoding — shapes unchanged, every non-bool dtype
        int32."""
        return tuple((f, dt if dt == "bool" else "int32", shape)
                     for f, dt, shape in sig)

    def migrate(self, cache: "DevicePackCache", tokens,
                entry: _ResidentEntry, sig: tuple, docs) -> None:
        if not (entry.sig != sig
                and self._widened_sig(entry.sig) == sig
                and cache.match(entry, docs) is not None):
            return
        # The ONLY signature change is a narrow→wide transfer-
        # encoding flip (full-scale suffix growth does this: the new
        # text lands at the shared arena tail, blowing the int16
        # offset bound).  Migrate the resident buffers to the wide
        # encoding IN-GRAPH — donated, zero bytes over the link —
        # so the window can still serve/splice.
        old_nbytes = entry.nbytes
        entry.ops = _widen_resident_ops(entry.ops, entry.base)
        if entry.state is not None:
            entry.state = _widen_resident_state(entry.state,
                                                entry.base)
        entry.sig = sig
        entry.nbytes = _dev_nbytes(entry.state, entry.ops, entry.base)
        cache.reaccount_migrated(tokens, entry, old_nbytes)

    def splice(self, cache: "DevicePackCache", entry: _ResidentEntry,
               docs, state, ops: MTOps, meta: dict,
               sharding) -> Optional[int]:
        """Upload only the suffix rows and extend the resident op
        buffers via the donated splice; returns uploaded bytes, or None
        when the extension does not apply (caller full-uploads).  The
        base state of a warm chunk is pinned by the token (it derives
        from the base summary alone), so only the op planes move."""
        t_new = self.t_rows(ops)
        t_old = entry.t_rows
        if np.any(t_new < t_old):
            return None
        rows_np, _L = gather_suffix_rows(MTOps, ops, t_old, t_new)
        if rows_np is None:
            return None  # suffix ~ whole buffer: full upload is cheaper
        uploaded = sum(v.nbytes for v in rows_np.values()) \
            + 2 * t_new.nbytes
        rows = MTOps(**{f: cache.put(v, sharding)
                        for f, v in rows_np.items()})
        start = cache.put(t_old, sharding)
        count = cache.put(t_new - t_old, sharding)
        new_ops = splice_row_planes(MTOps, entry.ops, rows, start, count)
        # The donated input buffers are DEAD past this point: the entry
        # swaps in the splice outputs and the old references are never
        # touched again.
        entry.ops = new_ops
        entry.t_rows = t_new
        return int(uploaded)


class DevicePackCache:
    """Byte-bounded LRU of device-resident packed chunk buffers (see the
    module docstring).  ``sharding`` (a ``jax.sharding.NamedSharding``)
    places entries on a mesh — the sharded fold passes its doc-sharded
    placement so mesh and single-device serve the identical tier.
    ``device_ops`` selects the family (default: merge-tree)."""

    def __init__(self, max_bytes: int = 192 << 20, sharding=None,
                 device_ops=None, pin_max_bytes: int = 64 << 20) -> None:
        self.max_bytes = int(max_bytes)
        #: device-byte budget for the PINNED tier (resident doc state of
        #: the streaming fold).  Separate from ``max_bytes`` so a wide
        #: pinned working set cannot starve the ordinary LRU tier, and
        #: vice versa.
        self.pin_max_bytes = int(pin_max_bytes)
        self._fam = device_ops if device_ops is not None \
            else MergeTreeDeviceOps()
        self._lock = threading.Lock()
        # tokens -> _ResidentEntry (insertion order = LRU order)
        self._entries: dict = {}  # guarded-by: _lock
        self._bytes = 0       # device bytes of unspilled entries
        self._host_bytes = 0  # host bytes of spilled entries
        self._pinned_bytes = 0  # device bytes of pinned, unspilled entries
        self._last_epoch = None  # guarded-by: _lock
        self._sharding = sharding
        self.counters = CounterSet(
            "served", "spliced", "misses", "bypass", "inserts",
            "evictions", "invalidations", "bytes_saved",
            "pins", "unpins", "spills", "unspills",
        )  # guarded-by: _lock (CounterSet is not internally synchronized)

    # -- placement -------------------------------------------------------------

    def set_sharding(self, sharding) -> None:
        """Pin the device placement (mesh path; idempotent — NamedSharding
        compares by value).  CHANGING an established placement drops the
        resident entries: buffers laid out for one placement must never
        serve another."""
        with self._lock:
            if sharding is self._sharding or sharding == self._sharding:
                return
            self._sharding = sharding
            dropped = len(self._entries)
            self._entries.clear()
            self._bytes = 0
            self._host_bytes = 0
            self._pinned_bytes = 0
            self.counters.bump("evictions", dropped)

    @staticmethod
    def put(x, sharding):
        # ``sharding`` is the caller's one-per-acquire snapshot (taken
        # under the lock), so one entry can never end up split across
        # placements by a racing set_sharding.
        if sharding is not None:
            return jax.device_put(jnp.asarray(x), sharding)
        return jax.device_put(jnp.asarray(x))

    @classmethod
    def put_tree(cls, tree, sharding):
        if tree is None:
            return None
        return jax.tree.map(lambda leaf: cls.put(leaf, sharding), tree)

    # -- the pinned resident-state tier (round 16) -----------------------------

    def _pool_sub(self, entry: _ResidentEntry) -> None:  # holds-lock
        if entry.spilled:
            self._host_bytes -= entry.nbytes
        else:
            self._bytes -= entry.nbytes
            if entry.pinned:
                self._pinned_bytes -= entry.nbytes

    def _pool_add(self, entry: _ResidentEntry) -> None:  # holds-lock
        if entry.spilled:
            self._host_bytes += entry.nbytes
        else:
            self._bytes += entry.nbytes
            if entry.pinned:
                self._pinned_bytes += entry.nbytes

    def _spill_locked(self, entry: _ResidentEntry) -> None:
        """Move an entry's buffers to host numpy copies (device HBM
        freed by refcount once the caller's references die).  Holds
        _lock; MUST run on the device-interaction thread (d2h)."""
        self._pool_sub(entry)
        entry.state = jax.tree.map(np.asarray, entry.state) \
            if entry.state is not None else None
        entry.ops = jax.tree.map(np.asarray, entry.ops)
        entry.base = jax.tree.map(np.asarray, entry.base) \
            if entry.base is not None else None
        entry.spilled = True
        self._pool_add(entry)
        self.counters.bump("spills")

    def _enforce_pin_budget(self, keep) -> None:  # holds-lock: _lock
        """Spill oldest pinned entries until the pinned tier fits its
        device-byte budget; ``keep`` (a tokens key) is spilled LAST —
        it is the entry the caller is actively serving."""
        while self._pinned_bytes > self.pin_max_bytes:
            victim = next(
                (k for k, e in self._entries.items()
                 if e.pinned and not e.spilled and k != keep), None)
            if victim is None:
                victim = keep if keep in self._entries else None
                if victim is None or self._entries[victim].spilled:
                    break
            self._spill_locked(self._entries[victim])

    def pin(self, tokens) -> bool:
        """Mark the chunk's resident entry as pinned doc state: exempt
        from the LRU sweep, budgeted by ``pin_max_bytes`` with
        spill-to-host (oldest-pinned-first) when the pinned set grows
        past it.  Returns False when no entry exists for ``tokens``.
        MUST be called from the device-interaction thread (a budget
        overflow spills — a d2h copy)."""
        with self._lock:
            entry = self._entries.get(tokens)
            if entry is None:
                return False
            if not entry.pinned:
                entry.pinned = True
                if not entry.spilled:
                    self._pinned_bytes += entry.nbytes
                self.counters.bump("pins")
                self._enforce_pin_budget(tokens)
            return True

    def unpin(self, tokens) -> bool:
        """Return a pinned entry to ordinary LRU life (a spilled one
        stays spilled until its next acquire re-uploads it)."""
        with self._lock:
            entry = self._entries.get(tokens)
            if entry is None or not entry.pinned:
                return False
            entry.pinned = False
            if not entry.spilled:
                self._pinned_bytes -= entry.nbytes
            self.counters.bump("unpins")
            return True

    def _restore_spilled(self, entry: _ResidentEntry, sharding) -> int:
        """Re-upload a spilled entry's host copies (the spill's other
        half).  Returns the h2d bytes.  Caller thread = device thread;
        the lock is NOT held across the uploads (they are slow) — the
        entry object is private to the acquiring thread by the tier's
        single-device-thread contract."""
        entry.state = self.put_tree(entry.state, sharding)
        entry.ops = self.put_tree(entry.ops, sharding)
        entry.base = self.put_tree(entry.base, sharding)
        with self._lock:
            if self._entries.get(entry.tokens) is entry:
                self._host_bytes -= entry.nbytes
                entry.spilled = False
                self._bytes += entry.nbytes
                if entry.pinned:
                    self._pinned_bytes += entry.nbytes
                    self._enforce_pin_budget(entry.tokens)
                self._sweep_unpinned(keep=entry.tokens)
            else:
                entry.spilled = False
            self.counters.bump("unspills")
        return entry.nbytes

    def _sweep_unpinned(self, keep=None) -> None:  # holds-lock: _lock
        """Evict oldest UNPINNED entries until the device pool fits —
        the LRU sweep of the cache tier; the pinned tier never evicts
        (it spills instead, on its own budget)."""
        while self._bytes > self.max_bytes:
            victim = next(
                (k for k, e in self._entries.items()
                 if not e.pinned and not e.spilled and k != keep), None)
            if victim is None:
                break
            self._pool_sub(self._entries.pop(victim))
            self.counters.bump("evictions")

    # -- introspection ---------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            out = self.counters.snapshot()
            out["entries"] = len(self._entries)
            out["bytes"] = self._bytes
            out["pinned_entries"] = sum(
                1 for e in self._entries.values() if e.pinned)
            out["pinned_bytes"] = self._pinned_bytes
            out["spilled_bytes"] = self._host_bytes
        return out

    # -- the dispatch-side handshake -------------------------------------------

    def acquire(self, state, ops, meta: dict, pin: bool = False):
        """Device-resident ``(state, ops, aux, h2d_bytes)`` for a packed
        chunk about to dispatch: the resident buffers on an exact hit
        (zero upload), a donated suffix splice on a lineage-proven
        extension, else a full upload that (re)stores the entry.
        Token-less chunks bypass and return the host
        arrays unchanged (``aux=None`` — the dispatcher derives it as
        before); ``h2d_bytes`` is what this call actually put on the
        link.  MUST be called from the single device-interaction thread
        (the pipeline's dispatch leg / the mesh fold).

        ``pin=True`` (the streaming fold) additionally pins the served
        entry into the resident-state tier — see :meth:`pin`.  A spilled
        pinned entry that matches is restored by re-uploading its host
        copies (cheaper than a repack; counted in ``h2d_bytes``)."""
        docs = meta["docs"]
        tokens = tuple(d.cache_token for d in docs)
        if any(t is None for t in tokens):
            with self._lock:
                self.counters.bump("bypass")
            return state, ops, None, _np_nbytes(state) + _np_nbytes(ops)
        sig = self._fam.sig(state, ops)
        full_bytes = _np_nbytes(state) + _np_nbytes(ops)
        with self._lock:
            entry = self._entries.get(tokens)
            sharding = self._sharding
        if entry is not None and entry.sig != sig and not entry.spilled:
            self._fam.migrate(self, tokens, entry, sig, docs)
        if entry is not None and entry.sig == sig:
            kind = self.match(entry, docs)
            restored = 0
            if kind is not None and entry.spilled:
                restored = self._restore_spilled(entry, sharding)
            if kind == "exact":
                with self._lock:
                    self._touch(tokens)
                    self.counters.bump("served")
                    self.counters.bump("bytes_saved",
                                       max(0, full_bytes - restored))
                gen = _lineage_gen(meta)
                if gen is not None:
                    # Content is equal either way; tracking the freshest
                    # tier-2 generation keeps future suffix lineage
                    # checks matching.
                    entry.gen = gen
                if pin:
                    self.pin(tokens)
                return entry.state, entry.ops, entry.base, restored
            if kind == "suffix" and entry.gen is not None \
                    and _lineage_parent(meta) == entry.gen:
                uploaded = self._fam.splice(self, entry, docs, state,
                                            ops, meta, sharding)
                if uploaded is not None:
                    uploaded += restored
                    self._refresh_windows(entry, docs, meta)
                    with self._lock:
                        self._touch(tokens)
                        self.counters.bump("spliced")
                        self.counters.bump("bytes_saved",
                                           max(0, full_bytes - uploaded))
                    if pin:
                        self.pin(tokens)
                    return entry.state, entry.ops, entry.base, uploaded
        # Miss / signature moved / unprovable lineage: full upload.
        with self._lock:
            self.counters.bump("misses")
        state_dev = self.put_tree(state, sharding)
        ops_dev = self.put_tree(ops, sharding)
        aux_host = self._fam.aux(meta)
        base_dev = self.put_tree(aux_host, sharding)
        self._store(tokens, docs, sig, _lineage_gen(meta), state_dev,
                    ops_dev, base_dev, ops, meta, pin=pin)
        base_bytes = _np_nbytes(tuple(jax.tree.leaves(aux_host)))
        return state_dev, ops_dev, base_dev, full_bytes + base_bytes

    # -- matching --------------------------------------------------------------

    @staticmethod
    def match(entry: _ResidentEntry, docs) -> Optional[str]:
        """The shared tier-2/2.5 window rule (``match_windows``) over
        the resident entry's bookkeeping."""
        return match_windows(entry.n_ops, entry.first_seq,
                             entry.last_seq, docs)

    def _refresh_windows(self, entry: _ResidentEntry, docs,
                         meta: dict) -> None:
        """After a successful splice: advance the entry's window
        bookkeeping, lineage generation, and family aux counts to the
        combined (extended) chunk."""
        n_ops, first_seq, last_seq = [], [], []
        for doc in docs:
            n, first, last = doc_window(doc)
            n_ops.append(n)
            first_seq.append(first)
            last_seq.append(last)
        entry.n_ops = n_ops
        entry.first_seq = first_seq
        entry.last_seq = last_seq
        entry.gen = _lineage_gen(meta)
        entry.aux = self._fam.entry_aux(meta)

    # -- bookkeeping -----------------------------------------------------------

    def reaccount_migrated(self, tokens, entry: _ResidentEntry,
                           old_nbytes: int) -> None:
        """Re-account an encoding-migrated entry (~2× the bytes) in
        ONE identity-guarded critical section: the adjustment applies
        only if the map still holds THE entry that was migrated, and the
        LRU sweep rebalances the budget (the migrated entry itself is
        never evicted mid-serve — if it alone exceeds the budget it is
        un-mapped, same policy as _store's never-admit rule, while this
        call keeps serving its arrays)."""
        with self._lock:
            if self._entries.get(tokens) is not entry:
                return
            delta = entry.nbytes - old_nbytes
            self._bytes += delta
            if entry.pinned:
                self._pinned_bytes += delta
                self._enforce_pin_budget(tokens)
            self._sweep_unpinned(keep=tokens)
            if self._bytes > self.max_bytes and not entry.pinned:
                self._pool_sub(self._entries.pop(tokens))
                self.counters.bump("evictions")

    def _touch(self, tokens) -> None:  # holds-lock: _lock
        entry = self._entries.pop(tokens, None)
        if entry is not None:
            self._entries[tokens] = entry

    def _store(self, tokens, docs, sig, gen, state_dev, ops_dev, base_dev,
               host_ops, meta: dict, pin: bool = False) -> None:
        n_ops, first_seq, last_seq = [], [], []
        for doc in docs:
            n, first, last = doc_window(doc)
            n_ops.append(n)
            first_seq.append(first)
            last_seq.append(last)
        t_rows = self._fam.t_rows(host_ops)
        entry = _ResidentEntry(tokens, n_ops, first_seq, last_seq, t_rows,
                               sig, gen, state_dev, ops_dev, base_dev,
                               aux=self._fam.entry_aux(meta))
        with self._lock:
            old = self._entries.pop(tokens, None)
            if old is not None:
                self._pool_sub(old)
                # A re-store inherits the old entry's pin: the pin names
                # the DOC's resident state, not one encoding of it.
                entry.pinned = old.pinned
            entry.pinned = entry.pinned or pin
            if entry.nbytes > self.max_bytes:
                self.counters.bump("evictions")
                return
            self._entries[tokens] = entry
            self._bytes += entry.nbytes
            if entry.pinned:
                self._pinned_bytes += entry.nbytes
                if pin and (old is None or not old.pinned):
                    self.counters.bump("pins")
                self._enforce_pin_budget(tokens)
            self.counters.bump("inserts")
            self._sweep_unpinned(keep=tokens)

    # -- epoch invalidation ----------------------------------------------------

    def invalidate_epoch(self, current_epoch: str) -> int:
        """Drop entries holding any token pinned to a DIFFERENT storage
        generation (token component 0 is the epoch — same contract as
        tiers 0/1, riding the same server-side sweep).  O(1) while the
        epoch is unchanged."""
        with self._lock:
            if current_epoch == self._last_epoch:
                return 0
            self._last_epoch = current_epoch
            stale = [key for key in self._entries
                     if any(tok[0] != current_epoch for tok in key)]
            for key in stale:
                # Pins do not survive an epoch flip: the pinned state
                # was derived under the dead storage generation.
                self._pool_sub(self._entries.pop(key))
                self.counters.bump("invalidations")
        return len(stale)
